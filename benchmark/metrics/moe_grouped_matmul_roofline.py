"""Least time the chip could take for the grouped-matmul kernel's calls of the
traced window over their summed device time. The calls are found in the trace
by the kernel's own name (``grouped_matmul``). What they had to do is the
routing's own fact, from the program's spans that started in the traced part of
the window (the driver's ``trace_t0`` / ``trace_t1``): ``serving.cb.chunk`` and
``serving.cb.prefill`` carry ``local_picks`` (the (token, held expert) pairs of
the pass) and ``experts_hit`` ((layer, held expert) with at least one pair, a
chunk's summed over its token-steps). Per span the larger of the pairs' FLOPs
over the bf16 peak and the bytes of the experts hit and of the rows over the HBM
bandwidth (benchmark/flops_pangu.py); a span's calls together can take no
less. A pass launched in the last milliseconds of the traced part runs its
kernels after it."""

import flops_pangu
import program_spans as ps

KERNEL = "grouped_matmul"


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def passes(run) -> list:
    """(pairs, experts hit) of each pass that started in the traced part."""
    w = run["window"]
    if "trace_t0" not in w:
        return []
    out = []
    for name in ("serving.cb.chunk", "serving.cb.prefill"):
        for s in ps.spans(run, name, in_window=False):
            a = s["attrs"]
            if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and "local_picks" in a and "experts_hit" in a:
                out.append((int(a["local_picks"]), int(a["experts_hit"])))
    return out


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or "router_width" not in run["ctx"].config:
        return None
    cfg = run["ctx"].config
    done, spent = passes(run), kernel_seconds(t)
    if not done or spent <= 0.0:
        return None
    least = 0.0
    for pairs, hit in done:
        fl, by = flops_pangu.grouped_matmul_cost(cfg, pairs, hit)
        least += max(fl / peaks["bf16_flops_per_s"], by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent

"""Least time the chip could take for the prefill attention kernel's calls of
the traced window over their summed device time. The calls are found in the
trace by the kernel's own name (``flash_attention_rows``: one a layer a prefill
or suffix pass). Their shapes come from the program's own span
``serving.cb.prefill`` (``prompt_len`` and ``shared``: the pass computes the
tokens between) for the spans that started in the traced part of the window
(the driver's ``trace_t0`` / ``trace_t1``). Per call the larger of FLOPs over
the bf16 peak and bytes over the HBM bandwidth (benchmark/flops_trinity.py),
over the VISIBLE (query, key) pairs only: a window layer's query counts
``min(t + 1, sliding_window)`` keys. A kernel that visits blocks behind the
horizon reads LOW; one that skips work it owes cannot read over 100. A pass
launched in the last part of the traced window runs its kernels after it, and
one launched before it runs inside: with passes of up to 16 k tokens the two
ends do not cancel in one run as they do over several."""

import flops_trinity
import program_spans as ps

KERNEL = "flash_attention_rows"


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def passes(run) -> list:
    """(first position, tokens) of each prefill pass launched in the traced part."""
    w = run["window"]
    if "trace_t0" not in w:
        return []
    out = []
    for s in ps.spans(run, "serving.cb.prefill", in_window=False):
        a = s["attrs"]
        if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and "prompt_len" in a and "shared" in a:
            out.append((int(a["shared"]), int(a["prompt_len"]) - int(a["shared"])))
    return out


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or "sliding_window" not in run["ctx"].config:
        return None
    cfg = run["ctx"].config
    done, spent = passes(run), kernel_seconds(t)
    if not done or spent <= 0.0:
        return None
    least = 0.0
    for first, n in done:
        for kind in ("full", "window"):
            fl, by = flops_trinity.flash_rows_cost(cfg, first, n, kind == "window")
            least += flops_trinity.n_layers_of(cfg, kind) * max(fl / peaks["bf16_flops_per_s"],
                                                                by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent

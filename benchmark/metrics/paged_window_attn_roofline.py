"""Least time the chip could take for the paged decode kernel's calls of the
traced window over their summed device time. The calls are found in the trace
by the kernel's own name (``paged_attention``: ``trace/reduce.py`` labels every
Mosaic call ``<name>[mosaic:..]``): one a layer a token-step. What they had to
read is the program's own fact, from its spans ``serving.cb.chunk`` that
started in the traced part of the window (the driver's ``trace_t0`` /
``trace_t1``): ``kv_tokens_full`` and ``kv_tokens_window``, the (row, key)
pairs a chunk's token-steps see in ONE layer of each kind (a window layer's row
at most ``sliding_window``). Per span and layer the larger of the pairs' FLOPs
over the bf16 peak and their K and V bytes over the HBM bandwidth
(benchmark/flops_trinity.py). A kernel that walks a window layer's pages from 0
reads LOW; one that skips pages it owes cannot pass 100 %."""

import flops_trinity
import program_spans as ps

KERNEL = "paged_attention"


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def chunks(run) -> list:
    """(full keys, window keys, rows x steps) of each chunk launched in the traced part."""
    w = run["window"]
    if "trace_t0" not in w:
        return []
    out = []
    for s in ps.spans(run, "serving.cb.chunk", in_window=False):
        a = s["attrs"]
        if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and "kv_tokens_full" in a and "kv_tokens_window" in a:
            out.append((int(a["kv_tokens_full"]), int(a["kv_tokens_window"]), int(a.get("slots", 0)) * w["decode_chunk"]))
    return out


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or "sliding_window" not in run["ctx"].config:
        return None
    cfg = run["ctx"].config
    done, spent = chunks(run), kernel_seconds(t)
    if not done or spent <= 0.0:
        return None
    least = 0.0
    for full, window, rows in done:
        for kind, keys in (("full", full), ("window", window)):
            fl, by = flops_trinity.paged_attention_cost(cfg, keys, rows)
            least += flops_trinity.n_layers_of(cfg, kind) * max(fl / peaks["bf16_flops_per_s"],
                                                                by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent

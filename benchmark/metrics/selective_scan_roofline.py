"""Least time the chip could take for the selective-scan kernel's calls of the
traced window (per call the larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth, from the call's shapes: benchmark/flops_jamba.py) over their
summed device time. The calls are found in the trace by the kernel's own name
(``trace/reduce.py`` labels every Mosaic call ``<name>[mosaic:..]``). Their
shapes, which the device trace does not keep, come from the program's own span
``serving.cb.prefill`` (attributes ``prompt_len`` and ``shared``: the pass
computes their difference, padded to its 16-token bucket; one call a Mamba
layer a pass), for the spans that started in the traced part of the window by
the host clock the driver kept (``trace_t0`` / ``trace_t1``). A pass launched
in the last milliseconds of the traced part runs its kernels after it: about
one pass in a hundred. The table of peaks has no number for the vector unit
that bounds this kernel: by these two bounds it is HBM's, and a low share says
the kernel is not."""

import flops_jamba
import program_spans as ps

KERNEL = "selective_scan"
BUCKET = 16


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def pass_tokens(run) -> list:
    """Tokens each prefill pass of the traced part computed, bucketed as the engine pads them."""
    w = run["window"]
    if "trace_t0" not in w:
        return []
    out = []
    for s in ps.spans(run, "serving.cb.prefill", in_window=False):
        a = s["attrs"]
        if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and "prompt_len" in a and "shared" in a:
            n = int(a["prompt_len"]) - int(a["shared"])
            out.append(-(-n // BUCKET) * BUCKET)
    return out


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or "mamba_d_state" not in run["ctx"].config:
        return None
    cfg = run["ctx"].config
    passes, spent = pass_tokens(run), kernel_seconds(t)
    if not passes or spent <= 0.0:
        return None
    least = 0.0
    for tokens in passes:
        fl, by = flops_jamba.scan_call_cost(cfg, tokens)
        least += flops_jamba.n_layers(cfg, "mamba") * max(fl / peaks["bf16_flops_per_s"], by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent

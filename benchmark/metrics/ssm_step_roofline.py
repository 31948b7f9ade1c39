"""Least time the chip could take for the one-token state-update kernel's calls
of the traced window (the bytes of the state it must move over the HBM
bandwidth) over their summed device time. The calls are found in the trace by
the kernel's own name, ``ssm_step`` (``trace/reduce.py`` labels every Mosaic
call ``<name>[mosaic:..]``), the way ``selective_scan_roofline`` finds its
kernel. What they must move comes from the program's own span
``serving.cb.chunk`` (attribute ``state_slots``: the live slots of the chunk,
each of whose ``h`` every Mamba layer reads and writes once a token-step), for
the spans that started in the traced part of the window by the host clock
(``trace_t0`` / ``trace_t1`` of the run's window): ``state_slots x decode_chunk x
Mamba layers x 2 x d_state x d_inner x 4`` bytes. Only the live slots count,
so a kernel that touched the dead slots' state reads lower, never higher. The
kernel leaves the conv tail to the program (``fedml_tpu/ops/ssm_step.py``), so
its bytes are not counted. A program without the kernel (or without the
attribute) gives nothing."""

import flops_jamba
import program_spans as ps

KERNEL = "ssm_step"


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def slot_steps(run) -> int:
    """Live slots x token-steps of the chunks launched in the traced part."""
    w = run["window"]
    if "trace_t0" not in w:
        return 0
    return sum(int(s["attrs"]["state_slots"]) * int(w["decode_chunk"])
               for s in ps.spans(run, "serving.cb.chunk", in_window=False)
               if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and "state_slots" in s["attrs"])


def slot_bytes(cfg: dict) -> int:
    """One slot's ``h`` in one layer, read and written: float32 ``[d_state, d_inner]`` each way."""
    return 2 * cfg["mamba_d_state"] * flops_jamba.d_inner(cfg) * 4


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or "mamba_d_state" not in run["ctx"].config:
        return None
    cfg = run["ctx"].config
    steps, spent = slot_steps(run), kernel_seconds(t)
    if not steps or spent <= 0.0:
        return None
    least = steps * flops_jamba.n_layers(cfg, "mamba") * slot_bytes(cfg) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / spent

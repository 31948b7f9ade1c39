"""Bytes one decode token-step of a model with window layers and routed experts
MUST read (benchmark/flops_trinity.py: the non-expert weights once, one expert's
matrices for each (layer, expert) HIT that step, K and V of the keys the live
rows SEE: every key in a full layer, ``min(len, sliding_window)`` a row in a
window layer) over the HBM bandwidth, over the device time of a token-step in
the trace: the decode program's device seconds over (its executions x
decode_chunk), the program found by its own name (``jit_paged_step``: where
prefills of 16 k tokens run beside it, "the longest program that ran once a
chunk" is a prefill). The keys
seen and the experts hit are the program's own facts: the means over the
``serving.cb.chunk`` spans that started in the TRACED part of the window (the
driver's ``trace_t0`` / ``trace_t1``: above the knee the live rows climb
through the run, so the window's mean is not the traced part's) of their
attributes ``kv_tokens_full`` / ``kv_tokens_window`` / ``experts_hit`` (each
summed over the chunk's token-steps) over decode_chunk. A program that reads a
window layer's whole prefix reads lower; one that skips what it owes cannot
pass 100 %."""

import flops_trinity
import program_spans as ps

DECODE_PROGRAM = "jit_paged_step"  # the profiler may append an id: matched by prefix
ATTRS = ("kv_tokens_full", "kv_tokens_window", "experts_hit")


def per_step(run):
    """Mean (full keys, window keys, experts hit) a token-step, from the chunk spans of the traced part."""
    w = run["window"]
    if "trace_t0" not in w:
        return None
    rows = [[s["attrs"][a] for a in ATTRS] for s in ps.spans(run, "serving.cb.chunk", in_window=False)
            if w["trace_t0"] <= s["start_s"] <= w["trace_t1"] and all(a in s["attrs"] for a in ATTRS)]
    if not rows:
        return None
    c = run["window"]["decode_chunk"]
    return tuple(sum(r[i] for r in rows) / len(rows) / c for i in range(len(ATTRS)))


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or not t.get("chunks") or "sliding_window" not in run["ctx"].config:
        return None
    ctx, w = run["ctx"], run["window"]
    seen = per_step(run)
    mods = ctx.tracer.reduce.module_seconds(t["raw"], t["lo"], t["hi"])
    runs = sum(n for name, (n, _) in mods.items() if name.startswith(DECODE_PROGRAM))
    seconds = sum(sec for name, (_, sec) in mods.items() if name.startswith(DECODE_PROGRAM))
    if not runs or seconds <= 0.0 or seen is None:
        return None
    step_s = seconds / (runs * w["decode_chunk"])
    least = flops_trinity.decode_step_bytes(ctx.config, *seen) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s

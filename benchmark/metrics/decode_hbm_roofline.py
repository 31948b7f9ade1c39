"""Bytes one decode token-step must read (every block and head weight once,
plus the keys and values of the tokens live, from engine.stats()) over the HBM
bandwidth, over the device time of a token-step in the trace: the decode
program's device seconds over (its executions x decode_chunk). HBM bounds it."""

import flops


def decode_module(mods: dict, chunks: int):
    """The traced program that ran once per decode chunk (within 2), the
    longest such."""
    near = {k: v for k, v in mods.items() if abs(v[0] - chunks) <= 2}
    return max(near.items(), key=lambda kv: kv[1][1]) if near else None


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or not t.get("chunks"):
        return None
    ctx, w = run["ctx"], run["window"]
    mods = ctx.tracer.reduce.module_seconds(t["raw"], t["lo"], t["hi"])
    found = decode_module(mods, t["chunks"])
    if found is None or not w["kv_tokens_live"]:
        return None
    _, (runs, seconds) = found
    step_s = seconds / (runs * w["decode_chunk"])
    live = sum(w["kv_tokens_live"]) / len(w["kv_tokens_live"])
    least = flops.decode_step_bytes(ctx.config, live) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s

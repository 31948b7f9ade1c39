"""Bytes one decode token-step of a hybrid model must move
(benchmark/flops_jamba.py: every weight once with the tied embedding as the
head, the keys and values live in the attention layers, each LIVE slot's
recurrent state read and written; live tokens and slots from engine.stats())
over the HBM bandwidth, over the device time of a token-step in the trace: the
decode program's device seconds over (its executions x decode_chunk), the
program found as ``decode_hbm_roofline`` finds it."""

import os

import flops_jamba
import harness

dense = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "decode_hbm_roofline.py"))


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or not t.get("chunks"):
        return None
    ctx, w = run["ctx"], run["window"]
    if "mamba_d_state" not in ctx.config:
        return None
    mods = ctx.tracer.reduce.module_seconds(t["raw"], t["lo"], t["hi"])
    found = dense.decode_module(mods, t["chunks"])
    if found is None or not w["kv_tokens_live"] or not w["slots_active"]:
        return None
    _, (runs, seconds) = found
    step_s = seconds / (runs * w["decode_chunk"])
    live = sum(w["kv_tokens_live"]) / len(w["kv_tokens_live"])
    slots = sum(w["slots_active"]) / len(w["slots_active"])
    least = flops_jamba.decode_step_bytes(ctx.config, live, slots) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s

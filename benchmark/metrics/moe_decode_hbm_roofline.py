"""Bytes one decode token-step of a model with routed experts MUST read
(benchmark/flops_pangu.py: the non-expert weights once, one expert's matrices
for each (layer, held expert) HIT that step, the latents of the tokens live)
over the HBM bandwidth, over the device time of a token-step in the trace: the
decode program's device seconds over (its executions x decode_chunk), the
program found as ``decode_hbm_roofline`` finds it. The experts hit a step are
the routing's own fact: the mean over the window's ``serving.cb.chunk`` spans
of their attribute ``experts_hit`` (summed over the chunk's token-steps and
layers) over decode_chunk. An implementation that reads every held expert
whatever the routing reads lower, one that skips the unhit cannot pass 100 %."""

import os

import flops_pangu
import harness
import program_spans as ps

dense = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "decode_hbm_roofline.py"))


def experts_hit_per_step(run):
    """Mean (layer, held expert) pairs hit a token-step, from the chunk spans of the window."""
    hits = [s["attrs"]["experts_hit"] for s in ps.spans(run, "serving.cb.chunk") if "experts_hit" in s["attrs"]]
    return sum(hits) / len(hits) / run["window"]["decode_chunk"] if hits else None


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or not t.get("chunks"):
        return None
    ctx, w = run["ctx"], run["window"]
    if "kv_lora_rank" not in ctx.config or "router_width" not in ctx.config:
        return None
    hit = experts_hit_per_step(run)
    mods = ctx.tracer.reduce.module_seconds(t["raw"], t["lo"], t["hi"])
    found = dense.decode_module(mods, t["chunks"])
    if found is None or hit is None or not w["kv_tokens_live"]:
        return None
    _, (runs, seconds) = found
    step_s = seconds / (runs * w["decode_chunk"])
    live = sum(w["kv_tokens_live"]) / len(w["kv_tokens_live"])
    least = flops_pangu.decode_step_bytes(ctx.config, live, hit) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s

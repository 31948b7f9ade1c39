"""Least time the chip could take for the Mosaic flash-attention calls of the
traced steps (the larger of FLOPs over the bf16 peak and bytes over the HBM
bandwidth, from the calls' shapes: benchmark/flops.py) over their summed device
time. Compute bounds it at 2,048 tokens (about 1 kFLOP a byte)."""

import re

import flops

# the reducer labels a Mosaic call "<name>[mosaic:<fwd|dq|dkv>]" (trace/reduce.py)
KERNEL = re.compile(r"\[mosaic:(fwd|dq|dkv)\]$")


def kind_of(label: str):
    m = KERNEL.search(label)
    return m.group(1) if m else None


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None:
        return None
    ctx = run["ctx"]
    batch = int(ctx.workload["program"]["batch_sequences"])
    seq = int(ctx.traffic["seq_len"])
    layers, steps = ctx.config["num_hidden_layers"], t["steps"]
    remat = bool(ctx.workload["program"].get("remat"))
    calls = {"fwd": layers * steps * (2 if remat else 1), "dq": layers * steps, "dkv": layers * steps}
    spent = {"fwd": 0.0, "dq": 0.0, "dkv": 0.0}
    for name, sec in t["op_seconds"].items():
        k = kind_of(name)
        if k is not None:
            spent[k] += sec
    if min(spent.values()) <= 0.0:
        return None  # a kernel is off the path or not found by name: say nothing
    least = 0.0
    for kind, n in calls.items():
        fl, by = flops.flash_call_cost(ctx.config, kind, batch, seq)
        least += n * max(fl / peaks["bf16_flops_per_s"], by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / sum(spent.values())

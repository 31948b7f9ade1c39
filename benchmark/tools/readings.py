#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip, at a
cell's own size, many seeds in ONE process (set-up and compiles shared).

    python3 benchmark/tools/readings.py --workload <cell> --seeds 12 --control 3 [--seconds S]

For each seed: the program against the plain reference (the lower readings).
For the first ``--control`` seeds also the control (the reference in the
program's place with int8 matmul operands) and, for a training cell, the fault
'half of the batch left out' planted in the reference. One JSON line per
reading goes to ``chiprun_out/readings_<cell>.jsonl``; the summary prints last.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402


def seed_list(n: int):
    return [(2**31 + 101) if i == 0 else 7919 * i * i + 104729 * i + 13 for i in range(n)]


def train_readings(ctx, drv, seed: int, control: bool):
    import jax.numpy as jnp

    import weights

    ctx.seed = seed
    p, tr, c = ctx.workload["program"], ctx.traffic, ctx.config
    batches = [traffic.packed_batch(tr, seed, i, int(p["batch_sequences"]), int(c["vocab_size"]))
               for i in range(drv.CHECK_STEPS)]
    trainer = drv.build_trainer(ctx)
    shapes = drv.param_shapes(trainer, int(tr["seq_len"]))
    params = weights.make_params(shapes, seed, jnp.float32)
    trainer._build(params)
    del params
    program = drv.first_steps(ctx, trainer, batches)
    trainer.params = trainer.opt_state = trainer._step_fn = None
    del trainer
    gc.collect()
    ref = drv.follow_reference(ctx, shapes, batches)
    rows = {"program": _judge(drv, program, ref)}
    if control:
        rows["control_int8"] = _judge(drv, drv.follow_reference(ctx, shapes, batches, quant=reference.int8_quant), ref)
        half = list(range(int(p["batch_sequences"]) // 2))
        rows["fault_half_batch"] = _judge(drv, drv.follow_reference(ctx, shapes, batches, keep_rows=half), ref)
    return rows


def _judge(drv, got, ref):
    v = compare.Verdict()
    drv.judge(v, got, ref, {})
    out = {k: r["value"] for k, r in v.rows.items()}
    out["loss_gap"] = max(out.pop(f"loss{i + 1}_gap") for i in range(drv.CHECK_STEPS))
    return out


def serve_readings(ctx, drv, seed: int, control: bool):
    ctx.seed = seed
    run = drv.run(ctx)
    rows = {"program": {k: r["value"] for k, r in run["verdict"].rows.items()}}
    if control and run["sample"]:
        chk = drv.check_sample(ctx, run["params"], run["sample"], run["requests"], quant=reference.int8_quant)
        rows["control_int8"] = {"widest_logit_gap": chk["control_widest_gap"],
                                "mean_logit_gap": chk["control_mean_gap"], "tokens": chk["tokens"]}
    del run
    gc.collect()
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args()
    cell = harness.Cell(ROOT, args.workload)
    harness.place_compile_cache(ROOT)
    ctx = harness.Ctx(cell, 0, args.seconds, False, T0)
    drv = cell.driver()
    fn = train_readings if cell.workload["driver"] == "llm_train" else serve_readings
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"readings_{args.workload}.jsonl")
    summary = {}
    with open(out_path, "a") as f:
        for i, seed in enumerate(seed_list(args.seeds)):
            rows = fn(ctx, drv, seed, i < args.control)
            for kind, row in rows.items():
                f.write(json.dumps({"seed": seed, "kind": kind, **row}) + "\n")
                f.flush()
                for k, v in row.items():
                    summary.setdefault(kind, {}).setdefault(k, []).append(v)
            ctx.log(f"seed {seed}: {json.dumps(rows)}")
    for kind, cols in summary.items():
        for k, vals in cols.items():
            print(f"{kind:18s} {k:20s} n={len(vals):2d} min {min(vals):.4g} max {max(vals):.4g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Device time of each flash-attention kernel by (block_q, block_k), on the chip.

The table the constants of ``ops/flash_attention.py`` (``LADDER``,
``_LARGEST``) are chosen from. One process, one chip:

    chiprun -- python tools/flash_block_sweep.py [--shapes cell,smoke_mha,smoke_gqa]

Every (kernel, block_q, block_k) is one jitted call of the kernel alone
(``_fwd_impl`` / one result of ``_bwd_impl``), timed over ``--reps`` calls in
flight and checked against the 128x128 result of the same kernel. Rows go to
``chiprun_out/flash_block_sweep.jsonl``; the table is printed at the end. A
block the compiler refuses is a row with ``error``, never a skipped one.
``--parent DIR`` times the same calls through the ``flash_attention.py`` of a
checkout unpacked at DIR (its ``block_q=`` / ``block_k=`` arguments).
"""

from __future__ import annotations

import argparse
import importlib.util
import itertools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

SHAPES = {  # B, T, Hq, Hkv, D
    "cell": (4, 2048, 32, 8, 128),       # mistral7b_lora_pack2k
    # chip_smoke.py's kernel phase, at a batch of 8 for its 1: one sequence is
    # a call of 0.1 ms, under the 0.3 ms it takes the host to dispatch it
    "smoke_mha": (8, 1024, 32, 32, 128),
    "smoke_gqa": (8, 1024, 32, 4, 128),
}
KINDS = ("fwd", "dq", "dkv")


def load_parent(root: str):
    spec = importlib.util.spec_from_file_location(
        "parent_flash_attention", os.path.join(root, "fedml_tpu", "ops", "flash_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="cell,smoke_mha,smoke_gqa")
    ap.add_argument("--rungs", default="128,256,512,1024", help="1,024 is no rung of LADDER: swept to show why")
    ap.add_argument("--kinds", default=",".join(KINDS))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--parent", default="")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "flash_block_sweep.jsonl"))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="interpreted on the CPU at a tiny shape: checks the tool, times mean nothing")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import fedml_tpu.ops.flash_attention as fa

    if args.rehearse_cpu:
        SHAPES["tiny"] = (1, 256, 4, 2, 16)
    elif jax.default_backend() != "tpu":
        print("flash_block_sweep: needs the chip (a CPU time is not a device time)", file=sys.stderr)
        return 2
    parent = load_parent(args.parent) if args.parent else None
    rungs = [int(r) for r in args.rungs.split(",")]
    kinds = args.kinds.split(",")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []

    def calls(mod, kind, bq, bk, Hq, Hkv):
        """The jitted call of one kernel through module ``mod`` (this tree's,
        or the PR 29 signature of a parent checkout's: one block pair for both
        backward kernels, ``interpret`` read inside)."""
        heads = dict(causal=True, Hq=Hq, Hkv=Hkv)
        new = hasattr(mod, "block_sizes")
        if new:
            heads["interpret"] = mod._interpret()
        if kind == "fwd":
            return jax.jit(lambda q, k, v, do, o, lse: mod._fwd_impl(
                q, k, v, block_q=bq, block_k=bk, **heads))

        def bwd(q, k, v, do, o, lse):
            blocks = dict(dq_blocks=(bq, bk), dkv_blocks=(bq, bk)) if new else dict(block_q=bq, block_k=bk)
            dq, dk, dv = mod._bwd_impl(q, k, v, do, o, lse, **blocks, **heads)
            return (dq,) if kind == "dq" else (dk, dv)  # the other call is dead code

        return jax.jit(bwd)

    for shape_name in args.shapes.split(","):
        B, T, Hq, Hkv, D = SHAPES[shape_name]
        ks = jax.random.split(jax.random.PRNGKey(T + Hkv), 4)
        q = jax.random.normal(ks[0], (B * Hq, T, D), jnp.float32).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (B * Hkv, T, D), jnp.float32).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (B * Hkv, T, D), jnp.float32).astype(jnp.bfloat16)
        do = jax.random.normal(ks[3], (B * Hq, T, D), jnp.float32).astype(jnp.bfloat16)
        o, lse = calls(fa, "fwd", 128, 128, Hq, Hkv)(q, k, v, do, None, None)
        operands = (q, k, v, do, o, lse)
        variants = [("change", fa)] + ([("parent", parent)] if parent else [])
        for (who, mod), kind in itertools.product(variants, kinds):
            base = None
            for bq, bk in itertools.product(rungs, rungs):
                if T % bq or T % bk:
                    continue
                row = {"shape": shape_name, "who": who, "kind": kind, "block_q": bq, "block_k": bk}
                try:
                    fn = calls(mod, kind, bq, bk, Hq, Hkv)
                    t0 = time.perf_counter()
                    out = jax.block_until_ready(fn(*operands))
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                    t0 = time.perf_counter()
                    outs = [fn(*operands) for _ in range(args.reps)]
                    jax.block_until_ready(outs)
                    row["ms"] = round(1e3 * (time.perf_counter() - t0) / args.reps, 4)
                    first = jnp.asarray(out[0], jnp.float32)
                    if base is None:
                        base = first
                    row["err_vs_first"] = float(jnp.max(jnp.abs(first - base)) / jnp.max(jnp.abs(base)))
                    del outs, out
                except Exception as e:  # noqa: BLE001 - a refused block is a row of the table
                    row["error"] = repr(e)[:300]
                rows.append(row)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
                print(json.dumps(row), flush=True)

    print("\nshape who kind | block_q x block_k -> ms a call")
    for key, group in itertools.groupby(rows, key=lambda r: (r["shape"], r["who"], r["kind"])):
        cells = [f"{r['block_q']}x{r['block_k']}:" + (f"{r['ms']:.3f}" if "ms" in r else "FAIL") for r in group]
        print(" ".join(key), "|", "  ".join(cells))
    return 0


if __name__ == "__main__":
    sys.exit(main())

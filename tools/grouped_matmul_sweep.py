#!/usr/bin/env python3
"""Device time of the grouped matmul alone by (tm, tk, tn), on the chip.

The table ``ops/grouped_matmul.py``'s ``WHOLE_MATRIX_BYTES`` and
``models/moe.row_tile``'s cap are chosen from. One process, one chip:

    chiprun -- python tools/grouped_matmul_sweep.py [--parent DIR] [--shapes ...]

A shape is one routed layer of a serving cell at one pass: the picks of a
seeded router (``models/moe.route`` over normal logits), laid out by
``models/moe.sort_pairs`` at the row tile ``tm``, then the layer's three
matmuls as the layer makes them (gate, up, ``silu(gate) * up``, down) in ONE
jitted call, timed over ``--reps`` calls in flight. ``blocks`` is ``auto``
(``block_sizes``), ``cut`` (the ``BLOCK_K x BLOCK_N`` cut whatever the size)
or ``<tk>x<tn>`` for both matrices' ``[K, N]`` read as gate's (down takes it
transposed). Every variant is checked against ``grouped_matmul_reference`` on a
sample of its live tiles (the reference gathers a matrix a tile: whole, it fits
no shape here). Rows go to ``chiprun_out/grouped_matmul_sweep.jsonl``; the
table is printed at the end. A block the compiler refuses is a row with
``error``, never a skipped one. ``--parent DIR`` times the same calls through
the ``grouped_matmul.py`` of a checkout unpacked at DIR (its own blocks; it
takes this tree's ``flash_attention`` helpers).
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

TRINITY = dict(d_model=2048, d_ff=1024, held=128, routed=128, top_k=8)  # trinity_mini_longmix_over: every expert held
PANGU = dict(d_model=7680, d_ff=2048, held=16, routed=256, top_k=8)     # pangu_ultra_moe_chat_open: rank 0's 16 of 256
SHAPES = {  # the tokens of one pass
    "trinity_step64": dict(TRINITY, tokens=64),          # a decode step at a full batch
    "trinity_512": dict(TRINITY, tokens=512),
    "trinity_2048": dict(TRINITY, tokens=2048),
    "trinity_8192": dict(TRINITY, tokens=8192),
    "trinity_16640": dict(TRINITY, tokens=16640),        # the longest prefill: 256 + 16,384
    "pangu_step64": dict(PANGU, tokens=64),
    "pangu_1280": dict(PANGU, tokens=1280),              # its longest prefill
}
SAMPLE_TILES = 24


def load_parent(root: str):
    """``root``'s kernel as a module of THIS tree's ``fedml_tpu.ops`` (its relative imports resolve here)."""
    import fedml_tpu.ops  # noqa: F401

    spec = importlib.util.spec_from_file_location(
        "fedml_tpu.ops.parent_grouped_matmul", os.path.join(root, "fedml_tpu", "ops", "grouped_matmul.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tms", default="auto,256",
                    help="row tiles: auto = models/moe.row_tile; a larger one only where that is at its cap of 128")
    ap.add_argument("--blocks", default="auto,cut")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default="")
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "grouped_matmul_sweep.jsonl"))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="interpreted on the CPU at a tiny shape: checks the tool, times mean nothing")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import fedml_tpu.ops.grouped_matmul as gm
    from fedml_tpu.models import moe

    shapes = dict(SHAPES)
    if args.rehearse_cpu:
        shapes = {"tiny_step": dict(d_model=256, d_ff=128, held=4, routed=8, top_k=2, tokens=8),
                  "tiny_prefill": dict(d_model=256, d_ff=128, held=4, routed=8, top_k=2, tokens=600)}
        args.shapes = ",".join(shapes)
    elif jax.default_backend() != "tpu":
        print("grouped_matmul_sweep: needs the chip (a CPU time is not a device time)", file=sys.stderr)
        return 2
    parent = load_parent(args.parent) if args.parent else None
    interpret = gm._interpret()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []

    def layer(matmul):
        """The three matmuls of a routed layer as ``RoutedMoE`` makes them, one jitted call."""
        def run(x, w_gate, w_up, w_down, tile_group, n_live):
            gate = matmul(x, w_gate, tile_group, n_live)
            up = matmul(x, w_up, tile_group, n_live)
            return matmul(jax.nn.silu(gate) * up, w_down, tile_group, n_live)

        return jax.jit(run)

    def variants(shape, auto_tm):
        """(who, tm, blocks label, matmul) of every call to time at ``shape``."""
        D, F = shape["d_model"], shape["d_ff"]
        tms = [auto_tm] + [int(t) for t in args.tms.split(",") if t != "auto" and auto_tm == 128 < int(t)]
        for tm, blocks in itertools.product(tms, args.blocks.split(",")):
            if blocks == "auto":
                by_kn = gm.block_sizes
            elif blocks == "cut":
                def by_kn(K, N, dtype):
                    return gm.cut_blocks(K, N)
            else:
                tk, tn = (int(b) for b in blocks.split("x"))

                def by_kn(K, N, dtype, tk=tk, tn=tn):
                    return (tk, tn) if (K, N) == (D, F) else (tn, tk)
            label = "/".join("x".join(map(str, by_kn(K, N, jnp.bfloat16))) for K, N in ((D, F), (F, D)))

            def matmul(x, w, tg, nl, tm=tm, by_kn=by_kn):
                tk, tn = by_kn(w.shape[1], w.shape[2], w.dtype)
                return gm._tiled_call(x, w, tg, nl, tm=tm, tk=tk, tn=tn, interpret=interpret)

            yield "change", tm, label, matmul
        if parent is not None:
            yield "parent", auto_tm, "its own", lambda x, w, tg, nl: parent._grouped_matmul(
                x, w, tg, nl, tm=auto_tm, interpret=interpret)

    for name in args.shapes.split(","):
        shape = shapes[name]
        D, F, held, T = shape["d_model"], shape["d_ff"], shape["held"], shape["tokens"]
        ks = jax.random.split(jax.random.PRNGKey(args.seed + T + held), 5)
        experts, _ = moe.route(jax.random.normal(ks[0], (T, shape["routed"]), jnp.float32), shape["top_k"], 1.0, True)
        tokens = jax.random.normal(ks[1], (T, D), jnp.float32).astype(jnp.bfloat16)
        w_gate, w_up = (jax.random.normal(k, (held, D, F), jnp.bfloat16) * D ** -0.5 for k in ks[2:4])
        w_down = jax.random.normal(ks[4], (held, F, D), jnp.bfloat16) * F ** -0.5
        auto_tm = moe.row_tile(T, shape["top_k"], shape["routed"])
        laid = {}
        for who, tm, label, matmul in variants(shape, auto_tm):
            if tm not in laid:
                row_token, _, _, tile_group, n_live, load = moe.sort_pairs(
                    experts, jnp.ones((T,), bool), 0, held, tm)
                laid[tm] = (tokens[row_token], tile_group, n_live, int(jnp.sum(load)), int(jnp.sum(load > 0)))
            x, tile_group, n_live, pairs, hit = laid[tm]
            live = int(n_live[0])
            row = {"shape": name, "who": who, "tm": tm, "blocks": label, "tokens": T, "pairs": pairs,
                   "experts_hit": hit, "row_tiles": live, "tiles_laid": int(tile_group.shape[0])}
            try:
                fn = layer(matmul)
                operands = (x, w_gate, w_up, w_down, tile_group, n_live)
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn(*operands))
                row["compile_s"] = round(time.perf_counter() - t0, 2)
                t0 = time.perf_counter()
                for _ in range(args.reps):  # in flight one behind the other; the last is done when all are
                    last = fn(*operands)
                jax.block_until_ready(last)
                row["ms"] = round(1e3 * (time.perf_counter() - t0) / args.reps, 4)
                del last
                # a sample of the live tiles against the plain formulation, matmul by matmul
                pick = jnp.unique(jnp.linspace(0, max(live - 1, 0), min(SAMPLE_TILES, max(live, 1))).astype(jnp.int32))
                rows_of = (pick[:, None] * tm + jnp.arange(tm)[None, :]).reshape(-1)
                every = jnp.asarray([pick.shape[0]], jnp.int32)
                gate = gm.grouped_matmul_reference(x[rows_of], w_gate, tile_group[pick], every, tm=tm)
                up = gm.grouped_matmul_reference(x[rows_of], w_up, tile_group[pick], every, tm=tm)
                want = gm.grouped_matmul_reference(jax.nn.silu(gate) * up, w_down, tile_group[pick], every, tm=tm)
                want, got = want.astype(jnp.float32), out[rows_of].astype(jnp.float32)
                row["err_vs_reference"] = float(
                    jnp.max(jnp.abs(got - want)) / jnp.maximum(jnp.max(jnp.abs(want)), 1e-9))
                del out
            except Exception as e:  # noqa: BLE001 - a refused block is a row of the table
                row["error"] = repr(e)[:300]
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
        del laid

    print("\nshape (pairs, experts hit) | who tm blocks: ms a layer's three matmuls "
          "(live tiles; error against the reference)")
    for key, group in itertools.groupby(rows, key=lambda r: r["shape"]):
        group = list(group)
        print(f"{key} ({group[0]['pairs']}, {group[0]['experts_hit']})")
        for r in group:
            cell = (f"{r['ms']:.3f} ms ({r['row_tiles']} tiles; {r['err_vs_reference']:.1e})" if "error" not in r
                    else "FAIL " + r["error"])
            print(f"    {r['who']} tm={r['tm']} {r['blocks']}: {cell}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

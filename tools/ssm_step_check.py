#!/usr/bin/env python3
"""A Mamba layer's one-token slot step on the chip: the kernel over the live
slots (``ops/ssm_step.ssm_step``) against the plain formulation that reads and
writes every slot (``ssm_step_reference``), at Jamba2-3B's widths (64 slots,
``d_state`` 16, ``d_inner`` 5,120). One process; prints one JSON line a live
count, and writes them to ``chiprun_out/ssm_step_check.jsonl``.

    python3 tools/ssm_step_check.py                           # on one TPU chip
    JAX_PLATFORMS=cpu python3 tools/ssm_step_check.py --tiny  # the same code at a tiny width, interpreted

A case is ``live`` slots of 64 chosen at random. Each formulation runs as a
loop of ``--layers`` steps inside ONE program (a decode step's layers, the
state carried and donated), so a line's us a call is device time, not
dispatch. It also gives the GB/s of the state the kernel must move (the live
slots' ``h`` read and written), the largest gap of ``y`` and of the live
slots' state to the plain formulation after one step, and whether every dead
slot's state kept its bits.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fedml_tpu.ops import ssm_step as st  # noqa: E402

OUT = []


def say(**row):
    OUT.append(row)
    print(json.dumps(row), flush=True)


def inputs(key, B, N, d):
    ks = jax.random.split(key, 7)
    return (jax.random.normal(ks[0], (B, N, d)), jax.nn.softplus(jax.random.normal(ks[1], (B, d)) - 4.0),
            jax.random.normal(ks[2], (B, d)), jax.random.normal(ks[3], (B, N)), jax.random.normal(ks[4], (B, N)),
            -jnp.exp(jax.random.normal(ks[5], (N, d)) * 0.5), jax.random.normal(ks[6], (d,)))


def looped(fn, layers):
    """``layers`` steps in one program, the state carried (and donated)."""
    def run(h, dt, c, b, ct, a, dskip, live):
        def body(_, carry):
            h, acc = carry
            y, h = fn(h, dt, c, b, ct, a, dskip, live)
            return h, acc + y[:, :1]
        return jax.lax.fori_loop(0, layers, body, (h, jnp.zeros((h.shape[0], 1), jnp.float32)))
    return jax.jit(run, donate_argnums=(0,))


def timed(fn, h, rest, reps):
    h, acc = jax.block_until_ready(fn(h, *rest))
    t = time.perf_counter()
    for _ in range(reps):
        h, acc = fn(h, *rest)
    jax.block_until_ready((h, acc))
    return (time.perf_counter() - t) / reps


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="B 8, N 16, d 256, interpreted on the CPU")
    ap.add_argument("--layers", type=int, default=26)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    B, N, d = (8, 16, 256) if args.tiny else (64, 16, 5120)
    counts = (0, 3, 8) if args.tiny else (0, 8, 27, 48, 64)
    rng = np.random.default_rng(args.seed)
    h0, *rest = inputs(jax.random.PRNGKey(args.seed), B, N, d)
    kern, plain = looped(st.ssm_step, args.layers), looped(st.ssm_step_reference, args.layers)
    # the kernel's state is donated, as the engine's pool is: the TPU compiler
    # takes the in-place call only on a donated or loop-carried state
    one_k, one_p = jax.jit(st.ssm_step, donate_argnums=(0,)), jax.jit(st.ssm_step_reference)
    for n in counts:
        live = np.zeros(B, bool)
        live[rng.choice(B, n, replace=False)] = True
        live = jnp.asarray(live)
        (yk, hk), (yp, hp) = one_k(jnp.array(h0), *rest, live), one_p(h0, *rest, live)
        dead = ~np.asarray(live)
        row = dict(live=n, slots=B, d_state=N, d_inner=d, layers=args.layers,
                   y_gap=float(jnp.max(jnp.abs(yk - yp))), h_gap=float(jnp.max(jnp.abs(hk - hp))),
                   dead_bits_kept=bool(np.array_equal(np.asarray(hk)[dead], np.asarray(h0)[dead])))
        sec_k = timed(kern, jnp.array(h0), (*rest, live), args.reps) / args.layers
        sec_p = timed(plain, jnp.array(h0), (*rest, live), args.reps) / args.layers
        must = 2.0 * n * N * d * 4
        row.update(kernel_us=sec_k * 1e6, plain_us=sec_p * 1e6, state_mb_moved=must / 1e6,
                   kernel_gb_per_s=must / sec_k / 1e9 if n else 0.0)
        say(**row)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ssm_step_check.jsonl"), "a") as f:
        for row in OUT:
            f.write(json.dumps(dict(row, device=str(jax.devices()[0].device_kind))) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

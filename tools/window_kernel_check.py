#!/usr/bin/env python3
"""The window's kernels on the chip: parity against their plain formulations and
device time at the serving cell's shapes. One process; prints one JSON line a
case, and writes them to ``chiprun_out/window_kernel_check.jsonl``.

    chiprun -- python3 tools/window_kernel_check.py

* ``flash_attention`` (forward and gradients) with a window below, equal to and
  above a block, and causal-only, against ``xla_attention`` with the mask: T
  2,048, 8,192 and 16,384 at 32 query heads on 4 kv heads of 128 (forward
  timings: what ``_visit_blocks`` costs past T 2,048, with and without a window).
* ``flash_attention_rows`` (a pass over a contiguous row cache at a runtime
  offset) against ``flash_attention_rows_reference`` at a small row, and timed
  at the cell's row of 16,896 for fresh and suffix passes of 512 .. 16,384 tokens.
* ``paged_attention`` with a start a row against ``paged_attention_reference``,
  and timed at 64 rows of mixed lengths, with and without starts.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fedml_tpu.models.transformer import repeat_kv, xla_attention  # noqa: E402
from fedml_tpu.ops import flash_attention as fa  # noqa: E402
from fedml_tpu.ops import paged_attention as pa  # noqa: E402

OUT = []


def say(**row):
    OUT.append(row)
    print(json.dumps(row), flush=True)


def timed(fn, *args, n=5):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


def qkv(key, B, T, H, Hkv, D, dtype):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (B, T, H, D), dtype), jax.random.normal(ks[1], (B, T, Hkv, D), dtype),
            jax.random.normal(ks[2], (B, T, Hkv, D), dtype))


def flash_cases():
    H, Hkv, D = 32, 4, 128
    for T, windows in ((2048, (0, 300, 512, 700, 2048)), (8192, (0, 2048)), (16384, (0, 2048))):
        q, k, v = qkv(jax.random.PRNGKey(T), 1, T, H, Hkv, D, jnp.bfloat16)
        for W in windows:
            fwd = jax.jit(lambda q, k, v, W=W: fa.flash_attention(q, k, v, window=W))
            row = {"kernel": "flash_attention", "T": T, "window": W, "fwd_ms": timed(fwd, q, k, v)}
            if T <= 2048:
                def ref(q, k, v, W=W):
                    kk, vv = repeat_kv(k, v, H)
                    return xla_attention(q, kk, vv, causal=True, window=W)

                loss = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) ** 2))  # noqa: E731
                got = jax.jit(jax.value_and_grad(loss(fwd), (0, 1, 2)))(q, k, v)
                want = jax.jit(jax.value_and_grad(loss(ref), (0, 1, 2)))(q, k, v)
                row["fwd_err"] = float(jnp.max(jnp.abs(fwd(q, k, v).astype(jnp.float32) - jax.jit(ref)(q, k, v).astype(jnp.float32))))
                for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
                    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                    row[name + "_rel_err"] = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            say(**row)


def rows_cases():
    H, Hkv, D = 32, 4, 128
    # parity at a small row: offsets and lengths off the block grid, window below / above a block
    S = 1024
    for T, off, W in ((256, 0, 0), (256, 0, 100), (160, 304, 100), (160, 304, 300), (512, 512, 0), (48, 976, 200)):
        ks = jax.random.split(jax.random.PRNGKey(off + T + W), 3)
        q = jax.random.normal(ks[0], (1, T, H, D), jnp.bfloat16)
        kr = jax.random.normal(ks[1], (1, S, Hkv, D), jnp.bfloat16)
        vr = jax.random.normal(ks[2], (1, S, Hkv, D), jnp.bfloat16)
        got = fa.flash_attention_rows(q, kr, vr, jnp.int32(off), window=W).astype(jnp.float32)
        want = fa.flash_attention_rows_reference(q, kr, vr, jnp.int32(off), window=W).astype(jnp.float32)
        say(kernel="flash_attention_rows", S=S, T=T, offset=off, window=W, err=float(jnp.max(jnp.abs(got - want))))
    # device time at the cell's row
    S = 16896
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    kr = jax.random.normal(ks[0], (1, S, Hkv, D), jnp.bfloat16)
    vr = jax.random.normal(ks[1], (1, S, Hkv, D), jnp.bfloat16)
    for T in (512, 2048, 8192, 16384):
        q = jax.random.normal(jax.random.PRNGKey(T), (1, T, H, D), jnp.bfloat16)
        for off in (0, 256):
            for W in (0, 2048):
                fn = jax.jit(lambda q, kr, vr, o, W=W: fa.flash_attention_rows(q, kr, vr, o, window=W))
                ms = timed(fn, q, kr, vr, jnp.int32(off))
                pairs = sum(min(t + 1, W) if W else t + 1 for t in (off, off + T - 1)) / 2 * T  # a trapezoid: close enough
                say(kernel="flash_attention_rows", S=S, T=T, offset=off, window=W, ms=ms,
                    tflops_per_s=4 * H * D * pairs / (ms * 1e-3) / 1e12)


def paged_cases():
    H, Hkv, D, ps, B = 32, 4, 128, 64, 64
    n_blocks, n_pages, W = 264, 8193, 2048
    rng = np.random.default_rng(0)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    k_pool = jax.random.normal(ks[0], (n_pages, ps, Hkv, D), jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (n_pages, ps, Hkv, D), jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, H, D), jnp.bfloat16)
    lengths = rng.choice([600, 2304, 8448, 16700], B, p=[0.4, 0.3, 0.2, 0.1]).astype(np.int32)
    lengths[0], lengths[1] = 0, 5
    tables = rng.permutation(np.arange(1, n_pages))[:B * n_blocks // 8]
    tables = np.resize(tables, (B, n_blocks)).astype(np.int32)
    starts = np.maximum(lengths - W, 0).astype(np.int32)
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths))
    for name, st in (("from_0", None), ("from_the_horizon", jnp.asarray(starts))):
        fn = jax.jit(lambda *a: pa.paged_attention(*a))
        ref = jax.jit(lambda *a: pa.paged_attention_reference(*a))
        a = args + ((st,) if st is not None else ())
        got, want = fn(*a).astype(jnp.float32), ref(*a).astype(jnp.float32)
        keys = int(np.sum(lengths - (starts if st is not None else 0)))
        ms = timed(fn, *a, n=20)
        say(kernel="paged_attention", walk=name, rows=B, keys=keys, err=float(jnp.max(jnp.abs(got - want))), ms=ms,
            gb_per_s=keys * 2 * Hkv * D * 2 / (ms * 1e-3) / 1e9)


def main() -> int:
    say(device=str(jax.devices()[0].device_kind), platform=jax.default_backend())
    which = sys.argv[1:] or ["flash", "rows", "paged"]
    if "flash" in which:
        flash_cases()
    if "rows" in which:
        rows_cases()
    if "paged" in which:
        paged_cases()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_kernel_check.jsonl"), "a") as f:
        for row in OUT:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

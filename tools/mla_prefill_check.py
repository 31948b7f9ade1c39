#!/usr/bin/env python3
"""A latent layer's prefill attention on the chip: the loop over every key block
(``models/mla.expanded_latent_attention``) against the rows kernel behind one
expansion of the keys and values (``models/mla.kernel_latent_attention``), at
the serving cells' widths and pass lengths. One process; prints one JSON line a
case, and writes them to ``chiprun_out/mla_prefill_check.jsonl``.

    python3 tools/mla_prefill_check.py [--cells kimi,pangu]   # on one TPU chip
    JAX_PLATFORMS=cpu python3 tools/mla_prefill_check.py --tiny   # the same code at a tiny width, interpreted

A case is one pass of ``T`` queries at offset ``off`` of a row of ``S``
positions (``kv_len = off + T``). Each line gives the ms a call of the loop, of
the expansion alone (``expand_latent_rows``), of the kernel alone over the
expanded rows, and of the kernel's whole path; the output's largest gap
between the two paths over the output's scale; and which of the two
``mla.latent_row_attention`` picks at that shape.
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

from fedml_tpu.models import mla  # noqa: E402
from fedml_tpu.ops.flash_attention import flash_attention_rows  # noqa: E402

OUT = []
# heads, kv_lora_rank, qk_nope, qk_rope, v, row, row width; the passes: (T, off)
CELLS = {
    "kimi": ((32, 512, 128, 64, 128, 16896, 640),
             ((128, 256), (256, 256), (512, 256), (512, 0), (1024, 256), (2048, 256), (8192, 256), (16384, 256),
              (16640, 0))),
    "pangu": ((128, 512, 128, 64, 128, 2048, 640),
              ((64, 256), (128, 256), (256, 256), (512, 256), (512, 0), (1024, 256), (1280, 0))),
    "tiny": ((4, 32, 16, 8, 16, 256, 128), ((16, 0), (48, 37), (128, 100))),
}


def say(**row):
    OUT.append(row)
    print(json.dumps(row), flush=True)


def timed(fn, *args, n=5):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3, out


def cell(name, dims, passes):
    H, r, dn, dr, dv, S, W = dims
    dtype = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(S + H), 4)
    lat = jax.random.normal(ks[0], (1, S, W), jnp.float32)
    lat = jnp.where(jnp.arange(W) < r + dr, lat, 0).astype(dtype)
    w_kv = (jax.random.normal(ks[1], (r, H, dn + dv), jnp.float32) * r ** -0.5).astype(dtype)
    kw = dict(rank=r, d_nope=dn)
    loop = jax.jit(lambda q_n, q_r, lat, w, off: mla.expanded_latent_attention(
        q_n, q_r, lat, w, off, off + q_n.shape[1], scale=(dn + dr) ** -0.5, **kw))
    path = jax.jit(lambda q_n, q_r, lat, w, off: mla.kernel_latent_attention(q_n, q_r, lat, w, off, off + q_n.shape[1], **kw))
    expand = jax.jit(lambda lat, w, kv_len: mla.expand_latent_rows(lat, w, kv_len, rank=r, d_nope=dn, d_rope=dr))
    for T, off in passes:
        q_n = jax.random.normal(jax.random.fold_in(ks[2], T), (1, T, H, dn), jnp.float32).astype(dtype)
        q_r = jax.random.normal(jax.random.fold_in(ks[3], T), (1, T, H, dr), jnp.float32).astype(dtype)
        o = jnp.int32(off)
        n = 2 if T * (off + T) * H > 2 ** 32 else 5   # the loop at 16 k takes about half a second
        loop_ms, want = timed(loop, q_n, q_r, lat, w_kv, o, n=n)
        path_ms, got = timed(path, q_n, q_r, lat, w_kv, o, n=n)
        expand_ms, (k, v) = timed(expand, lat, w_kv, jnp.int32(off + T), n=n)
        q = jnp.concatenate([q_n, q_r, jnp.zeros((1, T, H, k.shape[-1] - dn - dr), dtype)], axis=-1)
        # the rows head-major, as the path hands them over: the kernel's own transposes cancel
        kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        kernel = jax.jit(lambda q, kh, vh, o: flash_attention_rows(
            q, kh.transpose(0, 2, 1, 3), vh.transpose(0, 2, 1, 3), o, scale=(dn + dr) ** -0.5))
        kernel_ms, _ = timed(kernel, q, kh, vh, o, n=n)
        want, got = want.astype(jnp.float32), got.astype(jnp.float32)
        say(cell=name, heads=H, S=S, T=T, offset=off, loop_ms=loop_ms, expand_ms=expand_ms, kernel_ms=kernel_ms,
            path_ms=path_ms, speedup=loop_ms / path_ms,
            gap=float(jnp.max(jnp.abs(got - want))), scale=float(jnp.max(jnp.abs(want))),
            rule=mla.latent_row_attention(jax.default_backend(), T, S, H))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="kimi,pangu")
    ap.add_argument("--tiny", action="store_true", help="a tiny width, for a rehearsal on the CPU")
    args = ap.parse_args()
    say(device=str(jax.devices()[0].device_kind), platform=jax.default_backend())
    for name in (["tiny"] if args.tiny else args.cells.split(",")):
        cell(name, *CELLS[name])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mla_prefill_check.jsonl"), "a") as f:
        for row in OUT:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Weights of AI21-Jamba2-3B's parameter tree from ``--seed``: ``weights.py``'s
rules (``kernel``, ``scale``, ``embedding``) and one for every leaf a Mamba
layer adds, by the leaf's name. A leaf's values depend only on the seed and
its path; ONE jitted call makes the tree on the device.

  A_log        log(1..d_state) down the state axis, for every channel
  dt_proj/bias b with softplus(b) log-uniform over 0.001..0.1
  D            1
  conv_kernel  N(0, 1/d_conv);  conv_bias  N(0, 0.1^2)
  embedding    N(0, 0.02^2), NOT weights.py's N(0, 1): the head is tied, and a
               unit-variance embedding makes every token predict itself by
               |e|^2 = hidden_size against sqrt(hidden_size) for the rest, so
               that no fault of any layer could move a served token. At 0.02
               the blocks' outputs carry the residual stream after the first
               layer and the logits have a spread of about 1

The first two are the family's initialisation: with random values there the
state neither decays nor moves, and a wrong recurrence would read as a right
one. ``configs/jamba2-3b.json`` lists them under ``assumed``.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

import weights
from weights import flatten, shapes_of  # noqa: F401  (the drivers' one import)

DT_MIN, DT_MAX = 1e-3, 1e-1
EMBED_STD = 0.02


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "A_log":  # [d_state, d_inner]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))[:, None], shape).astype(dtype)
    if name == "bias" and "dt_proj" in path:
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32) * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1
    if name == "embedding":
        return (EMBED_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if name == "D":
        return jnp.ones(shape, dtype)
    if name == "conv_kernel":  # [d_conv, d_inner]
        return (jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5).astype(dtype)
    if name == "conv_bias":
        return (0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    return weights._leaf(key, path, shape, dtype)


def make_params(shapes: dict, seed: int, dtype):
    """Nested-dict parameter tree for ``shapes`` ({"a/b/kernel": shape})."""

    def build(seed_u32):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed_u32)
        out: dict = {}
        for path, shape in sorted(shapes.items()):
            node = out
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = _leaf(key, path, shape, dtype)
        return out

    return jax.jit(build)(jnp.uint32(int(seed) & 0xFFFFFFFF))

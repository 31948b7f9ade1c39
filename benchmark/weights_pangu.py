"""Weights of openPangu-Ultra-MoE's parameter tree from ``--seed``:
``weights.py``'s rules (``kernel`` variance 1 / fan_in, ``scale`` ones) and one
for every leaf the routed layer adds, by the leaf's name. A leaf's values
depend only on the seed and its path; ONE jitted call makes the tree on the
device.

  router               [hidden, router_width]: variance 1 / hidden, so logits of
                       unit spread: all 256 experts equally likely, the 8 picks
                       about 0.05 apart (configs/openpangu-ultra-moe-718b.json,
                       ``assumed.weights``)
  w_gate, w_up, w_down [held, in, out]: variance 1 / in, as a projection
  embedding            N(0, 0.02^2): untied, the head is a ``kernel`` of its own
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

import weights
from weights import flatten, shapes_of  # noqa: F401  (the drivers' one import)

EMBED_STD = 0.02


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "embedding":
        return (EMBED_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if name in ("router", "w_gate", "w_up", "w_down"):  # fan_in is the second-to-last dim
        return (jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5).astype(dtype)
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

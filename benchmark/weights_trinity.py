"""Weights of Trinity-Mini's parameter tree from ``--seed``: ``weights_pangu``'s
rules (``kernel``, ``router``, the stacked experts variance 1 / fan_in, ``scale``
ones, the untied ``embedding`` N(0, 0.02^2)) and one for the leaf this family
adds. A leaf's values depend only on the seed and its path; ONE jitted call
makes the tree on the device.

  router_bias  [router_width]: N(0, 0.01^2). The selection bias is the load
               balancer's state in a trained checkpoint; zeros would leave the
               mechanism unexercised, and 0.01 moves a pick only where two
               experts' scores are that close (configs/trinity-mini.json,
               ``assumed.router_bias``). Float32 whatever the tree's dtype: it
               is added to float32 scores.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

import weights_pangu
from weights import flatten, shapes_of  # noqa: F401  (the drivers' one import)

BIAS_STD = 0.01


def _leaf(key, path: str, shape, dtype):
    if path.rsplit("/", 1)[-1] == "router_bias":
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        return BIAS_STD * jax.random.normal(k, shape, jnp.float32)
    return weights_pangu._leaf(key, path, shape, dtype)


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

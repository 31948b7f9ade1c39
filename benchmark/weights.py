"""Weights from ``--seed``, made on the device by ONE jitted call.

The benchmark makes the weights, the program and the plain reference both
receive them: neither makes them for the other. A leaf's values depend only
on the seed and the leaf's path, so the reference can make the same tree
again after the program's state has been freed.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

LORA_A_STD = 0.02


def _path(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "lora_b":
        return jnp.zeros(shape, dtype)
    if name == "lora_a":
        return (LORA_A_STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if name == "embedding":  # unit-variance rows: the residual stream starts at RMS 1
        return jax.random.normal(k, shape, dtype)
    if name == "kernel":     # 1/fan_in variance: every projection keeps the scale
        return (jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5).astype(dtype)
    raise ValueError(f"no init rule for leaf {path!r}")


def shapes_of(tree) -> dict:
    """{path: shape} of a pytree of arrays or ShapeDtypeStructs."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path(p): tuple(x.shape) for p, x in flat}


def make_params(shapes: dict, seed: int, dtype, sharding=None):
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

    fn = jax.jit(build) if sharding is None else jax.jit(build, out_shardings=sharding)
    return fn(jnp.uint32(int(seed) & 0xFFFFFFFF))


def is_adapter(path: str) -> bool:
    return path.rsplit("/", 1)[-1] in ("lora_a", "lora_b")


def flatten(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_path(p): x for p, x in flat}

"""Mamba-1 selective state-space mixer with inner norms (the Jamba family's).

A layer of ``TransformerConfig.layer_pattern`` named ``"mamba"`` puts this
mixer where an attention block has its attention; the residual, the norms
around it and the feed-forward are ``models/transformer.Block``'s. With
``d = mamba_expand * d_model``, ``N = mamba_d_state``, ``K = mamba_d_conv``,
``R = mamba_dt_rank``:

    (u, z) = split(x W_in)
    c_t    = silu(b_conv + sum_j w_conv[j] * u_{t-K+1+j})      depthwise, causal
    (dt_raw, B_t, C_t) = split(c_t W_x), each through an RMSNorm of its own
    dt_t   = softplus(dt_raw W_dt + b_dt)
    h_t    = exp(dt_t (x) A) * h_{t-1} + (dt_t * c_t) (x) B_t,   A = -exp(A_log)
    y_t    = h_t C_t + D * c_t;   out = (y * silu(z)) W_out

What a request carries between passes is ``h`` (float32) and the last ``K-1``
rows of ``u`` (the activation dtype). Both are held TRANSPOSED against the
papers' ``[d, N]``: ``A_log`` and ``h`` are ``[N, d]``, channels on the TPU's
lanes (a ``[.., 16]`` minor dimension would pad eightfold in HBM).

Three ways in, chosen by the config and the shapes of the call, never by name:

* ``decode=False``: the cache-free forward, zero state to the left.
* decode mode, ``T > 1``: a prefill pass of one row cache. It starts from the
  ``cache`` collection's ``conv`` / ``ssm`` (zeros when the collection is new,
  a prefix-cache snapshot when the engine put one there), treats tokens at or
  past ``seq_lens`` as padding that leaves the state untouched, and leaves the
  state at ``seq_lens`` in ``conv`` / ``ssm`` and the state at ``snap_lens`` in
  ``snap_conv`` / ``snap_ssm`` (row caches only: the slot pool has no such
  leaves). The recurrence runs in ``ops/selective_scan.py``.
* decode mode, ``T == 1``: one token a row, plain XLA, state ``[B, ...]``
  indexed by row: the engines' slots. ``cache_idx < 0`` marks a freed slot,
  whose state stands still.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import LoRALinear, RMSNorm, TransformerConfig

log = logging.getLogger(__name__)

#: the cache leaves a request's slot holds (by name: ``serving/paged_kv.py``
#: writes them by slot where it scatters K/V by page) and the row-only leaves
#: a prefill leaves for the prefix cache
STATE_LEAVES = ("conv", "ssm")
SNAPSHOT_LEAVES = {"snap_conv": "conv", "snap_ssm": "ssm"}
#: the key of a PACKED row cache under which the Mamba layers' leaves ride
#: stacked, one ``[n_mamba_layers, ...]`` array a leaf name
PACKED = "recurrent"


def dt_rank(cfg: TransformerConfig) -> int:
    return cfg.mamba_dt_rank or math.ceil(cfg.d_model / 16)


def d_inner(cfg: TransformerConfig) -> int:
    return cfg.mamba_expand * cfg.d_model


def mamba_layers(cfg: TransformerConfig) -> tuple:
    return tuple(f"layer_{i}" for i, k in enumerate(cfg.layer_pattern) if k == "mamba")


def pack_state(cfg: TransformerConfig, cache):
    """A row cache as it crosses a program's boundary: the recurrent leaves of
    all Mamba layers stacked under ``PACKED``, the rest as the model holds it.
    Launching a program costs the chip's host about 60 us an OUTPUT buffer
    (PR 29's profile: 7.5 ms to dispatch a prefill that returned 26 layers x 4
    leaves, more than the device took to run it); stacked, a prefill returns 4.
    The copy is one request's state, 9 MB. A cache without Mamba layers is
    returned as it is."""
    layers = mamba_layers(cfg)
    if not layers:
        return cache
    out = {k: v for k, v in cache.items() if k not in layers}
    out[PACKED] = {name: jnp.stack([cache[layer]["mamba"][name] for layer in layers])
                   for name in cache[layers[0]]["mamba"]}
    return out


def unpack_state(cfg: TransformerConfig, cache):
    """``pack_state``'s inverse, for the program that takes a packed row."""
    if PACKED not in cache:
        return cache
    out = {k: v for k, v in cache.items() if k != PACKED}
    for i, layer in enumerate(mamba_layers(cfg)):
        out[layer] = {"mamba": {name: stacked[i] for name, stacked in cache[PACKED].items()}}
    return out


def state_bytes(cfg: TransformerConfig) -> int:
    """Bytes of ONE request's recurrent state over all Mamba layers."""
    d, itemsize = d_inner(cfg), jnp.dtype(cfg.dtype).itemsize
    per_layer = (cfg.mamba_d_conv - 1) * d * itemsize + cfg.mamba_d_state * d * 4
    return per_layer * sum(1 for k in cfg.layer_pattern if k == "mamba")


@functools.lru_cache(maxsize=None)
def _selective_scan_impl(platform: str, T: int, d: int, n: int):
    """Which formulation a prefill's recurrence runs, logged once per distinct
    case: ``ops.selective_scan.selective_scan`` (compiled on the TPU wherever
    its blocks tile, interpreted on the CPU wherever the token tile does) and
    the plain ``lax.scan`` formulation for a shape the kernel cannot tile.
    Decided here, from shapes, before anything runs."""
    from ..ops import selective_scan as ss

    shape = f"T={T} d_inner={d} d_state={n}"
    ok = ss.tiles(T, d, n) if platform == "tpu" else T % ss.SUB == 0
    if not ok:
        log.warning("selective scan -> plain lax.scan formulation (one step a token): "
                    "the kernel cannot tile %s on %s", shape, platform)
        return ss.selective_scan_reference
    log.info("selective scan -> pallas kernel (platform=%s, %s)", platform, shape)
    return ss.selective_scan


def _a_log_init(key, shape, dtype=jnp.float32):
    """The family's initialisation: A = -(1..N) for every channel."""
    n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
    return jnp.broadcast_to(jnp.log(n)[:, None], shape).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus(bias) log-uniform over 0.001..0.1 (the family's)."""
    dt = jnp.exp(jax.random.uniform(key, shape) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _DtProj(nn.Module):
    """dt_raw -> d_inner with a bias; the product leaves the MXU in float32,
    because ``dt`` sits inside an exponential that every later token passes."""

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(), (x.shape[-1], self.features))
        bias = self.param("bias", _dt_bias_init, (self.features,))
        y = jnp.dot(x, kernel.astype(x.dtype), preferred_element_type=jnp.float32)
        return y + bias.astype(jnp.float32)


class MambaMixer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, seq_lens: Optional[jnp.ndarray] = None,
                 snap_lens: Optional[jnp.ndarray] = None,
                 cache_idx: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        f32 = jnp.float32
        B, T, _ = x.shape
        d, N, K, R = d_inner(cfg), cfg.mamba_d_state, cfg.mamba_d_conv, dt_rank(cfg)
        uz = LoRALinear(2 * d, cfg, name="in_proj")(x)
        u, z = uz[..., :d], uz[..., d:]
        w_conv = self.param("conv_kernel", nn.initializers.lecun_normal(), (K, d)).astype(f32)
        b_conv = self.param("conv_bias", nn.initializers.zeros, (d,)).astype(f32)
        a_t = -jnp.exp(self.param("A_log", _a_log_init, (N, d)).astype(f32))
        d_skip = self.param("D", nn.initializers.ones, (d,)).astype(f32)

        if cfg.decode:
            conv = self.variable("cache", "conv", jnp.zeros, (B, K - 1, d), x.dtype)
            ssm = self.variable("cache", "ssm", jnp.zeros, (B, N, d), f32)
            conv0, h0 = conv.value, ssm.value
        else:
            conv0, h0 = jnp.zeros((B, K - 1, d), x.dtype), jnp.zeros((B, N, d), f32)

        ext = jnp.concatenate([conv0, u], axis=1)  # [B, K-1+T, d]: row t+j is u_{t-K+1+j}
        c = sum(w_conv[j] * ext[:, j:j + T].astype(f32) for j in range(K))
        c = nn.silu(c + b_conv).astype(x.dtype)

        proj = LoRALinear(R + 2 * N, cfg, name="x_proj")(c).astype(f32)
        dt_raw = RMSNorm(cfg.norm_eps, name="dt_norm")(proj[..., :R])
        b_t = RMSNorm(cfg.norm_eps, name="b_norm")(proj[..., R:R + N])
        c_t = RMSNorm(cfg.norm_eps, name="c_norm")(proj[..., R + N:])
        dt = jax.nn.softplus(_DtProj(d, name="dt_proj")(dt_raw.astype(x.dtype)))  # [B, T, d] f32
        cf = c.astype(f32)

        if cfg.decode and T == 1:
            # one token a row: the engines' slot step (and generate()'s loop)
            dt1, c1 = dt[:, 0], cf[:, 0]
            h = jnp.exp(dt1[:, None, :] * a_t) * h0 + (dt1 * c1)[:, None, :] * b_t[:, 0, :, None]
            y = (jnp.sum(h * c_t[:, 0, :, None], axis=1) + d_skip * c1)[:, None]
            new_conv = ext[:, 1:]
            if cache_idx is not None:  # a freed slot's state stands still
                live = cache_idx >= 0
                h = jnp.where(live[:, None, None], h, h0)
                new_conv = jnp.where(live[:, None, None], new_conv, conv0)
            if self.is_mutable_collection("cache"):
                conv.value, ssm.value = new_conv, h
        else:
            if cache_idx is not None:
                raise ValueError(f"cache_idx decode requires T=1 steps, got T={T}")
            lens = jnp.full((B,), T, jnp.int32) if seq_lens is None else seq_lens.astype(jnp.int32)
            snaps = jnp.zeros((B,), jnp.int32) if snap_lens is None else snap_lens.astype(jnp.int32)
            scan = _selective_scan_impl(jax.default_backend(), T, d, N)
            y, h_len, h_snap = scan(cf, dt, a_t, b_t, c_t, d_skip, h0, lens, snaps)
            if cfg.decode and self.is_mutable_collection("cache"):
                def tail(at):  # the K-1 rows of u before position ``at`` of each row
                    return jax.vmap(lambda e, a: jax.lax.dynamic_slice_in_dim(e, a, K - 1))(ext, at)

                conv.value, ssm.value = tail(lens), h_len
                self.variable("cache", "snap_conv", jnp.zeros, (B, K - 1, d), x.dtype).value = tail(snaps)
                self.variable("cache", "snap_ssm", jnp.zeros, (B, N, d), f32).value = h_snap
        out = (y * nn.silu(z.astype(f32))).astype(x.dtype)
        return LoRALinear(cfg.d_model, cfg, name="out_proj")(out)

"""Multi-head latent attention (MLA): a layer of kind ``"mla"``.

A layer of ``TransformerConfig.layer_pattern`` named ``"mla"`` puts this mixer
where an attention block has its attention. With ``H = n_heads``, ``d_n =
qk_nope_head_dim``, ``d_r = qk_rope_head_dim``, ``d_v = v_head_dim``, ``r_q =
q_lora_rank``, ``r = kv_lora_rank``, no biases:

    c_q         = N_q(x W_dq)                                  [r_q]
    (q_n, q_r)  = split(c_q W_uq) a head                       [d_n], [d_r]
    (c_kv, k_r) = split(x W_dkv),  c = N_kv(c_kv)              [r], [d_r]: k_r ONE for all heads
    (k_n, v)    = split(c W_ukv) a head                        [d_n], [d_v]
    score_i     = (q_n,i . k_n,i + RoPE(q_r,i) . RoPE(k_r)) / sqrt(d_n + d_r)
    out         = concat_i(softmax(score_i) v_i) W_o

Two variants by the config: ``q_lora_rank = 0`` takes the query straight from
``x`` (``[q_n ; q_r] = x W_q``: no bottleneck, no ``N_q``), and ``use_rope =
False`` rotates nothing (the ``d_r`` columns stay, unrotated, ``k_r`` still
one for all heads: Kimi-Linear's NoPE latent layers).

What a token leaves in the cache is ``[c ; RoPE(k_r)]``, ``r + d_r`` values:
ONE leaf a layer, ``latent``, ``[B, S, W]`` in a row cache and ``[pages, page,
W]`` in the page pool, where an attention layer has a K and a V leaf with kv
heads (serving/paged_kv.py scatters and gathers either by the leaf's own
trailing shape). ``W`` is ``r + d_r`` filled up with zero columns to whole
128-lane tiles (``TransformerConfig.latent_row_width``: 576 -> 640).

Two formulations of the same mathematics, chosen by the config and the shapes
of the call, never by name:

* EXPANDED, two ways, ``latent_row_attention`` picking by shape. The loop
  (``expanded_latent_attention``) rebuilds ``k_n`` and ``v`` from the latents a
  block of keys at a time up to the pass's end, with a running softmax; every
  query of the pass meets every block (``T x kv_len`` work): the cache-free
  forward (a training step needs its gradient), ``generate()``'s steps, and
  passes over a row cache that are short against the row. A long pass over a
  row cache expands every head's keys and values once, only as far as the
  pass's end (``expand_latent_rows``), and attends through
  ``ops/flash_attention.flash_attention_rows``, which visits only the key
  blocks a query can see (``kernel_latent_attention``). The ``[H, T, S]``
  scores never exist either way.
* ABSORBED (the paged decode step, one token a row): ``q~_i = W_uk,i^T q_n,i``
  turns the scores into ``q~_i . c + q_r,i . k_r``, multi-query attention of H
  heads over one ``W`` wide key whose first ``r`` columns are also the value;
  ``ops/paged_attention.paged_latent_attention`` reads each live latent page
  once for both contractions; ``o_i = W_uv,i (sum_s p_i,s c_s)``.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import LoRALinear, RMSNorm, TransformerConfig, rotary_embedding

log = logging.getLogger(__name__)

#: the cache leaf a latent layer holds (beside the row cache's ``idx``)
LATENT_LEAF = "latent"
#: keys of one step of the expanded form's loop
KEY_BLOCK = 256
NEG_INF = -1e30


class _Kernel(nn.Module):
    """A projection's ``kernel`` as a value: the absorbed form contracts
    ``W_ukv`` head by head on both sides of the attention."""

    shape: Tuple[int, int]

    @nn.compact
    def __call__(self) -> jnp.ndarray:
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape)


@functools.lru_cache(maxsize=None)
def _paged_latent_impl(platform: str, rank: int, width: int, page_size: int,
                       n_heads: int, dtype: str):
    """Which formulation the paged decode step reads the latent pool with,
    logged once per distinct case (as ``transformer._paged_attention_impl``):
    the kernel, compiled on the TPU wherever its blocks tile and interpreted
    on the CPU at any shape, else the gather + masked-einsum formulation.
    Decided here, from shapes, before anything runs."""
    from ..ops import paged_attention as pa

    shape = (f"kv_lora_rank={rank} row_width={width} page_size={page_size} "
             f"n_heads={n_heads} dtype={dtype}")
    if platform == "tpu" and not pa.latent_tiles(rank, width, page_size, dtype):
        log.warning("paged latent decode attention -> reference formulation (full-"
                    "context gather): the kernel cannot tile %s", shape)
        return pa.paged_latent_attention_reference
    log.info("paged latent decode attention -> pallas kernel (platform=%s, %s)", platform, shape)
    return pa.paged_latent_attention


def rotate_rope_columns(x, d_nope: int, positions, theta: float):
    """``(x[..., :d_nope], RoPE(x[..., d_nope:]))`` of ``x [B, T, heads, d_nope +
    d_rope]``: positions enter the rotary columns alone."""
    return x[..., :d_nope], rotary_embedding(x[..., d_nope:], positions, theta)


def unrotated_columns(x, d_nope: int, positions, theta: float):
    """``rotate_rope_columns``'s split without the rotation: a config with
    ``use_rope=False`` keeps the ``d_rope`` columns, shared by all heads for
    the key, and gives them no position."""
    return x[..., :d_nope], x[..., d_nope:]


def score_scale(cfg: TransformerConfig) -> float:
    """1 / sqrt of the query/key width, nope and rope columns together."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def expanded_latent_attention(q_n, q_r, latents, w_kv, q_pos0, kv_len, *, rank: int,
                              d_nope: int, scale: float):
    """Causal attention of queries ``q_n [B, T, H, d_n]`` / ``q_r [B, T, H,
    d_r]`` (rotated) at absolute positions ``q_pos0 + arange(T)`` over
    ``latents [B, S, W]`` (``[c ; k_r ; zeros]``), of which positions ``<
    kv_len`` are written. ``w_kv [r, H, d_n + d_v]`` expands a latent into every head's key and
    value. Returns ``[B, T, H, d_v]`` in the queries' dtype. f32 scores,
    statistics and accumulation; the loop runs ``ceil(kv_len / block)`` steps
    (a runtime count: a suffix pass over a 2,048-slot row whose prefix is 256
    tokens expands three blocks, not eight)."""
    B, T, H, _ = q_n.shape
    S = latents.shape[1]
    d_v, d_rope = w_kv.shape[-1] - d_nope, q_r.shape[-1]
    blk = KEY_BLOCK if S % KEY_BLOCK == 0 else S
    f32 = jnp.float32
    q_pos = q_pos0 + jnp.arange(T)

    def body(j, carry):
        m, l, acc = carry
        lat = jax.lax.dynamic_slice_in_dim(latents, j * blk, blk, axis=1)  # [B, blk, W]
        kv = jnp.einsum("bsr,rhd->bshd", lat[..., :rank], w_kv)            # [B, blk, H, d_n + d_v]
        s = (jnp.einsum("bthd,bshd->bhts", q_n, kv[..., :d_nope], preferred_element_type=f32)
             + jnp.einsum("bthd,bsd->bhts", q_r, lat[..., rank:rank + d_rope],
                          preferred_element_type=f32)) * scale
        k_pos = j * blk + jnp.arange(blk)
        valid = jnp.logical_and(k_pos[None, :] <= q_pos[:, None], k_pos[None, :] < kv_len)  # [T, blk]
        s = jnp.where(valid[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        # a query that sees no key of this block keeps m = NEG_INF: exp(0) must not count
        p = jnp.where(valid[None, None], jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhts,bshd->bhtd", p.astype(q_n.dtype), kv[..., d_nope:],
                        preferred_element_type=f32)
        return m_new, l_new, acc * corr[..., None] + pv

    init = (jnp.full((B, H, T), NEG_INF, f32), jnp.zeros((B, H, T), f32), jnp.zeros((B, H, T, d_v), f32))
    n_blk = jnp.minimum(-(-jnp.asarray(kv_len, jnp.int32) // blk), S // blk)
    _, l, acc = jax.lax.fori_loop(0, n_blk, body, init)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q_n.dtype)


def expand_latent_rows(latents, w_kv, kv_len, *, rank: int, d_nope: int, d_rope: int):
    """Every head's keys ``[B, S, H, d_k]`` (``[c W_uk,i ; k_r ; 0]``: the
    ``d_n + d_r`` columns filled up with zeros to whole 128-lane tiles, else
    the compiler lays the 192-wide row out anew, whole, for the kernel) and
    values ``[B, S, H, d_v]`` (``c W_uv,i``) of a row cache's ``latents [B, S,
    W]``, in the latents' dtype, expanded a block of ``flash_attention_rows``'
    keys at a time only as far as ``kv_len`` (a runtime count: a 2,048-token
    pass behind a 256-token prefix expands 5 of a 16,896-position row's 33
    blocks). Positions at or past ``kv_len`` are zero, whatever the row held
    there: the kernel multiplies them by ``p = 0``. Built head-major, which is
    what the kernel reads: the transposes to this ``[B, S, H, .]`` interface
    and the kernel's back cancel."""
    from ..ops.flash_attention import rows_block

    B, S, _ = latents.shape
    H, d_v = w_kv.shape[1], w_kv.shape[2] - d_nope
    d_k = -(-(d_nope + d_rope) // 128) * 128
    blk = rows_block(S)

    def body(j, kv):
        k, v = kv
        start = j * blk
        lat = jax.lax.dynamic_slice_in_dim(latents, start, blk, axis=1)           # [B, blk, W]
        lat = jnp.where((start + jnp.arange(blk) < kv_len)[None, :, None], lat, jnp.zeros((), lat.dtype))
        kv_b = jnp.einsum("bsr,rhd->bhsd", lat[..., :rank], w_kv)                  # [B, H, blk, d_n + d_v]
        k_r = jnp.broadcast_to(lat[:, None, :, rank:rank + d_rope], (B, H, blk, d_rope))
        pad = jnp.zeros((B, H, blk, d_k - d_nope - d_rope), lat.dtype)
        k = jax.lax.dynamic_update_slice(k, jnp.concatenate([kv_b[..., :d_nope], k_r, pad], axis=-1), (0, 0, start, 0))
        v = jax.lax.dynamic_update_slice(v, kv_b[..., d_nope:], (0, 0, start, 0))
        return k, v

    init = (jnp.zeros((B, H, S, d_k), latents.dtype), jnp.zeros((B, H, S, d_v), latents.dtype))
    n_blk = jnp.minimum(-(-jnp.asarray(kv_len, jnp.int32) // blk), S // blk)
    k, v = jax.lax.fori_loop(0, n_blk, body, init)
    return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)


def kernel_latent_attention(q_n, q_r, latents, w_kv, q_pos0, kv_len, *, rank: int, d_nope: int):
    """``expanded_latent_attention``'s pass over a row cache, through
    ``ops.flash_attention.flash_attention_rows``: the keys and values
    expanded once (``expand_latent_rows``), then only the key blocks a query
    can see are visited. The query is filled up with zeros as the keys are,
    and the scores keep ``score_scale`` (``d_n + d_r``, not the filled
    width). Forward only."""
    from ..ops.flash_attention import flash_attention_rows

    k, v = expand_latent_rows(latents, w_kv, kv_len, rank=rank, d_nope=d_nope, d_rope=q_r.shape[-1])
    pad = jnp.zeros(q_n.shape[:-1] + (k.shape[-1] - d_nope - q_r.shape[-1],), q_n.dtype)
    return flash_attention_rows(jnp.concatenate([q_n, q_r, pad], axis=-1), k, v, q_pos0,
                                scale=(d_nope + q_r.shape[-1]) ** -0.5)


#: a pass of ``T`` queries over a row of ``S`` positions takes the kernel where
#: ``T * T > LATENT_KERNEL_T2_PER_KEY * S``. The loop's cost grows with the
#: pass's ``T x kv_len`` scores, the kernel path's has a fixed part that grows
#: with the row: its expanded keys and values are zero-filled, written and read
#: whole. On a v5e the two cross at about 150 S at openPangu's widths (a
#: 2,048 row: T 512 loop 1.6 ms, kernel 1.7; T 1,024 6.5 against 2.7) and 210 S
#: at Kimi-Linear's (16,896: T 1,024 0.6 against 1.7; T 2,048 3.1 against 2.3):
#: ``tools/mla_prefill_check.py``
LATENT_KERNEL_T2_PER_KEY = 192


@functools.lru_cache(maxsize=None)
def latent_row_attention(platform: str, T: int, S: int, n_heads: int) -> str:
    """Which formulation a ``T``-token pass of a latent layer over its row
    cache of ``S`` positions attends with, logged once per distinct case:
    ``"kernel"`` (``kernel_latent_attention``) on the TPU where the pass is
    long against its row (``LATENT_KERNEL_T2_PER_KEY``) and the row tiles
    the kernel's blocks; else ``"loop"`` (``expanded_latent_attention``).
    Decided here, from shapes (the heads scale both sides alike); the engine
    names the same answer on its prefill span (``latent_attn``)."""
    from ..ops.flash_attention import rows_tile

    long = T * T > LATENT_KERNEL_T2_PER_KEY * S
    impl = "kernel" if platform == "tpu" and long and rows_tile(S) else "loop"
    if T > 1:
        log.info("latent row-cache attention -> %s (platform=%s, T=%d, S=%d, n_heads=%d)",
                 impl, platform, T, S, n_heads)
    return impl


class LatentAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache_idx: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        B, T, _ = x.shape
        H, r = cfg.n_heads, cfg.kv_lora_rank
        d_n, d_r, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if min(r, d_n, d_r, d_v) < 1 or cfg.q_lora_rank < 0:
            raise ValueError("a layer of kind 'mla' needs kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, "
                             f"v_head_dim and a q_lora_rank >= 0, got {cfg!r}")
        if cfg.weight_quant != "none":
            raise ValueError("weight_quant is not wired for latent attention (kv_b_proj is contracted per head)")
        if cfg.q_lora_rank:
            c_q = RMSNorm(cfg.norm_eps, name="q_a_norm")(LoRALinear(cfg.q_lora_rank, cfg, name="q_a_proj")(x))
            q = LoRALinear(H * (d_n + d_r), cfg, name="q_b_proj")(c_q)
        else:  # no bottleneck: the query straight from x
            q = LoRALinear(H * (d_n + d_r), cfg, name="q_proj")(x)
        q = q.reshape(B, T, H, d_n + d_r)
        split = rotate_rope_columns if cfg.use_rope else unrotated_columns
        q_n, q_r = split(q, d_n, positions, cfg.rope_theta)
        c_kv, k_r = split(LoRALinear(r + d_r, cfg, name="kv_a_proj")(x)[:, :, None], r, positions, cfg.rope_theta)
        c, k_r = RMSNorm(cfg.norm_eps, name="kv_a_norm")(c_kv[:, :, 0]), k_r[:, :, 0]
        pad = jnp.zeros((B, T, cfg.latent_row_width - r - d_r), c.dtype)
        latent = jnp.concatenate([c, k_r, pad], axis=-1)  # [B, T, W]: what the cache keeps
        w_kv = _Kernel((r, H * (d_n + d_v)), name="kv_b_proj")().astype(x.dtype).reshape(r, H, d_n + d_v)
        scale = score_scale(cfg)
        expanded = functools.partial(expanded_latent_attention, rank=r, d_nope=d_n, scale=scale)

        if cfg.decode and cfg.kv_page_size > 0:
            out = self._paged_decode(q_n, q_r, latent, w_kv, cache_idx, block_tables, scale)
        elif cfg.decode:
            # a row cache: prefill rows, suffix passes, generate()'s steps. New
            # latents go in at the running index, shared by every row
            S = cfg.max_seq_len
            cl = self.variable("cache", LATENT_LEAF, jnp.zeros, (B, S, latent.shape[-1]), x.dtype)
            cidx = self.variable("cache", "idx", lambda: jnp.zeros((), jnp.int32))
            idx = cidx.value
            if self.is_mutable_collection("cache"):
                cl.value = jax.lax.dynamic_update_slice(cl.value, latent.astype(cl.value.dtype), (0, idx, 0))
                cidx.value = idx + T
            if latent_row_attention(jax.default_backend(), T, S, H) == "kernel":
                attend = functools.partial(kernel_latent_attention, rank=r, d_nope=d_n)
            else:
                attend = expanded
            with jax.named_scope("mla_prefill_attention"):
                out = attend(q_n, q_r, cl.value, w_kv, idx, idx + T)
        else:  # a training forward: the kernel has no gradient
            with jax.named_scope("mla_prefill_attention"):
                out = expanded(q_n, q_r, latent, w_kv, 0, T)
        return LoRALinear(cfg.d_model, cfg, name="o_proj")(out.reshape(B, T, H * d_v))

    def _paged_decode(self, q_n, q_r, latent, w_kv, cache_idx, block_tables, scale) -> jnp.ndarray:
        """One token a row over the latent page pool, absorbed. The write, the
        trash page and a freed slot's ``cache_idx = -1`` are
        ``Attention._paged_decode_attention``'s, with one leaf."""
        cfg = self.cfg
        B, T, H, d_n = q_n.shape
        ps, n_pages, r = cfg.kv_page_size, cfg.kv_num_pages, cfg.kv_lora_rank
        if T != 1:
            raise ValueError(f"paged decode requires T=1 steps, got T={T}")
        if cache_idx is None or block_tables is None:
            raise ValueError("paged decode requires cache_idx and block_tables")
        if n_pages < 2:
            raise ValueError("kv_num_pages must be >= 2 (page 0 is the trash page)")
        cl = self.variable("cache", LATENT_LEAF, jnp.zeros, (n_pages, ps, latent.shape[-1]), q_n.dtype)
        self.variable("cache", "idx", lambda: jnp.zeros((), jnp.int32))  # congruent with the row cache
        w_idx = jnp.maximum(cache_idx, 0)
        page = jnp.take_along_axis(block_tables, (w_idx // ps)[:, None], axis=1)[:, 0]
        page = jnp.where(cache_idx < 0, 0, page)
        if self.is_mutable_collection("cache"):
            cl.value = cl.value.at[page, w_idx % ps].set(latent[:, 0].astype(cl.value.dtype))
        attend = _paged_latent_impl(jax.default_backend(), r, latent.shape[-1], ps, H,
                                    jnp.dtype(q_n.dtype).name)
        with jax.named_scope("mla_decode_attention"):
            q_abs = jnp.einsum("bhd,rhd->bhr", q_n[:, 0], w_kv[..., :d_n])        # W_uk^T q_n
            pad = jnp.zeros((B, H, latent.shape[-1] - r - q_r.shape[-1]), q_abs.dtype)
            q_full = jnp.concatenate([q_abs, q_r[:, 0], pad], axis=-1)              # [B, H, W]
            o_lat = attend(q_full, cl.value, block_tables, cache_idx + 1, rank=r, scale=scale)
            out = jnp.einsum("bhr,rhd->bhd", o_lat, w_kv[..., d_n:])               # W_uv (sum_s p_s c_s)
        return out[:, None]

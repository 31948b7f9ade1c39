"""Ring attention: sequence/context parallelism over the ICI ring.

The reference has NO sequence parallelism (SURVEY §5 "Long-context —
absent"); this is the TPU-native extension the build plan calls for: the
sequence axis is sharded over an 'sp' mesh axis, each device holds one
query/KV block, and KV blocks rotate around the ring via
``jax.lax.ppermute`` while an online-softmax accumulator keeps the result
exact (Liu et al. 2023, blockwise ring attention).

Causality across blocks: device i's queries attend KV block j fully when
j < i, causally when j == i, not at all when j > i — enforced with masks so
the rotation count is uniform (no data-dependent control flow).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

# --- active mesh context (set by train-step builders so model code can find
# the 'sp' axis without threading the mesh through flax modules) -----------
_ACTIVE_MESH: Optional[Mesh] = None


class active_mesh:
    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        global _ACTIVE_MESH
        self._prev = _ACTIVE_MESH
        _ACTIVE_MESH = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        global _ACTIVE_MESH
        _ACTIVE_MESH = self._prev


def get_active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


def _dot_qk(qc, kc, scale: float):
    """[B, Tq, H, D] x [B, Tk, H, D] -> [B, H, Tq, Tk] f32: operands stay in
    their input dtype (bf16 rides the MXU at full rate), accumulation and
    the post-matmul scale are f32 — same recipe as ops/flash_attention."""
    return jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                      preferred_element_type=jnp.float32) * scale


def _online_update(m, l, acc, logits, allow, v_cur):
    """ONE copy of the numerically delicate online-softmax step, shared by
    both ring bodies (max/correction/accumulate; masked entries contribute
    exactly zero)."""
    logits = jnp.where(allow, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None]) * allow.astype(jnp.float32)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_cur.dtype), v_cur,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _ring_block(q, k, v, axis_name: str):
    """Per-device ring attention body. q/k/v: [B, T_local, H, D]."""
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    B, Tl, H, D = q.shape

    # initial accumulators must be marked device-varying for the scan carry
    pvary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    m = pvary(jnp.full((B, H, Tl), NEG_INF, jnp.float32))
    l = pvary(jnp.zeros((B, H, Tl), jnp.float32))
    acc = pvary(jnp.zeros((B, H, Tl, D), jnp.float32))

    row_ids = jnp.arange(Tl)

    def body(step, carry):
        m, l, acc, k_cur, v_cur = carry
        j = (idx - step) % n  # block index currently held
        # mask: j < idx -> full block; j == idx -> causal; j > idx -> none
        intra = row_ids[:, None] >= row_ids[None, :]  # [Tl, Tl]
        allow2d = jnp.where(j == idx, intra, j < idx)  # scalar conds broadcast
        allow = jnp.broadcast_to(allow2d[None, None], (B, H, Tl, Tl))
        m, l, acc = _online_update(m, l, acc, _dot_qk(q, k_cur, scale), allow, v_cur)
        # rotate kv to the next device
        perm = [(d, (d + 1) % n) for d in range(n)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_next, v_next

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m, l, acc, k, v))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, Tl, H, D]


# --- zigzag layout (balanced causal ring) ------------------------------------
#
# The contiguous layout above is exact but imbalanced under causality: device
# i's queries need i+1 of the n KV blocks, so device 0 idles while device n-1
# works every rotation — and every step computes a FULL [Tl, Tl] logits tile,
# mostly masked (~50% of all computed pairs are wasted). The zigzag layout
# (Brandon et al. "Striped Attention" lineage; the zigzag variant used by
# ring-flash implementations) reshards the sequence so device i owns chunk i
# AND chunk 2n-1-i of 2n half-blocks: every device then needs exactly 2n+1
# chunk-pairs (uniform), and per rotation only 3 of 4 quarter-tiles can ever
# be unmasked (front-queries x back-KV is ALWAYS masked and is statically
# skipped) — 25% fewer FLOPs than the contiguous ring and no stragglers.


def _zigzag_split(x, axis_name: str, n: int):
    """Contiguous shard [B, Tl, ...] -> (front, back) halves in zigzag
    ownership: device d ends up holding global chunks d and 2n-1-d. Two
    ppermutes (one per local half) — each is a bijection, verified by
    construction: dest(c) = c for c < n else 2n-1-c over even/odd chunk ids
    hits every device exactly once."""
    idx = jax.lax.axis_index(axis_name)
    C = x.shape[1] // 2
    h0, h1 = x[:, :C], x[:, C:]  # global chunk ids 2*idx, 2*idx+1

    def dest(c: int) -> int:
        return c if c < n else 2 * n - 1 - c

    r0 = jax.lax.ppermute(h0, axis_name, [(s, dest(2 * s)) for s in range(n)])
    r1 = jax.lax.ppermute(h1, axis_name, [(s, dest(2 * s + 1)) for s in range(n)])
    # device d received its even chunk via r0 and odd via r1; the FRONT
    # chunk (id=d) is the even one iff d is even
    even = (idx % 2) == 0
    front = jnp.where(even, r0, r1)
    back = jnp.where(even, r1, r0)
    return front, back


def _zigzag_merge(front, back, axis_name: str, n: int):
    """Inverse of _zigzag_split: route chunks d / 2n-1-d back to their
    contiguous owners and concatenate into [B, Tl, ...]."""
    idx = jax.lax.axis_index(axis_name)
    even = (idx % 2) == 0
    # the EVEN-id chunk this device holds is front (id=d) iff d even,
    # else back (id=2n-1-d, even when d is odd)
    send_even = jnp.where(even, front, back)
    send_odd = jnp.where(even, back, front)

    def even_id(d: int) -> int:
        return d if d % 2 == 0 else 2 * n - 1 - d

    def odd_id(d: int) -> int:
        return d if d % 2 == 1 else 2 * n - 1 - d

    r0 = jax.lax.ppermute(send_even, axis_name,
                          [(d, even_id(d) // 2) for d in range(n)])
    r1 = jax.lax.ppermute(send_odd, axis_name,
                          [(d, odd_id(d) // 2) for d in range(n)])
    return jnp.concatenate([r0, r1], axis=1)


def _ring_block_zigzag(q, k, v, axis_name: str):
    """Balanced causal ring attention body. q/k/v: [B, Tl, H, D] contiguous;
    resharded to zigzag internally, result resharded back — callers see the
    same contract as _ring_block."""
    idx = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    B, Tl, H, D = q.shape
    qf, qb = _zigzag_split(q, axis_name, n)
    kf, kb = _zigzag_split(k, axis_name, n)
    vf, vb = _zigzag_split(v, axis_name, n)
    C = Tl // 2

    pvary = lambda x: jax.lax.pcast(x, (axis_name,), to="varying")
    zero_m = jnp.full((B, H, C), NEG_INF, jnp.float32)
    zero_l = jnp.zeros((B, H, C), jnp.float32)
    zero_a = jnp.zeros((B, H, C, D), jnp.float32)
    intra = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]  # [C, C]

    def body(step, carry):
        mf, lf, af, mb, lb, ab, kf_c, vf_c, kb_c, vb_c = carry
        j = (idx - step) % n  # device whose zigzag chunks we currently hold
        # front queries (chunk idx) x front KV (chunk j):
        #   j < idx full, j == idx causal, j > idx masked
        allow_ff = jnp.broadcast_to(
            jnp.where(j == idx, intra, j < idx)[None, None], (B, H, C, C))
        mf, lf, af = _online_update(mf, lf, af, _dot_qk(qf, kf_c, scale), allow_ff, vf_c)
        # back queries (chunk 2n-1-idx) x front KV (chunk j <= n-1): always
        # fully visible
        allow_all = jnp.broadcast_to(jnp.ones((), bool), (B, H, C, C))
        mb, lb, ab = _online_update(mb, lb, ab, _dot_qk(qb, kf_c, scale), allow_all, vf_c)
        # back queries x back KV (chunk 2n-1-j): j > idx full, == causal
        allow_bb = jnp.broadcast_to(
            jnp.where(j == idx, intra, j > idx)[None, None], (B, H, C, C))
        mb, lb, ab = _online_update(mb, lb, ab, _dot_qk(qb, kb_c, scale), allow_bb, vb_c)
        # (front queries x back KV is ALWAYS masked: chunk id 2n-1-j >= n >
        # idx — statically skipped, the zigzag saving)
        perm = [(d, (d + 1) % n) for d in range(n)]
        rot = lambda t: jax.lax.ppermute(t, axis_name, perm)
        return mf, lf, af, mb, lb, ab, rot(kf_c), rot(vf_c), rot(kb_c), rot(vb_c)

    carry = (pvary(zero_m), pvary(zero_l), pvary(zero_a),
             pvary(zero_m), pvary(zero_l), pvary(zero_a), kf, vf, kb, vb)
    mf, lf, af, mb, lb, ab, _, _, _, _ = jax.lax.fori_loop(0, n, body, carry)
    out_f = af / jnp.maximum(lf, 1e-20)[..., None]
    out_b = ab / jnp.maximum(lb, 1e-20)[..., None]
    to_btHD = lambda o: jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)
    return _zigzag_merge(to_btHD(out_f), to_btHD(out_b), axis_name, n)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   layout: str = "zigzag"):
    """Shard the sequence axis over `axis_name` and run blockwise ring
    attention. q/k/v: [B, T, H, D] (global view). ``layout="zigzag"``
    (default) balances causal work across the ring and skips the
    always-masked quarter-tiles; ``"contiguous"`` is the classic Liu et al.
    formulation (kept for comparison and for odd local block lengths)."""
    if layout not in ("zigzag", "contiguous"):
        raise ValueError(f"unknown ring layout {layout!r}")
    n = mesh.shape[axis_name]
    Tl = q.shape[1] // n
    if layout == "zigzag" and Tl % 2:
        layout = "contiguous"  # zigzag needs an even local block
    body = _ring_block_zigzag if layout == "zigzag" else _ring_block
    spec = P(None, axis_name, None, None)
    return shard_map(
        functools.partial(body, axis_name=axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )(q, k, v)


def ring_attention_inner(q, k, v):
    """Model-facing entry (transformer.Attention attention_impl='ring'):
    uses the active mesh's 'sp' axis; falls back to exact XLA attention when
    no mesh/axis is active (single-device runs, tests)."""
    mesh = get_active_mesh()
    if mesh is not None and "sp" in mesh.axis_names:
        return ring_attention(q, k, v, mesh)
    from ..models.transformer import xla_attention

    return xla_attention(q, k, v, causal=True)

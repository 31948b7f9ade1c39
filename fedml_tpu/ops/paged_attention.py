"""Pallas TPU paged decode attention — one query token a row, GQA-native.

The paged serving engine's decode step (``serving/paged_kv._paged_step_fn``)
attends one new token of each of B rows to that row's keys and values, which
live in a page pool shared by all rows: ``[n_pages, page_size, n_kv_heads,
head_dim]`` a layer, addressed through per-row block tables. The plain
formulation (``paged_attention_reference``) gathers every row's WHOLE table
into ``[B, S, n_kv_heads, head_dim]``, repeats the kv heads up to the query
heads and runs a masked einsum: three pool-to-HBM copies of the full context
length a layer, whatever is live. This kernel reads each LIVE page once:

* grid over rows; a row walks ``block_tables[b, :ceil(len_b / page)]`` in
  blocks of several pages. Each page of K and of V is one contiguous DMA
  from the pool in HBM into a VMEM block; the next block's copies are in
  flight while this one is computed (two slots). A row of length 0 (a free
  slot) starts no copy and runs no block.
* GQA without a repeat: a page arrives as ``[page_size * n_kv_heads,
  head_dim]`` rows (a free reshape of the pool), and ALL query heads are
  contracted against ALL those rows in one MXU pass; the columns of another
  kv head are masked out of the softmax beside the positions past the row's
  length. The MXU streams each K/V row once either way, so the G query heads
  of a group share one read and the mask costs VPU work only.
* online softmax across blocks: bf16 (input dtype) operands, f32 scores,
  softmax statistics and accumulation, as in ``ops/flash_attention.py``.

``paged_latent_attention`` is the same walk for a layer of latent attention
(models/mla.py) in its absorbed form: ONE pool ``[n_pages, page_size, W]`` a
layer (``W``: the latent and the rotary key, zero columns up to whole lanes),
every query head against the same row, whose first ``r`` columns are also the
value. A page is DMA'd once and used for both
contractions; there is no kv-head mask because there is one "head".

A layer that sees a WINDOW hands the kernel a start position a row beside its
length: the row attends to its positions ``starts[b] <= l < lengths[b]``, the
walk begins at the block that holds ``starts[b]``, and the pages before that
position's page are neither copied nor scored (their table entries may point
anywhere: the engine has taken those pages back). Without ``starts`` the
program is the one it was.

Rows of a VMEM block that no copy of this row has filled (the dead pages of
a row's last block, the pages behind a window's start in its first) hold what
an earlier block left there, and at the very
start zeros: their probabilities are exactly 0, so they add 0 as long as the
pool itself is finite — the assumption the plain formulation makes of the
trash page too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _dot_nn, _dot_nt, _grid, _interpret

# K/V rows (tokens x kv heads) of one VMEM block. A measured constant like
# flash_attention's BLOCK_Q/BLOCK_K: 1024 rows of 128 lanes at bf16 are 256 KB
# a buffer, 1 MB for K and V in two slots; at 8 kv heads and 16-token pages a
# block is 8 pages = 128 tokens.
BLOCK_ROWS = 1024


def pages_per_block(page_size: int, n_kv_heads: int, n_blocks: int) -> int:
    return max(1, min(n_blocks, BLOCK_ROWS // (page_size * n_kv_heads)))


def tiles(head_dim: int, page_size: int, n_heads: int, n_kv_heads: int,
          dtype) -> bool:
    """The compiled kernel's hard shape rule: head_dim fills whole 128-lane
    vregs, a page is whole sublane tiles of its dtype (16 rows at bf16, 8 at
    f32), and the query heads group evenly over the kv heads (G = 1
    included). Interpret mode (the CPU) takes any shape."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (head_dim % 128 == 0 and page_size % sublanes == 0
            and n_heads % n_kv_heads == 0)


def _kernel(*refs, page_size: int, n_kv: int, n_blocks: int, ppb: int, scale: float,
            windowed: bool = False):
    if windowed:
        len_ref, bt_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem = refs
    else:
        len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem = refs
    b = pl.program_id(0)
    H, D = q_ref.shape[1], q_ref.shape[2]
    G = H // n_kv
    R = page_size * n_kv      # K/V rows of one page
    NB = ppb * R              # rows of one block
    TB = ppb * page_size      # tokens of one block
    length = len_ref[b]
    n_pages = pl.cdiv(length, page_size)
    n_blk = pl.cdiv(n_pages, ppb)
    if windowed:
        start = jnp.minimum(start_ref[b], jnp.maximum(length - 1, 0))
        page0 = start // page_size   # the first page with a position the row sees
        blk0 = page0 // ppb
    else:
        blk0 = 0

    @pl.when(b == 0)
    def _():
        # see the module docstring: stale V rows must be finite
        vbuf[...] = jnp.zeros_like(vbuf)

    def page_copies(blk, slot, i):
        page = bt_ref[b * n_blocks + blk * ppb + i]
        dst = pl.ds(i * R, R)
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, dst], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, dst], sem.at[1, slot]))

    def for_live_pages(blk, slot, act):
        for i in range(ppb):
            live = blk * ppb + i < n_pages
            if windowed:
                live = jnp.logical_and(live, blk * ppb + i >= page0)

            @pl.when(live)
            def _():
                for copy in page_copies(blk, slot, i):
                    act(copy)

    @pl.when(n_blk > blk0)
    def _():
        for_live_pages(blk0, blk0 % 2, lambda c: c.start())

    q = q_ref[0]  # [H, D]
    col = jax.lax.broadcasted_iota(jnp.int32, (H, NB), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (H, NB), 0)
    own_head = (col % n_kv) == (row // G)
    tok = col // n_kv  # position of a column inside its block

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _():
            for_live_pages(blk + 1, 1 - slot, lambda c: c.start())

        for_live_pages(blk, slot, lambda c: c.wait())
        k = kbuf[slot]
        v = vbuf[slot]
        s = _dot_nt(q, k) * scale  # [H, NB] f32
        valid = jnp.logical_and(own_head, tok < length - blk * TB)
        if windowed:
            valid = jnp.logical_and(valid, tok >= start - blk * TB)
        s = jnp.where(valid, s, NEG_INF)
        # every block that runs holds a live position of every head, so
        # m_new is a real score and exp() of a masked column is exactly 0
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _dot_nn(p.astype(v.dtype), v)
        return m_new, l_new, acc_new

    m = jnp.full((H, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((H, 1), jnp.float32)
    acc = jnp.zeros((H, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(blk0, n_blk, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, starts=None):
    """q ``[B, n_heads, head_dim]`` (one token a row); k_pool/v_pool
    ``[n_pages, page_size, n_kv_heads, head_dim]``; block_tables ``[B,
    n_blocks]`` int32 page ids; lengths ``[B]`` int32: row b attends to its
    logical positions ``< lengths[b]``, position l at page ``block_tables[b,
    l // page_size]``, slot ``l % page_size``. Returns ``[B, n_heads,
    head_dim]`` in q's dtype; a row of length 0 returns zeros and reads no
    page. With ``starts`` ``[B]`` int32 row b attends to ``starts[b] <= l <
    lengths[b]`` only and the pages wholly before ``starts[b]`` are not read."""
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths, starts,
                            interpret=_interpret())


# jitted so that the layers of a model share ONE trace and ONE Mosaic lowering
# of the kernel (about a second each on a chip's host)
@functools.partial(jax.jit, static_argnames="interpret")
def _paged_attention(q, k_pool, v_pool, block_tables, lengths, starts=None, *, interpret: bool):
    B, H, D = q.shape
    n_pages, ps, n_kv, _ = k_pool.shape
    n_blocks = block_tables.shape[1]
    ppb = pages_per_block(ps, n_kv, n_blocks)
    # a page's tokens x kv heads as one run of rows: the pool's own bytes
    k_rows = k_pool.reshape(n_pages, ps * n_kv, D)
    v_rows = v_pool.reshape(n_pages, ps * n_kv, D)
    buf = pltpu.VMEM((2, ppb * ps * n_kv, D), k_pool.dtype)
    # a length past the table would walk off it in SMEM: never, whatever the caller sent
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, n_blocks * ps)
    scalars = (lengths, block_tables.astype(jnp.int32).reshape(-1))
    windowed = {}
    if starts is not None:
        scalars += (jnp.clip(starts.astype(jnp.int32), 0, n_blocks * ps),)
        windowed = {"windowed": True}
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, n_kv=n_kv, n_blocks=n_blocks,
                          ppb=ppb, scale=D ** -0.5, **windowed),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        # rows in order: the V buffers are zeroed by the first and kept
        compiler_params=_grid("arbitrary"),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, q, k_rows, v_rows)


def paged_attention_reference(q, k_pool, v_pool, block_tables, lengths, starts=None):
    """The plain formulation, same arguments and result: gather each row's
    whole block table into logical order, repeat the kv heads, mask the
    positions at or past the row's length (and before its start). Materialises
    ``[B, S, n_heads, head_dim]`` twice — what a shape the kernel cannot tile
    still runs."""
    B, H, D = q.shape
    _, ps, n_kv, _ = k_pool.shape
    S = block_tables.shape[1] * ps
    k = jnp.repeat(k_pool[block_tables].reshape(B, S, n_kv, D), H // n_kv, axis=2)
    v = jnp.repeat(v_pool[block_tables].reshape(B, S, n_kv, D), H // n_kv, axis=2)
    logits = jnp.einsum("bhd,bkhd->bhk", q, k).astype(jnp.float32) * D ** -0.5
    valid = jnp.arange(S)[None, :] < lengths[:, None]  # [B, S]
    if starts is not None:
        valid = jnp.logical_and(valid, jnp.arange(S)[None, :] >= starts[:, None])
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhk,bkhd->bhd", probs, v)


# -- latent pages: one pool, the key's first ``rank`` columns are the value -----

# tokens of one VMEM block of latents: 512 rows of 640 lanes at bf16 are 640 KB a slot
LATENT_BLOCK_TOKENS = 512


def latent_tiles(rank: int, width: int, page_size: int, dtype) -> bool:
    """The compiled latent kernel's shape rule: the latent part (the value: a
    slice of the key) and the whole row fill whole 128-lane vregs (576 = 512 +
    64 does not: Mosaic cannot slice a page out of a 576-wide pool, which the
    TPU holds 640 wide anyway, so the model fills the row up with zeros), and
    a page is whole sublane tiles of its dtype. Interpret mode (the CPU) takes
    any shape."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return rank % 128 == 0 and width % 128 == 0 and page_size % sublanes == 0


def _latent_kernel(len_ref, bt_ref, q_ref, c_hbm, o_ref, cbuf, sem, *,
                   page_size: int, n_blocks: int, ppb: int, rank: int, scale: float):
    b = pl.program_id(0)
    H = q_ref.shape[1]
    TB = ppb * page_size  # tokens of one block
    length = len_ref[b]
    n_pages = pl.cdiv(length, page_size)
    n_blk = pl.cdiv(n_pages, ppb)

    @pl.when(b == 0)
    def _():
        # stale rows are values too: they must be finite
        cbuf[...] = jnp.zeros_like(cbuf)

    def page_copy(blk, slot, i):
        page = bt_ref[b * n_blocks + blk * ppb + i]
        return pltpu.make_async_copy(c_hbm.at[page], cbuf.at[slot, pl.ds(i * page_size, page_size)],
                                     sem.at[slot])

    def for_live_pages(blk, slot, act):
        for i in range(ppb):
            @pl.when(blk * ppb + i < n_pages)
            def _():
                act(page_copy(blk, slot, i))

    @pl.when(n_blk > 0)
    def _():
        for_live_pages(0, 0, lambda c: c.start())

    q = q_ref[0]  # [H, W]
    tok = jax.lax.broadcasted_iota(jnp.int32, (H, TB), 1)

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _():
            for_live_pages(blk + 1, 1 - slot, lambda c: c.start())

        for_live_pages(blk, slot, lambda c: c.wait())
        kv = cbuf[slot]  # [TB, W]
        s = _dot_nt(q, kv) * scale  # the filling columns are zeros on both sides
        s = jnp.where(tok < length - blk * TB, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _dot_nn(p.astype(kv.dtype), kv[:, :rank])
        return m_new, l_new, acc_new

    m = jnp.full((H, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((H, 1), jnp.float32)
    acc = jnp.zeros((H, rank), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blk, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def paged_latent_attention(q, pool, block_tables, lengths, *, rank: int, scale: float):
    """q ``[B, n_heads, W]`` (the absorbed query, the rotated rotary query
    and zeros, of one token a row); pool ``[n_pages, page_size, W]``;
    block_tables and lengths as ``paged_attention``'s. Row b's head i
    scores ``q[b, i] . pool_row * scale`` over its positions ``< lengths[b]``
    and returns the softmax-weighted sum of the rows' first ``rank`` columns:
    ``[B, n_heads, rank]`` in q's dtype, zeros for a row of length 0."""
    return _paged_latent_attention(q, pool, block_tables, lengths, rank=rank, scale=scale,
                                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _paged_latent_attention(q, pool, block_tables, lengths, *, rank: int, scale: float, interpret: bool):
    B, H, W = q.shape
    n_pages, ps, _ = pool.shape
    n_blocks = block_tables.shape[1]
    ppb = max(1, min(n_blocks, LATENT_BLOCK_TOKENS // ps))
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, n_blocks * ps)
    return pl.pallas_call(
        functools.partial(_latent_kernel, page_size=ps, n_blocks=n_blocks, ppb=ppb, rank=rank, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb * ps, W), pool.dtype), pltpu.SemaphoreType.DMA((2,))],
        ),
        compiler_params=_grid("arbitrary"),  # rows in order: the buffer is zeroed by the first and kept
        interpret=interpret,
        name="paged_latent_attention",
    )(lengths, block_tables.astype(jnp.int32).reshape(-1), q, pool)


def paged_latent_attention_reference(q, pool, block_tables, lengths, *, rank: int, scale: float):
    """The plain formulation, same arguments and result: gather each row's
    whole block table into logical order, mask the positions at or past the
    row's length."""
    B, H, W = q.shape
    ps = pool.shape[1]
    S = block_tables.shape[1] * ps
    rows = pool[block_tables].reshape(B, S, W)
    logits = jnp.einsum("bhw,bsw->bhs", q, rows).astype(jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsr->bhr", probs, rows[..., :rank])

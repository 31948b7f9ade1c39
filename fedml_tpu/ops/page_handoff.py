"""The page handoff's copy: blocks of contiguous row caches into pages of
page pools, as DMAs from HBM to HBM.

A finished prefill row ``[1, S, ...]`` holds a request's K/V (or latents) at
their positions; the serving pool ``[num_pages, page_size, ...]`` holds them a
page a logical block. ``rows_to_pages`` moves ONE RUNTIME RANGE of logical
blocks, ``span = (first, count)``, of every row handed to it: block ``blk`` of
row ``i`` to page ``ids[blk]`` of pool ``i``. The kernel is one program over
all the leaves of a page group: the page ids and the span are scalar-prefetched,
rows and pools stay in HBM (``memory_space=ANY``), every pool is aliased in to
out, so nothing but the ``count`` pages of each pool is read or written and no
pool is copied. It starts every page's copy, then waits for them all: the
copies overlap, and a call costs what its bytes cost (0.1-0.2 us a 32-64 KB
page on a v5e, where a loop of ``dynamic_update_slice`` costs 0.9-1.5 us a page
and a whole-row scatter made XLA lay the pools out anew around it).

Rows and pools cross into the kernel as runs of 128-lane rows, a page's tokens
x kv heads as one run (``ops/paged_attention`` reads the pools the same way):
the pool's own bytes, so the reshape moves nothing. Compiled on the TPU where
a page is whole sublane tiles (``tiles``); a shape that is not takes the plain
formulation (the loop of ``dynamic_update_slice``). Interpreted on the CPU
(tests), any shape; no other platform.
"""

from __future__ import annotations

import math
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

KERNEL_NAME = "page_handoff"


def tiles(page_rows: int, width: int, dtype) -> bool:
    """The compiled kernel's shape rule: a page is whole sublane tiles of its dtype (16 rows of 128 lanes at
    bf16, 8 at f32) and its rows fill whole 128-lane vregs. Interpret mode (the CPU) takes any shape."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return width % 128 == 0 and page_rows % sublanes == 0


def _plain(rows, pools, ids, span, page_rows: Sequence[int]):
    """The same moves as a loop of ``dynamic_update_slice`` over the carried pools, a page an iteration."""
    ids, span = jnp.asarray(ids), jnp.asarray(span)

    def move(i, carried):
        blk = span[0] + i
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            pool, jax.lax.dynamic_slice_in_dim(row, blk * n, n, axis=0)[None], ids[blk], axis=0)
            for pool, row, n in zip(carried, rows, page_rows))

    return jax.lax.fori_loop(0, span[1], move, tuple(pools))


def _kernel(ids_ref, span_ref, *refs, page_rows: Sequence[int]):
    n = len(page_rows)
    row_refs, out_refs, sem = refs[:n], refs[2 * n:3 * n], refs[3 * n]
    first, count = span_ref[0], span_ref[1]

    def copies(i):
        blk = first + i
        return [pltpu.make_async_copy(row.at[pl.ds(blk * rows, rows)], out.at[ids_ref[blk]], sem)
                for row, out, rows in zip(row_refs, out_refs, page_rows)]

    @pl.loop(0, count)
    def _start(i):
        for copy in copies(i):
            copy.start()

    @pl.loop(0, count)
    def _wait(i):
        for copy in copies(i):
            copy.wait()


def rows_to_pages(rows: Sequence[jax.Array], pools: Sequence[jax.Array], ids: jax.Array, span: jax.Array,
                  *, page_size: int) -> List[jax.Array]:
    """``pools`` with blocks ``[span[0], span[0] + span[1])`` of each of ``rows`` written at pages
    ``ids[blk]``. ``rows[i]``: ``[1, S, ...]``; ``pools[i]``: ``[num_pages, page_size, ...]`` of the same
    trailing shape and dtype; ``ids``: int32 ``[S / page_size]``, read inside the span only; ``span``: int32
    ``[2]``, the caller's to keep inside the row. The pools are updated in place where they are donated."""
    n = len(pools)
    if not n:
        return []
    for row, pool in zip(rows, pools):
        if row.shape[2:] != pool.shape[2:] or row.dtype != pool.dtype or pool.shape[1] != page_size:
            raise ValueError(f"row {row.shape} {row.dtype} does not lie in pages of {pool.shape} {pool.dtype}")
    # a page's tokens x kv heads as one run of rows: the pool's own bytes
    page_rows = [page_size * math.prod(pool.shape[2:-1]) for pool in pools]
    rows2 = [row.reshape(-1, row.shape[-1]) for row in rows]
    pools2 = [pool.reshape(pool.shape[0], r, pool.shape[-1]) for pool, r in zip(pools, page_rows)]
    interpret = _interpret()
    if not interpret and not all(tiles(r, pool.shape[-1], pool.dtype) for r, pool in zip(page_rows, pools)):
        moved = _plain(rows2, pools2, ids, span, page_rows)
    else:
        anywhere = pl.BlockSpec(memory_space=pl.ANY)
        moved = pl.pallas_call(
            lambda *refs: _kernel(*refs, page_rows=page_rows),
            out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools2],
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(1,), in_specs=[anywhere] * (2 * n), out_specs=[anywhere] * n,
                scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
            input_output_aliases={2 + n + i: i for i in range(n)},
            name=KERNEL_NAME,
            interpret=interpret,
        )(ids, span, *rows2, *pools2)
    return [out.reshape(pool.shape) for out, pool in zip(moved, pools)]

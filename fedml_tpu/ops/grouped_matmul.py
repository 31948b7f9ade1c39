"""Pallas TPU grouped matmul: rows sorted by expert, one weight matrix a group.

The routed layer (``models/moe.RoutedMoE``) sorts its (token, held expert)
pairs by expert and lays them out in TILES of ``tm`` rows, each tile holding
rows of ONE expert (a group's last tile is padded). This kernel multiplies
tile ``i`` of ``x [M, K]`` by ``w[tile_group[i]] [K, N]``:

* grid ``(M / tm, N / tn, K / tk)``, the row tile outermost; the weight block
  of a step is ``w[group, k-block, n-block]``, named by the scalar-prefetched
  ``tile_group``: an expert no tile names is never read. A block is copied
  only when its index differs from the step before.
* the block ``[tk, tn]`` follows the matrix (``block_sizes``). A matrix of at
  most ``WHOLE_MATRIX_BYTES`` is ONE block: every tile of a group then names
  the same block, so it stays in VMEM and each hit expert's matrix crosses HBM
  once a call, and a tile is one product written straight out. A larger
  matrix is cut into ``BLOCK_K x BLOCK_N`` blocks, the contraction innermost
  into an f32 accumulator; there tile ``i + 1`` of the same expert starts at
  block ``(0, 0)`` again, so every row tile reads its expert's whole matrix.
* what bounds a call: at one tile an expert (a decode step: one or two tokens
  an expert) the HBM reads of the experts that were hit, whatever the blocks.
  At several tiles an expert (a prefill) the MXU where the matrix is one
  block, and HBM where it is cut: a ``tm``-row tile against blocks it alone
  reads is ``tm`` FLOPs a weight byte, under the chip's ridge of 240 at any
  ``tm`` up to 128 (until PR 36 every matrix was cut, and this header said
  "the MXU": a 16,640-token prefill of 128 experts of ``2048 x 1024`` took
  24.1 ms a layer at 581 GB/s of weights; it takes 13.2: PERF.md, PR 36).
* only the first ``n_live[0]`` tiles hold rows. A tile past them does nothing:
  its block indices are those of the last live step, so no block is fetched
  and none is written. Its rows of the result are NOT written: the caller
  reads only the rows it laid out.

bf16 (input dtype) operands, f32 accumulation, result in the input dtype, as
the other kernels here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _dot_nn, _grid, _interpret

# targets for the weight block [tk, tn] of a matrix that is cut: 1536 x 1024 bf16
# is 3 MB a buffer, two in flight; the contraction side is the longer one so
# that a (tile, n-block) takes few steps
BLOCK_K = 1536
BLOCK_N = 1024
# a matrix up to this size is one block: two in flight are 8 MiB of VMEM_LIMIT
# beside the row tiles; the largest size the chip has timed (PERF.md, PR 36)
WHOLE_MATRIX_BYTES = 4 * 1024 * 1024
VMEM_LIMIT = 48 * 1024 * 1024


def _block(dim: int, target: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``target``; the whole ``dim`` where none does (tiny test sizes)."""
    best = 0
    for b in range(128, min(dim, target) + 1, 128):
        if dim % b == 0:
            best = b
    return best or dim


def tiles(K: int, N: int, tm: int, dtype) -> bool:
    """The compiled kernel's shape rule: both weight dims in whole 128-lane
    blocks and a row tile of whole sublane tiles of the dtype (16 at bf16).
    Interpret mode (the CPU) takes any shape."""
    sublanes = 8 * (4 // jnp.dtype(dtype).itemsize)
    return K % 128 == 0 and N % 128 == 0 and tm % sublanes == 0


def cut_blocks(K: int, N: int) -> tuple:
    """The ``BLOCK_K x BLOCK_N`` cut of a ``[K, N]`` matrix."""
    return _block(K, BLOCK_K), _block(N, BLOCK_N)


def block_sizes(K: int, N: int, dtype) -> tuple:
    """The weight block ``(tk, tn)`` of a ``[K, N]`` matrix, from its shape and
    dtype alone: the matrix whole where it fits ``WHOLE_MATRIX_BYTES``, else
    its cut."""
    if K * N * jnp.dtype(dtype).itemsize <= WHOLE_MATRIX_BYTES:
        return K, N
    return cut_blocks(K, N)


def _kernel_one_product(tg_ref, nl_ref, x_ref, w_ref, o_ref):
    """A tile whose contraction is one block: no accumulator, no first / last step."""

    @pl.when(pl.program_id(0) < nl_ref[0])
    def _():
        o_ref[...] = _dot_nn(x_ref[...], w_ref[...]).astype(o_ref.dtype)


def _kernel(tg_ref, nl_ref, x_ref, w_ref, o_ref, acc_ref):
    i, k = pl.program_id(0), pl.program_id(2)
    live = i < nl_ref[0]

    @pl.when(jnp.logical_and(live, k == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        acc_ref[...] += _dot_nn(x_ref[...], w_ref[...])

    @pl.when(jnp.logical_and(live, k == pl.num_programs(2) - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, tile_group, n_live, *, tm: int):
    """``x [M, K]`` in tiles of ``tm`` rows, ``w [E, K, N]``, ``tile_group [M /
    tm]`` int32 (the expert of each live tile), ``n_live [1]`` int32 (tiles that
    hold rows; they come first). Returns ``[M, N]`` in x's dtype; rows of tiles
    past ``n_live`` are unspecified."""
    return _grouped_matmul(x, w, tile_group, n_live, tm=tm, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_matmul(x, w, tile_group, n_live, *, tm: int, interpret: bool):
    tk, tn = block_sizes(w.shape[1], w.shape[2], w.dtype)
    return _tiled_call(x, w, tile_group, n_live, tm=tm, tk=tk, tn=tn, interpret=interpret)


def _tiled_call(x, w, tile_group, n_live, *, tm: int, tk: int, tn: int, interpret: bool):
    """The Mosaic call at weight blocks ``[tk, tn]`` (``block_sizes``' for the
    program; ``tools/grouped_matmul_sweep.py`` times others)."""
    M, K = x.shape
    E, _, N = w.shape
    if M % tm or K % tk or N % tn:
        raise ValueError(f"grouped_matmul: [{M}, {K}] x [{K}, {N}] is not whole tiles of {tm} rows "
                         f"and blocks of {tk} x {tn}")
    nj, nk = N // tn, K // tk

    def at(i, j, k, tg, nl):
        """Block indices of a step; a tile past the live ones repeats the last live step's."""
        dead = i >= nl[0]
        last = jnp.maximum(nl[0] - 1, 0)
        return (jnp.where(dead, last, i), jnp.where(dead, nj - 1, j), jnp.where(dead, nk - 1, k))

    def x_map(i, j, k, tg, nl):
        ii, _, kk = at(i, j, k, tg, nl)
        return ii, kk

    def w_map(i, j, k, tg, nl):
        ii, jj, kk = at(i, j, k, tg, nl)
        return tg[ii], kk, jj

    def o_map(i, j, k, tg, nl):
        ii, jj, _ = at(i, j, k, tg, nl)
        return ii, jj

    return pl.pallas_call(
        _kernel_one_product if nk == 1 else _kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, nj, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map), pl.BlockSpec((None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[] if nk == 1 else [pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=_grid("arbitrary", "arbitrary", "arbitrary", vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_matmul",
    )(tile_group.astype(jnp.int32), n_live.astype(jnp.int32), x, w)


def grouped_matmul_reference(x, w, tile_group, n_live, *, tm: int):
    """The plain formulation, same arguments: every tile against its group's
    matrix, gathered (``[M / tm, K, N]`` of weights: tests only). Dead tiles
    give zeros."""
    M, K = x.shape
    n_tiles = M // tm
    live = jnp.arange(n_tiles) < n_live[0]
    wt = w[jnp.where(live, tile_group, 0)]  # [n_tiles, K, N]
    y = jnp.einsum("tmk,tkn->tmn", x.reshape(n_tiles, tm, K), wt, preferred_element_type=jnp.float32)
    return jnp.where(live[:, None, None], y, 0.0).astype(x.dtype).reshape(M, -1)

"""Pallas TPU one-token step of a Mamba-1 layer over the engine's slots, in place.

The serving engine's decode step advances every slot of the pool by one token
a layer. A slot whose request has finished (``cache_idx < 0``) must keep its
state, so the plain formulation computes the update for all ``B`` slots and
writes back ``where(live, h, h0)``: the whole ``[B, N, d]`` float32 state (21 MB
a layer at Jamba's widths) is read and written whatever the live count. This
kernel moves only the live slots' state:

* the live slots' ids and their count are scalar-prefetched, made on the
  device from the mask (``live_slots``); the host changes nothing;
* the state ``h`` ``[B, N, d]`` stays in HBM, aliased in to out, so a slot the
  kernel does not copy keeps its bits: a dead slot is never read or written;
* a live slot's ``h`` crosses HBM once each way, by manual DMAs through a ring
  of ``GROUP`` VMEM buffers: while one slot computes, the reads of the next
  ``GROUP - 2`` are in flight and the write of the one before drains, so a
  layer's call costs about what its live bytes cost;
* a grid step takes ``GROUP`` live positions (the per-row inputs arrive in live
  order, ``GROUP`` rows a block); a step past the live count keeps the last
  live group's blocks, so it copies nothing and its ``y`` block, never written,
  is not written back over a live one. ``y`` is 0 on a dead row.

The arithmetic is the plain formulation's, all float32 (``h`` is held
TRANSPOSED, ``[N, d]``, channels on the lanes, as ``ops/selective_scan.py``
holds it):

    h = exp(dt (x) A) * h0 + (dt * c) (x) B_t,   y = sum_n h * C_t + D * c

The conv tail (``[B, K-1, d]`` bfloat16, 1/10 of a Jamba slot's bytes) is not
the kernel's: the compiled step lays that leaf out with the slots on the
sublanes (``{2,0,1}``), so one slot's tail is a row of each of ``K-1`` tiles,
nothing a DMA takes alone; the mixer keeps its select over all slots
(``models/mamba.py``). ``ssm_step_reference`` is the plain formulation with
the same arguments and results: the tests' yardstick, ``generate()``'s step
and what a shape the kernel cannot tile runs (``models/mamba._ssm_step_impl``
decides from shapes, before anything runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret
from .selective_scan import block_d

GROUP = 8        # live slots a grid step, and VMEM buffers in the ring: one f32 sublane tile of rows
KERNEL_NAME = "ssm_step"


def tiles(d_inner: int, d_state: int) -> bool:
    """The compiled kernel's shape rule: whole 128-lane vregs of channels,
    whole sublane tiles of state. Interpret mode (the CPU) takes any shape."""
    return d_inner % 128 == 0 and d_state % 8 == 0


def live_slots(live):
    """The live slots' ids in ascending order, padded with 0 to ``[B]``, and
    their count ``[1]``, both int32: the kernel's scalar-prefetched operands."""
    B = live.shape[0]
    ids = jnp.nonzero(live, size=B, fill_value=0)[0].astype(jnp.int32)
    return ids, jnp.sum(live.astype(jnp.int32)).reshape(1)


def _kernel(ids_ref, n_ref, dt_ref, c_ref, b_ref, ct_ref, a_ref, dskip_ref, h_in,
            y_ref, h_out, hbuf, sem, *, n_steps: int, bd: int):
    g = pl.program_id(0)
    count = n_ref[0]
    d = a_ref.shape[1]

    def copy(k, j, out: bool):  # live position k's state between HBM and buffer j
        if out:
            return pltpu.make_async_copy(hbuf.at[j], h_out.at[ids_ref[k]], sem.at[1, j])
        return pltpu.make_async_copy(h_in.at[ids_ref[k]], hbuf.at[j], sem.at[0, j])

    def start(k, j, out: bool):
        copy(k, j, out).start()

    def wait(k, j, out: bool):
        copy(k, j, out).wait()

    @pl.when(g == 0)
    def _prologue():  # the first GROUP - 2 live slots' reads
        for k in range(GROUP - 2):
            @pl.when(k < count)
            def _():
                start(k, k, False)

    @pl.when(g * GROUP < count)
    def _group():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    for j in range(GROUP):  # buffer j holds live position k: k % GROUP == j
        k = g * GROUP + j

        @pl.when(k < count)
        def _slot():
            # the buffer of position k - 2 takes position k + GROUP - 2 once its write is done
            jn = (j - 2) % GROUP

            @pl.when(k >= 2)
            def _():
                wait(k - 2, jn, True)

            @pl.when(k + GROUP - 2 < count)
            def _():
                start(k + GROUP - 2, jn, False)

            wait(k, j, False)
            b, ct = b_ref[0, :, j:j + 1], ct_ref[0, :, j:j + 1]     # [N, 1]

            def chunk(i, _):
                sl = pl.ds(pl.multiple_of(i * bd, bd), bd)
                dt, c = dt_ref[j:j + 1, sl], c_ref[j:j + 1, sl]       # [1, bd]
                h = jnp.exp(dt * a_ref[:, sl]) * hbuf[j, :, sl] + (dt * c) * b
                hbuf[j, :, sl] = h
                y_ref[j:j + 1, sl] = jnp.sum(h * ct, axis=0, keepdims=True) + dskip_ref[:, sl] * c
                return 0

            jax.lax.fori_loop(0, d // bd, chunk, 0)
            start(k, j, True)

    @pl.when(g == n_steps - 1)
    def _epilogue():  # the writes no later slot waited for
        for back in (2, 1):
            k = count - back

            @pl.when(k >= 0)
            def _():
                wait(k, k % GROUP, True)


def ssm_step(h, dt, c, b, c_t, a_t, d_skip, live):
    """One token a slot. ``h`` ``[B, N, d]`` float32: the slots' state; ``dt``,
    ``c`` ``[B, d]``; ``b``, ``c_t`` ``[B, N]``; ``a_t`` ``[N, d]`` (negative);
    ``d_skip`` ``[d]``; ``live`` ``[B]`` bool. Returns ``y`` ``[B, d]`` float32
    (0 on a dead row) and the state, a dead slot's bits unchanged. The state is
    updated in place where it is donated. On the TPU the state has to be
    donated or carried by a loop, as the engine's pool is: where the program
    must keep the caller's copy, the compiler's memory-space assignment fails
    on the aliased HBM operand (jax 0.9.0 / libtpu 0.0.34)."""
    return _ssm_step(h, dt, c, b, c_t, a_t, d_skip, live, interpret=_interpret())


# jitted so that the layers of a model share ONE trace and ONE Mosaic lowering
@functools.partial(jax.jit, static_argnames="interpret")
def _ssm_step(h, dt, c, b, c_t, a_t, d_skip, live, *, interpret: bool):
    f32 = jnp.float32
    B, N, D = h.shape
    ids, count = live_slots(live)
    Bp = -(-B // GROUP) * GROUP

    def rows(x):  # [B, ...] -> the live rows first, in ascending slot order, padded to whole groups
        x = x.astype(f32)[ids]
        return jnp.pad(x, [(0, Bp - B)] + [(0, 0)] * (x.ndim - 1))

    def lanes(x):  # [B, N] -> [Bp/GROUP, N, GROUP]: a row of a group is a lane
        return rows(x).reshape(Bp // GROUP, GROUP, N).swapaxes(1, 2)

    def group(g, ids, count):  # a step past the live count keeps the last live group's blocks: no copy
        return jnp.minimum(g, jnp.maximum(count[0] - 1, 0) // GROUP)

    row = pl.BlockSpec((GROUP, D), lambda g, *ref: (group(g, *ref), 0))
    col = pl.BlockSpec((1, N, GROUP), lambda g, *ref: (group(g, *ref), 0, 0))
    # the state's memory space is pinned on the chip: left to the compiler, it may
    # stage the whole pool in VMEM around the call (the interpreter takes ANY only)
    space = pl.ANY if interpret else pltpu.HBM
    hbm = pl.BlockSpec(memory_space=space)
    state = h.astype(f32) if interpret else pltpu.with_memory_space_constraint(h.astype(f32), space)
    n_steps = Bp // GROUP
    y, h = pl.pallas_call(
        functools.partial(_kernel, n_steps=n_steps, bd=block_d(D)),
        out_shape=(jax.ShapeDtypeStruct((Bp, D), f32),
                   jax.ShapeDtypeStruct(h.shape, f32) if interpret else pltpu.HBM(h.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_steps,),
            in_specs=[row, row, col, col,
                      pl.BlockSpec((N, D), lambda g, *_: (0, 0)),
                      pl.BlockSpec((1, D), lambda g, *_: (0, 0)),
                      hbm],
            out_specs=(row, hbm),
            scratch_shapes=[pltpu.VMEM((GROUP, N, D), f32), pltpu.SemaphoreType.DMA((2, GROUP))],
        ),
        # the grid runs in order: the ring of buffers and its DMAs cross grid steps
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        input_output_aliases={8: 1},
        interpret=interpret,
        name=KERNEL_NAME,
    )(ids, count, rows(dt), rows(c), lanes(b), lanes(c_t), a_t.astype(f32), d_skip.astype(f32).reshape(1, D),
      state)
    pos = jnp.cumsum(live.astype(jnp.int32)) - 1  # a live slot's position in live order
    return jnp.where(live[:, None], y[jnp.maximum(pos, 0)], 0.0), h


def ssm_step_reference(h, dt, c, b, c_t, a_t, d_skip, live=None):
    """The plain formulation, same arguments and results: every slot's update
    computed, a dead slot's state put back by a select over all ``B``.
    ``live=None``: every row is live (``generate()``'s loop), no select."""
    f32 = jnp.float32
    h0, dt, c = h.astype(f32), dt.astype(f32), c.astype(f32)
    h1 = jnp.exp(dt[:, None, :] * a_t.astype(f32)) * h0 + (dt * c)[:, None, :] * b.astype(f32)[:, :, None]
    y = jnp.sum(h1 * c_t.astype(f32)[:, :, None], axis=1) + d_skip.astype(f32) * c
    if live is None:
        return y, h1
    return jnp.where(live[:, None], y, 0.0), jnp.where(live[:, None, None], h1, h0)

"""Pallas TPU selective scan — the prefill recurrence of a Mamba-1 layer.

A state-space layer carries, per channel ``d`` of its inner width and per state
index ``n``, the recurrence

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d] + dt_t[d] * u_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n] + D[d] * u_t[d]

over the tokens of a prompt. A token-by-token ``lax.scan`` is one tiny program
a token; an associative scan materialises ``[T, N, d]`` in HBM a layer. This
kernel keeps the state on the chip:

* grid ``(rows, channel blocks, token chunks)``; the chunk axis runs in order
  and the state of a (row, channel block) stays in VMEM between its chunks, in
  the output block that the last chunk leaves behind. HBM sees ``u``, ``dt`` and
  ``y`` once (``[T, d]`` each) and the state twice (in, out).
* the state is held TRANSPOSED, ``[N, d]``: channels on the 128 lanes, the
  ``N`` state indices on sublanes, so one token's update is whole-vreg VPU work
  (one ``exp`` on the EUP, five multiplies/adds a state element) and ``y_t`` a
  sublane reduction. ``B_t[n]`` and ``C_t[n]`` must be broadcast along lanes:
  they arrive as ``[T/8, N, 8]`` tiles (a free reshape + a small transpose
  outside), a token of the tile is a static lane slice.
* a true length and one snapshot position, both runtime scalars a row: tokens
  at or past ``length`` act as ``dt = 0`` (``exp(0) = 1`` and no input: the
  state stands still), so the state after the last chunk IS the state at the
  true length whatever the padding; the state after ``snap`` tokens is kept
  beside it (``snap = 0`` keeps the initial state). The serving engine's
  prefix cache stores that second state at a page boundary
  (``serving/paged_kv.py``).

All arithmetic is float32. ``selective_scan_reference`` is the plain
``jax.numpy`` formulation with the same arguments: the tests' yardstick, and
what a shape the kernel cannot tile runs (``models/mamba._selective_scan_impl``
decides from shapes, before anything runs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _grid, _interpret

SUB = 8          # tokens unrolled in one pass of the inner loop: one f32 sublane tile
BLOCK_T = 256    # most tokens of one chunk (a grid step)
BLOCK_D = 512    # channels (lanes) of one program: [16, 512] f32 of state is 8 vregs
KERNEL_NAME = "selective_scan"


def block_t(T: int) -> int:
    """The largest multiple of ``SUB`` up to ``BLOCK_T`` that divides T."""
    return max(c for c in range(SUB, min(BLOCK_T, T) + 1, SUB) if T % c == 0)


def block_d(d_inner: int) -> int:
    for bd in (BLOCK_D, 256, 128):
        if d_inner % bd == 0:
            return bd
    return d_inner


def tiles(T: int, d_inner: int, d_state: int) -> bool:
    """The compiled kernel's hard shape rule: whole sublane tiles of tokens,
    whole 128-lane vregs of channels, whole sublane tiles of state. Interpret
    mode (the CPU) needs only ``T % SUB == 0``."""
    return T % SUB == 0 and d_inner % 128 == 0 and d_state % 8 == 0


def _kernel(len_ref, snap_ref, dt_ref, u_ref, b_ref, c_ref, a_ref, dskip_ref, h0_ref,
            y_ref, hlen_ref, hsnap_ref, *, bt: int):
    row, chunk = pl.program_id(0), pl.program_id(2)
    length, snap = len_ref[row], snap_ref[row]

    @pl.when(chunk == 0)
    def _():
        hlen_ref[0] = h0_ref[0]
        hsnap_ref[0] = h0_ref[0]

    a = a_ref[...]            # [N, bd]
    dskip = dskip_ref[...]    # [1, bd]
    bd = a.shape[1]
    t0 = chunk * bt
    tok = jax.lax.broadcasted_iota(jnp.int32, (SUB, bd), 0)

    def sub(i, carry):
        h, hs = carry
        r = pl.multiple_of(i * SUB, SUB)
        base = t0 + r
        u8 = u_ref[0, pl.ds(r, SUB), :]                              # [8, bd]
        dt8 = jnp.where(tok + base < length, dt_ref[0, pl.ds(r, SUB), :], 0.0)
        dtu8 = dt8 * u8
        b8, c8 = b_ref[0, i], c_ref[0, i]                            # [N, 8]
        y8 = dskip * u8
        for j in range(SUB):
            h = jnp.exp(dt8[j:j + 1, :] * a) * h + dtu8[j:j + 1, :] * b8[:, j:j + 1]
            y_j = jnp.sum(h * c8[:, j:j + 1], axis=0, keepdims=True)  # [1, bd]
            y8 = jnp.where(tok == j, y8 + y_j, y8)
            hs = jnp.where(base + (j + 1) == snap, h, hs)
        y_ref[0, pl.ds(r, SUB), :] = y8
        return h, hs

    h, hs = jax.lax.fori_loop(0, bt // SUB, sub, (hlen_ref[0], hsnap_ref[0]))
    hlen_ref[0] = h
    hsnap_ref[0] = hs


def selective_scan(u, dt, a_t, b, c, d_skip, h0, length, snap):
    """``u``, ``dt`` ``[B, T, d]``; ``a_t`` ``[N, d]`` (negative); ``b``, ``c``
    ``[B, T, N]``; ``d_skip`` ``[d]``; ``h0`` ``[B, N, d]``; ``length``, ``snap``
    ``[B]`` int32. Returns ``y`` ``[B, T, d]``, the state after ``length`` tokens
    and the state after ``snap`` tokens (both ``[B, N, d]``), all float32.
    ``snap`` is meant to lie in ``0..length``; past it, it reads the state at
    ``length``."""
    return _selective_scan(u, dt, a_t, b, c, d_skip, h0, length, snap,
                           interpret=_interpret())


# jitted so that the layers of a model share ONE trace and ONE Mosaic lowering
@functools.partial(jax.jit, static_argnames="interpret")
def _selective_scan(u, dt, a_t, b, c, d_skip, h0, length, snap, *, interpret: bool):
    f32 = jnp.float32
    B, T, D = u.shape
    N = a_t.shape[0]
    bt, bd = block_t(T), block_d(D)

    def lanes8(x):  # [B, T, N] -> [B, T/8, N, 8]: a token of a tile is a lane
        return x.astype(f32).reshape(B, T // SUB, SUB, N).swapaxes(2, 3)

    seq = pl.BlockSpec((1, bt, bd), lambda r, d, t, *_: (r, t, d))
    bc = pl.BlockSpec((1, bt // SUB, N, SUB), lambda r, d, t, *_: (r, t, 0, 0))
    state = pl.BlockSpec((1, N, bd), lambda r, d, t, *_: (r, 0, d))
    shape_h = jax.ShapeDtypeStruct((B, N, D), f32)
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        out_shape=(jax.ShapeDtypeStruct((B, T, D), f32), shape_h, shape_h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, D // bd, T // bt),
            in_specs=[seq, seq, bc, bc,
                      pl.BlockSpec((N, bd), lambda r, d, t, *_: (0, d)),
                      pl.BlockSpec((1, bd), lambda r, d, t, *_: (0, d)),
                      state],
            out_specs=(seq, state, state),
        ),
        compiler_params=_grid("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=KERNEL_NAME,
    )(length.astype(jnp.int32), snap.astype(jnp.int32), dt.astype(f32), u.astype(f32),
      lanes8(b), lanes8(c), a_t.astype(f32), d_skip.astype(f32).reshape(1, D), h0.astype(f32))


def selective_scan_reference(u, dt, a_t, b, c, d_skip, h0, length, snap):
    """The plain formulation, same arguments and results: one ``lax.scan`` step
    a token over ``[B, N, d]`` states."""
    f32 = jnp.float32
    u, dt, b, c = (x.astype(f32) for x in (u, dt, b, c))
    a_t, d_skip, h0 = a_t.astype(f32), d_skip.astype(f32), h0.astype(f32)
    T = u.shape[1]
    dt = jnp.where(jnp.arange(T)[None, :, None] < length[:, None, None], dt, 0.0)

    def step(carry, x):
        h, hs = carry
        t, u_t, dt_t, b_t, c_t = x
        h = jnp.exp(dt_t[:, None, :] * a_t) * h + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        y_t = jnp.sum(h * c_t[:, :, None], axis=1) + d_skip * u_t
        hs = jnp.where((t + 1 == snap)[:, None, None], h, hs)
        return (h, hs), y_t

    xs = (jnp.arange(T), u.swapaxes(0, 1), dt.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1))
    (h, hs), y = jax.lax.scan(step, (h0, h0), xs)
    return y.swapaxes(0, 1), h, hs

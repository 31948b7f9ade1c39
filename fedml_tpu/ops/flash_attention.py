"""Pallas TPU flash attention — forward AND backward kernels, GQA-native.

The hot op of the LLM path (per /opt/skills/guides/pallas_guide.md). Design:

* forward: grid over (batch*q_heads, query blocks); each program holds one q
  block in VMEM and streams K/V for its KV head through the MXU in k-blocks.
  The [T, T] score matrix never exists in HBM. Saves the per-row logsumexp
  so the backward can rebuild probabilities without a second softmax pass.
* backward: two kernels, both streaming — dQ over (BHq, q blocks) consuming
  K/V blocks, and dK/dV over (BHkv, k blocks) consuming the Q/dO blocks of
  every query head in its group. Each recomputes its score tile from the
  saved logsumexp (p = exp(s - lse)), so the backward is O(T) memory too:
  this is what lets training peak memory drop vs the einsum path, whose
  [B, H, T, T] probs tensor sits in HBM exactly where the step peaks
  (VERDICT r2 weak #2).
* GQA (n_kv_heads < n_heads) is native: K/V are NEVER repeated to the query
  head count — the kernels map each query head to its KV head through the
  BlockSpec index maps, cutting K/V HBM traffic by the group size G
  (``repeat_kv`` in the einsum path materializes G copies).

The tile schedule (PR 30; the sweep's table and the trace are in PERF.md):

* blocks come from the shape, :func:`block_sizes`: per side the largest rung
  of ``LADDER`` (512, 256, 128) that divides the LOCAL sequence length, one
  block below 128. An inner iteration costs about a microsecond beside its
  matmuls whatever the tile, so the tile is as large as pays: at T = 2,048,
  D = 128 on v5e a forward call takes 7.0 ms at 128x128 and 1.4 ms at
  512x512; 1,024 loses again, because a causal kernel computes
  ``T * (T + block) / 2`` scores. Whole-T operands plus a 512x512 tile's f32
  temporaries pass Mosaic's default 16 MiB of scoped VMEM from about
  T = 16,384, so every call asks for what its blocks need (``_vmem_limit``:
  at least 32 MiB, at most 100 of the core's 128).
* causal masking by global row/col index, with block-level skipping on both
  sides of the diagonal (forward + dQ skip fully-masked k-blocks; dK/dV skips
  fully-masked q-blocks), so causal costs ~half the FLOPs of dense; and only
  the blocks the diagonal crosses build and apply the mask. Their number is
  static (one block size divides the other), so they are straight-line code
  beside the loop over the unmasked blocks.
* no loop where the sequence is short: a loop whose trip count depends on
  the grid index costs about 0.7 us an iteration beside its work, because
  nothing is scheduled across its edge. Up to ``UNROLL_BLOCKS`` blocks a side
  (T = 2,048 at 512) each block index gets its own straight-line program
  with every slice static (``_each_block``): forward 2.14 -> 1.40 ms a call,
  dK/dV 2.87 -> 2.49, against two loops; longer sequences loop.
* dK/dV works on the TRANSPOSED tile [block_k, block_q]: dV = P^T dO and
  dK = dS^T Q are then plain matmuls (no [block_q, block_k] tile is ever
  transposed), and lse / delta reach it as lane-dense [1, block_q] rows.
  The forward and dQ keep [block_q, 1] statistics: replicated over 128 lanes
  they measured the same (forward) or 4 % slower (dQ) at 512 blocks.

Compute is fp32 in-kernel (scores, exp, statistics, accumulators), bf16 only
where the operands are: the matmul inputs, and ``p`` / ``dS`` cast to the
operand dtype for the second matmul. Outputs in the input dtype.

On the chip (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21): all three kernels
compile and match XLA attention for T 8..16384 and head_dim 16..256, MHA and
GQA, bf16 and f32. K/V (and, in dK/dV, Q/dO) arrive as whole (1, T, D) VMEM
blocks, so sequence length is bounded by VMEM: T=32768 fails at compile time
with RESOURCE_EXHAUSTED in vmem — an error the caller sees, not a fallback.

Reference parity: ``train/llm/models/attention.py`` (the reference's
flash-attn flag on GPT-NeoX) — here the kernel is native to the framework
rather than an external CUDA dependency.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

log = logging.getLogger(__name__)

NEG_INF = -1e30

# The rungs a block may take, largest first. They are the code's constants,
# from a sweep on the chip (tools/flash_block_sweep.py; PERF.md, Findings
# PR 30); callers may pass explicit blocks. A change to them is a measured
# perf change with a ledger entry, never an environment variable or an
# untracked file.
LADDER = (512, 256, 128)
# f32 [block_q, block_k] temporaries one inner iteration keeps live (scores,
# probabilities and their cast; the backward also dP and dS) and the bytes a
# tile may fill with them before a side is halved
_TILE_TEMPS = {"fwd": 4, "dq": 6, "dkv": 6}
_TILE_BUDGET = 16 * 2 ** 20
# scoped VMEM a call may ask for (v5e: 128 MiB a core, Mosaic's default 16)
VMEM_FLOOR = 32 * 2 ** 20
VMEM_CEILING = 100 * 2 ** 20
# most values of a block index, and most steps of a loop, that become
# straight-line code (_each_block, _loop): T up to 2,048 at 512 blocks
UNROLL_BLOCKS = 4


def _interpret() -> bool:
    """Kernels run compiled on the TPU and interpreted on the CPU (tests,
    ``chip_smoke.py --dry-run-cpu``). Any other platform is an error — never
    a silent interpret-mode run that would pass for the real kernel."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"flash_attention supports platforms 'tpu' (compiled) and 'cpu' "
        f"(interpreted), not {platform!r}")


def _grid(*dimension_semantics, vmem_limit_bytes=None):
    """Mosaic grid semantics: 'parallel' dims can be pipelined/partitioned
    freely; 'arbitrary' preserves iteration order (the dkv kernel's
    accumulating revisits need it). Interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def _tile_bytes(kind: str, block_q: int, block_k: int, D: int) -> int:
    """f32 bytes one inner iteration keeps live: its [block_q, block_k]
    temporaries and the [block, D] accumulators and operand slices."""
    return 4 * (_TILE_TEMPS[kind] * block_q * block_k + 2 * (block_q + block_k) * D)


def block_sizes(T: int, D: int, kind: str):
    """``(block_q, block_k)`` of kernel ``kind`` ('fwd', 'dq', 'dkv') at
    LOCAL sequence length ``T`` and head_dim ``D``: the largest rung of
    ``LADDER`` that divides ``T``, a side halved while the tile's f32
    temporaries pass ``_TILE_BUDGET`` (only a head_dim in the thousands
    does). ``T`` below the smallest rung is one block. Raises where no rung
    divides ``T``. The operands' dtype does not enter: scores and
    accumulators are f32 whatever it is."""
    if T < LADDER[-1]:
        return T, T
    rung = next((r for r in LADDER if T % r == 0), None)
    if rung is None:
        raise ValueError(
            f"flash_attention: no block of {LADDER} tiles seq_len {T}; "
            "pad the sequence or use attention_impl='xla'")
    bq = bk = rung
    while _tile_bytes(kind, bq, bk, D) > _TILE_BUDGET and max(bq, bk) > LADDER[-1]:
        bq, bk = (bq // 2, bk) if bq >= bk else (bq, bk // 2)
    return bq, bk


def tiles(seq_len: int, block_q=None, block_k=None) -> bool:
    """The kernel's hard shape rule: the blocks (clamped to the sequence)
    must tile it, and one must divide the other (the blocks the diagonal
    crosses are then a static number) — with the default choice, any length
    below the smallest rung or a multiple of it. An explicit ``"pallas"``
    request that fails it raises."""
    bq = min(block_q or LADDER[-1], seq_len)
    bk = min(block_k or LADDER[-1], seq_len)
    return seq_len % bq == 0 and seq_len % bk == 0 and (bq % bk == 0 or bk % bq == 0)


def _mxu_precision(a):
    """bf16 operands ride the MXU natively (f32 accumulation via
    preferred_element_type) and that is the ONLY contraction Mosaic accepts
    for them: under an ambient ``jax.default_matmul_precision("highest")`` a
    bf16 matmul lowers to contract_precision<fp32> and fails to compile ("Bad
    lhs type", seen on v5e). So bf16 pins DEFAULT; f32 operands keep the
    ambient precision (``highest`` gives a true f32 contraction — what the
    parity checks run under)."""
    return jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None


def _dot_nt(a, b):
    """[m, k] x [n, k] -> [m, n] f32: contract the trailing dims WITHOUT
    casting the operands up — bf16 inputs ride the MXU at full bf16 rate
    with f32 accumulation (preferred_element_type); an up-front
    .astype(f32) would force the ~4x-slower f32 matmul path."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_mxu_precision(a),
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    """[m, k] x [k, n] -> [m, n] f32 accumulate (see _dot_nt)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_mxu_precision(a),
                               preferred_element_type=jnp.float32)


def _loop(lo, hi, body, carry):
    """``fori_loop``, or the same few steps as straight-line code where the
    bounds are static (see :func:`_each_block`)."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= UNROLL_BLOCKS:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _imax(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.maximum(a, b)


def _imin(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) else jnp.minimum(a, b)


def _visit_blocks(step, carry, i, block_i: int, block_j: int, num_j: int,
                  causal: bool, visible_before: bool, window: int = 0):
    """Fold ``step(j, carry, masked)`` over the j-blocks that i-block ``i``
    sees — the causal geometry of all three kernels, in one place so that
    their visit sets cannot diverge. The diagonal crosses the j-blocks
    ``[first, first + n)``, ``n`` static because one block size divides the
    other (:func:`tiles`): only they build and apply the mask, as
    straight-line code. For a q-block (forward, dQ: j runs over k-blocks,
    ``visible_before``) the blocks before them are wholly visible and the
    ones after are never streamed; for a k-block (dK/dV: j runs over
    q-blocks) the ones before are skipped and the ones after wholly visible.

    With ``window`` (a query at row t sees the keys ``t - window < j <= t``)
    the visible side is cut off at the horizon: the blocks wholly behind it
    are not visited at all, so a row costs ``window`` keys whatever T, and the
    few blocks the horizon crosses are masked like the diagonal's. For a
    q-block rows ``[r0, r1]`` that is the k-blocks from the one holding column
    ``r0 - window + 1``; those up to the one holding ``r1 - window`` are masked.
    For a k-block columns ``[c0, c1]`` the q-blocks up to the one holding row
    ``c1 + window - 1``; those from the one holding ``c0 + window`` are masked."""
    unmasked = functools.partial(step, masked=False)
    if not causal:
        return _loop(0, num_j, unmasked, carry)
    first, n = (i * block_i) // block_j, max(1, block_i // block_j)
    if not window:
        if visible_before:
            carry = _loop(0, first, unmasked, carry)
        for d in range(n):
            carry = step(first + d, carry, masked=True)
        if not visible_before:
            carry = _loop(first + n, num_j, unmasked, carry)
        return carry
    masked = functools.partial(step, masked=True)
    i_lo = i * block_i
    i_hi = i_lo + block_i - 1
    if visible_before:
        lo = _imax(i_lo - window + 1, 0) // block_j
        # k-blocks whose first column is at or behind a row's horizon: the first ceil((r1 - W + 1) / bk)
        edge = _imin(_imax((_imax(i_hi - window + 1, 0) + block_j - 1) // block_j, lo), first)
        carry = _loop(lo, edge, masked, carry)
        carry = _loop(edge, first, unmasked, carry)
    for d in range(n):
        carry = step(first + d, carry, masked=True)
    if not visible_before:
        hi = _imin((i_hi + window - 1) // block_j + 1, num_j)
        edge = _imin(_imax((i_lo + window) // block_j, first + n), hi)
        carry = _loop(first + n, edge, unmasked, carry)
        carry = _loop(edge, hi, masked, carry)
    return carry


def _visible(row0, col0, shape, row_axis: int, window: int = 0):
    """Causal mask of one tile whose first query row is ``row0`` and first
    key column ``col0``; queries run along ``row_axis`` of ``shape``. With
    ``window`` a row also loses the columns at or behind ``row - window``."""
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, row_axis)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - row_axis)
    if window:
        return jnp.logical_and(col <= row, col > row - window)
    return col <= row


def _block(ref, i, block: int):
    """Rows ``[i*block, (i+1)*block)`` of a whole-T (1, T, D) VMEM operand."""
    start = i * block if isinstance(i, int) else pl.multiple_of(i * block, block)
    return ref[0, pl.ds(start, block), :]


def _each_block(index, count: int, causal: bool, program):
    """``program(index)`` for the block a grid step works on. A causal
    kernel's work depends on that index (how many blocks lie before the
    diagonal), and a loop whose trip count the compiler cannot see costs about
    0.7 us an iteration beside its work (nothing overlaps across its edge): so
    where the index takes at most ``UNROLL_BLOCKS`` values, each value gets
    its own straight-line program (every block index static), picked by
    ``pl.when``. Code grows with ``count ** 2``; longer sequences loop."""
    if not causal or count > UNROLL_BLOCKS:
        program(index)
        return
    for value in range(count):
        pl.when(index == value)(functools.partial(program, value))


# --- forward -----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int, block_k: int,
                causal: bool, scale: float, window: int = 0):
    q = q_ref[0]  # [block_q, D], input dtype — matmuls accumulate in f32
    T = k_ref.shape[1]
    D = q.shape[-1]

    def program(qi):
        def step(kj, carry, masked: bool):
            m, l, acc = carry
            k_blk = _block(k_ref, kj, block_k)
            v_blk = _block(v_ref, kj, block_k)
            # scale AFTER the matmul (in f32): pre-scaling bf16 q would round
            s = _dot_nt(q, k_blk) * scale  # [block_q, block_k] on the MXU
            if masked:
                vis = _visible(qi * block_q, kj * block_k, s.shape, 0, window)
                s = jnp.where(vis, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            # a masked entry gives exp(NEG_INF - m_new) = 0 exactly: m_new is
            # finite from the first block on (column 0 is visible to every row)
            p = jnp.exp(s - m_new)
            if masked and window:
                # behind a horizon a row may see nothing of its first block:
                # m_new is still NEG_INF there and exp(0) would count
                p = jnp.where(vis, p, 0.0)
            l_new = l * corr + p.sum(axis=-1, keepdims=True)
            # p back to the input dtype for the AV matmul (f32 accumulate) —
            # the canonical flash mixed-precision recipe
            acc_new = acc * corr + _dot_nn(p.astype(v_blk.dtype), v_blk)
            return m_new, l_new, acc_new

        # row stats kept 2D [block_q, 1]: Mosaic vectorizes (sublane, lane)
        # tiles, 1D vectors lower poorly; replicated over 128 lanes they
        # measured the same
        carry = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
                 jnp.zeros((block_q, 1), jnp.float32),
                 jnp.zeros((block_q, D), jnp.float32))
        carry = _visit_blocks(step, carry, qi, block_q, block_k, T // block_k, causal, True, window)
        m, l, acc = carry
        l_safe = jnp.maximum(l, 1e-20)
        o_ref[0] = (acc * (1.0 / l_safe)).astype(o_ref.dtype)
        lse_ref[0] = m + jnp.log(l_safe)

    _each_block(pl.program_id(1), T // block_q, causal, program)


def _kv_index(Hq: int, Hkv: int):
    """Program index over [B*Hq] -> block index into [B*Hkv]: query head h
    attends to kv head h // (Hq//Hkv)."""
    G = Hq // Hkv

    def index(i, j):
        return ((i // Hq) * Hkv + (i % Hq) // G, 0, 0)

    return index


def _vmem_limit(kind: str, block_q: int, block_k: int, D: int, resident_bytes: int) -> int:
    """Scoped VMEM a call asks for: its pipelined operand and result blocks
    twice (double buffering) and the tile's temporaries with half again of
    room, between ``VMEM_FLOOR`` and ``VMEM_CEILING``."""
    need = 2 * resident_bytes + 3 * _tile_bytes(kind, block_q, block_k, D) // 2
    return int(min(VMEM_CEILING, max(VMEM_FLOOR, need)))


# the impls are jitted so that the layers of a model (and the forward's second
# run under remat) share ONE trace and ONE Mosaic lowering of each kernel
@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "Hq", "Hkv", "interpret", "window"))
def _fwd_impl(q, k, v, *, causal: bool, block_q: int, block_k: int, Hq: int,
              Hkv: int, interpret: bool, window: int = 0):
    """q [B*Hq, T, D]; k/v [B*Hkv, T, D] -> (out [B*Hq, T, D], lse f32)."""
    BHq, T, D = q.shape
    scale = D ** -0.5
    grid = (BHq, T // block_q)
    kv_idx = _kv_index(Hq, Hkv)
    item = q.dtype.itemsize
    resident = 2 * T * D * item + 2 * block_q * D * item + block_q * 128 * 4
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, **({"window": window} if window else {})),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            # the residual keeps a trailing singleton lane dim: Mosaic requires
            # the last two block dims be (8k, 128k) or equal the array dims,
            # and (block_q, 1) on an array whose last dim IS 1 satisfies that
            # (compiled and parity-checked on v5e, PR 21). The benchmark's
            # trace reader tells the forward call by this [.., T, 1] result.
            jax.ShapeDtypeStruct((BHq, T, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, T, D), kv_idx),
            pl.BlockSpec((1, T, D), kv_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ),
        compiler_params=_grid(
            "parallel", "parallel",
            vmem_limit_bytes=_vmem_limit("fwd", block_q, block_k, D, resident)),
        interpret=interpret,
    )(q, k, v)


# --- backward ----------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   block_q: int, block_k: int, causal: bool, scale: float, window: int = 0):
    q = q_ref[0]                              # [block_q, D], input dtype
    do = do_ref[0]                            # [block_q, D], input dtype
    lse = lse_ref[0]                          # [block_q, 1]
    delta = delta_ref[0]                      # [block_q, 1] rowsum(dO * O)
    T = k_ref.shape[1]

    def program(qi):
        def step(kj, dq, masked: bool):
            k_blk = _block(k_ref, kj, block_k)
            v_blk = _block(v_ref, kj, block_k)
            s = _dot_nt(q, k_blk) * scale          # f32 accumulate, bf16 MXU rate
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(_visible(qi * block_q, kj * block_k, p.shape, 0, window), p, 0.0)
            dp = _dot_nt(do, v_blk)                # [block_q, block_k] f32
            ds = p * (dp - delta)
            return dq + _dot_nn(ds.astype(k_blk.dtype), k_blk)

        dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
        dq = _visit_blocks(step, dq, qi, block_q, block_k, T // block_k, causal, True, window)
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    _each_block(pl.program_id(1), T // block_q, causal, program)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q: int, block_k: int,
                    causal: bool, scale: float, window: int = 0):
    """Grid over (B*Hkv, k blocks, G): the group dim is a GRID axis, not a
    VMEM block axis — q/do arrive one query head at a time (index-mapped
    ``i*G + g``), so VMEM stays O(T*D) regardless of the GQA group size.
    g varies fastest, so the (i, j)-indexed dk/dv output blocks are
    revisited consecutively and accumulate across the group in f32.

    The tile is the TRANSPOSED score block [block_k, block_q] (keys on
    sublanes, queries on lanes): dV = P^T dO and dK = dS^T Q are then plain
    matmuls with no transpose of a [block_q, block_k] tile, and lse / delta
    arrive lane-dense as [1, block_q] rows that broadcast down sublanes."""
    g = pl.program_id(2)
    k = k_ref[0]                              # [block_k, D], input dtype
    v = v_ref[0]                              # [block_k, D], input dtype
    T = q_ref.shape[1]
    D = k.shape[-1]

    def program(ki):
        def step(qj, carry, masked: bool):
            dk, dv = carry
            q_blk = _block(q_ref, qj, block_q)
            do_blk = _block(do_ref, qj, block_q)
            s_t = _dot_nt(k, q_blk) * scale        # [block_k, block_q] f32
            p_t = jnp.exp(s_t - lse_ref[0, qj])
            if masked:
                p_t = jnp.where(_visible(qj * block_q, ki * block_k, p_t.shape, 1, window), p_t, 0.0)
            dv_new = dv + _dot_nn(p_t.astype(do_blk.dtype), do_blk)
            dp_t = _dot_nt(v, do_blk)
            ds_t = p_t * (dp_t - delta_ref[0, qj])
            dk_new = dk + _dot_nn(ds_t.astype(q_blk.dtype), q_blk)
            return dk_new, dv_new

        carry = (jnp.zeros((block_k, D), jnp.float32), jnp.zeros((block_k, D), jnp.float32))
        carry = _visit_blocks(step, carry, ki, block_k, block_q, T // block_q, causal, False, window)
        dk, dv = carry

        @pl.when(g == 0)
        def _init():
            dk_ref[0] = jnp.zeros_like(dk_ref[0])
            dv_ref[0] = jnp.zeros_like(dv_ref[0])

        dk_ref[0] = dk_ref[0] + dk * scale
        dv_ref[0] = dv_ref[0] + dv

    _each_block(pl.program_id(1), T // block_k, causal, program)


@functools.partial(jax.jit, static_argnames=("causal", "dq_blocks", "dkv_blocks", "Hq", "Hkv", "interpret", "window"))
def _bwd_impl(q, k, v, do, o, lse, *, causal: bool, dq_blocks, dkv_blocks,
              Hq: int, Hkv: int, interpret: bool, window: int = 0):
    BHq, T, D = q.shape
    BHkv = k.shape[0]
    G = Hq // Hkv
    scale = D ** -0.5
    item = q.dtype.itemsize
    # delta = rowsum(dO * O): tiny elementwise reduce, XLA fuses it; feeding
    # it in precomputed keeps both kernels single-pass. Same [.., T, 1]
    # layout as lse (see _fwd_impl).
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [BHq, T, 1]
    kv_idx = _kv_index(Hq, Hkv)
    win = {"window": window} if window else {}

    block_q, block_k = dq_blocks
    resident = 2 * T * D * item + 3 * block_q * D * item + 2 * block_q * 128 * 4
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, **win),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(BHq, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, T, D), kv_idx),
            pl.BlockSpec((1, T, D), kv_idx),
            pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda i, j: (i, j, 0)),
        compiler_params=_grid(
            "parallel", "parallel",
            vmem_limit_bytes=_vmem_limit("dq", block_q, block_k, D, resident)),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # group dim as a grid axis (g fastest -> consecutive output revisits);
    # query head for program (i, j, g) is i*G + g
    def q_idx(i, j, g):
        return (i * G + g, 0, 0)

    def stat_idx(i, j, g):
        return (i * G + g, 0, 0, 0)

    block_q, block_k = dkv_blocks
    num_q = T // block_q
    # the statistics with the q-block on the lane axis: [BHq, T, 1] and
    # [BHq, T/block_q, 1, block_q] are the same bytes
    lse_rows = lse.reshape(BHq, num_q, 1, block_q)
    delta_rows = delta.reshape(BHq, num_q, 1, block_q)
    resident = 2 * T * D * item + 2 * block_k * D * item + 2 * block_k * D * 4 + 2 * T * 8 * 4
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, **win),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, jnp.float32),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ),
        grid=(BHkv, T // block_k, G),
        in_specs=[
            pl.BlockSpec((1, T, D), q_idx),
            pl.BlockSpec((1, block_k, D), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, T, D), q_idx),
            pl.BlockSpec((1, num_q, 1, block_q), stat_idx),
            pl.BlockSpec((1, num_q, 1, block_q), stat_idx),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda i, j, g: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, g: (i, j, 0)),
        ),
        # g accumulates into revisited output blocks -> must stay ordered
        compiler_params=_grid(
            "parallel", "parallel", "arbitrary",
            vmem_limit_bytes=_vmem_limit("dkv", block_q, block_k, D, resident)),
        interpret=interpret,
    )(q, k, v, do, lse_rows, delta_rows)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --- custom_vjp wiring (on the [BH, T, D] layout) ----------------------------
# ``blocks`` is ((block_q, block_k) of fwd, of dq, of dkv): static, hashable

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_r(q, k, v, causal, blocks, Hq, Hkv, window):
    return _flash_r_fwd(q, k, v, causal, blocks, Hq, Hkv, window)[0]


def _flash_r_fwd(q, k, v, causal, blocks, Hq, Hkv, window):
    block_q, block_k = blocks[0]
    out, lse = _fwd_impl(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                         Hq=Hq, Hkv=Hkv, interpret=_interpret(), window=window)
    return out, (q, k, v, out, lse)


def _flash_r_bwd(causal, blocks, Hq, Hkv, window, res, g):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, g, o, lse, causal=causal, dq_blocks=blocks[1],
                     dkv_blocks=blocks[2], Hq=Hq, Hkv=Hkv, interpret=_interpret(), window=window)


_flash_r.defvjp(_flash_r_fwd, _flash_r_bwd)


@functools.lru_cache(maxsize=None)
def _chosen_blocks(T: int, D: int, dtype: str, Hq: int, Hkv: int):
    """The default blocks of the three kernels at one LOCAL shape, logged
    once per distinct shape (as ``_auto_attention_impl`` logs its choice)."""
    blocks = tuple(block_sizes(T, D, kind) for kind in ("fwd", "dq", "dkv"))
    log.info("flash_attention blocks (block_q, block_k): fwd=%s dq=%s dkv=%s "
             "(T=%d, D=%d, dtype=%s, Hq=%d, Hkv=%d)", *blocks, T, D, dtype, Hq, Hkv)
    return blocks


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: int = 0,
) -> jnp.ndarray:
    """[B, T, Hq, D], [B, T, Hkv, D] x2 -> [B, T, Hq, D]. GQA-native: Hkv may
    divide Hq; K/V are consumed at their own head count (no repeat). Each of
    the three kernels takes its blocks from :func:`block_sizes` at this
    (local) shape; an explicit ``block_q`` AND ``block_k`` override all
    three. Raises when the blocks do not tile T (see :func:`tiles`) — a
    caller that asked for this kernel never silently gets einsum attention
    instead. ``window`` (causal only; 0 = none): a query sees its ``window``
    newest keys, and the three kernels visit only the blocks those lie in
    (``_visit_blocks``): ``T x window`` work, not ``T^2 / 2``."""
    B, T, Hq, D = q.shape
    if window < 0 or (window and not causal):
        raise ValueError(f"flash_attention: window {window} needs causal=True and must be >= 0")
    window = 0 if window >= T else int(window)  # a window no row reaches is no window
    Hkv = k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {Hkv}")
    if bool(block_q) != bool(block_k):
        raise ValueError("flash_attention: pass block_q and block_k together, or neither")
    if not tiles(T, block_q, block_k):
        raise ValueError(
            f"flash_attention: blocks ({min(block_q or LADDER[-1], T)}, "
            f"{min(block_k or LADDER[-1], T)}) do not tile seq_len {T}, or neither "
            "divides the other; pad the sequence or use attention_impl='xla'")
    if block_q:
        blocks = ((min(block_q, T), min(block_k, T)),) * 3
    else:
        blocks = _chosen_blocks(T, D, jnp.dtype(q.dtype).name, Hq, Hkv)
    qr = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * Hq, T, D)
    kr = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * Hkv, T, D)
    vr = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * Hkv, T, D)
    out = _flash_r(qr, kr, vr, causal, blocks, Hq, Hkv, window)
    return jnp.transpose(out.reshape(B, Hq, T, D), (0, 2, 1, 3))


# --- a pass over a contiguous row cache (serving: prefill, suffix pass) ---------

def _rows_block(n: int) -> int:
    """The largest rung of ``LADDER`` that divides ``n``; ``n`` itself below the smallest."""
    return n if n < LADDER[-1] else next((r for r in LADDER if n % r == 0), 0)


def rows_tile(S: int) -> bool:
    """``flash_attention_rows``' shape rule: a rung of ``LADDER`` divides the
    row length ``S`` (or it is one block). The pass's length is filled up to
    whole blocks inside the call, so any goes."""
    return _rows_block(S) > 0


def _rows_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *, block_q: int, block_k: int,
                 window: int, scale: float):
    """One q block of a pass whose first query sits at row ``off_ref[0]`` of
    the cache (a RUNTIME value: a suffix pass starts behind its shared prefix)
    against the whole-``S`` K/V rows of its kv head. Row t sees the columns
    ``t - window < j <= t`` (``window`` 0: ``j <= t``). Only the k-blocks that
    hold such a column are visited, all bounds runtime values: the blocks the
    horizon crosses and the blocks the diagonal crosses build the mask, those
    between them do not."""
    q = q_ref[0]
    num_k = k_ref.shape[1] // block_k
    D = q.shape[-1]
    r0 = off_ref[0] + pl.program_id(1) * block_q
    r1 = r0 + block_q - 1

    def step(kj, carry, masked: bool):
        m, l, acc = carry
        k_blk = _block(k_ref, kj, block_k)
        v_blk = _block(v_ref, kj, block_k)
        s = _dot_nt(q, k_blk) * scale
        if masked:
            vis = _visible(r0, kj * block_k, s.shape, 0, window)
            s = jnp.where(vis, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if masked:  # a row may see nothing of a block: exp(NEG_INF - NEG_INF) would count
            p = jnp.where(vis, p, 0.0)
        return m_new, l * corr + p.sum(axis=-1, keepdims=True), acc * corr + _dot_nn(p.astype(v_blk.dtype), v_blk)

    carry = (jnp.full((block_q, 1), NEG_INF, jnp.float32), jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, D), jnp.float32))
    hi = jnp.minimum(r1 // block_k + 1, num_k)        # blocks that hold a column <= r1
    diag = jnp.minimum((r0 + 1) // block_k, hi)       # the first block with a column > r0
    lo = 0
    if window:
        lo = jnp.maximum(r0 - window + 1, 0) // block_k
        edge = jnp.clip((jnp.maximum(r1 - window + 1, 0) + block_k - 1) // block_k, lo, diag)
        carry = jax.lax.fori_loop(lo, edge, functools.partial(step, masked=True), carry)
        lo = edge
    carry = jax.lax.fori_loop(lo, diag, functools.partial(step, masked=False), carry)
    m, l, acc = jax.lax.fori_loop(diag, hi, functools.partial(step, masked=True), carry)
    o_ref[0] = (acc * (1.0 / jnp.maximum(l, 1e-20))).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def _rows_impl(q, k_rows, v_rows, offset, *, window: int, interpret: bool):
    B, T, Hq, D = q.shape
    S, Hkv = k_rows.shape[1], k_rows.shape[2]
    block_k = _rows_block(S)
    block_q = _rows_block(-(-T // LADDER[-1]) * LADDER[-1]) if T >= LADDER[-1] else T
    Tp = -(-T // block_q) * block_q
    qr = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * Hq, T, D)
    if Tp != T:
        qr = jnp.pad(qr, ((0, 0), (0, Tp - T), (0, 0)))
    kr = jnp.transpose(k_rows, (0, 2, 1, 3)).reshape(B * Hkv, S, D)
    vr = jnp.transpose(v_rows, (0, 2, 1, 3)).reshape(B * Hkv, S, D)
    kv_idx = _kv_index(Hq, Hkv)
    item = q.dtype.itemsize
    resident = 2 * S * D * item + 2 * block_q * D * item
    out = pl.pallas_call(
        functools.partial(_rows_kernel, block_q=block_q, block_k=block_k, window=window, scale=D ** -0.5),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hq, Tp // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda i, j, *_: (i, j, 0)),
                pl.BlockSpec((1, S, D), lambda i, j, *_: kv_idx(i, j)),
                pl.BlockSpec((1, S, D), lambda i, j, *_: kv_idx(i, j)),
            ],
            out_specs=pl.BlockSpec((1, block_q, D), lambda i, j, *_: (i, j, 0)),
        ),
        compiler_params=_grid("parallel", "parallel",
                              vmem_limit_bytes=_vmem_limit("fwd", block_q, block_k, D, resident)),
        interpret=interpret,
        name="flash_attention_rows",
    )(jnp.reshape(offset, (1,)).astype(jnp.int32), qr, kr, vr)
    return jnp.transpose(out[:, :T].reshape(B, Hq, T, D), (0, 2, 1, 3))


def flash_attention_rows(q, k_rows, v_rows, offset, *, window: int = 0):
    """q ``[B, T, Hq, D]``: the queries of one pass, row t of them at position
    ``offset + t`` of a contiguous row cache ``k_rows`` / ``v_rows``
    ``[B, S, Hkv, D]`` that already holds the pass's own keys and values at
    ``offset .. offset + T - 1`` (``offset`` a runtime int32 scalar, shared by
    the rows). A query sees the cache's positions ``<=`` its own, with
    ``window`` only the ``window`` newest of them. Returns ``[B, T, Hq, D]``.
    Forward only; GQA-native; what lies in the cache past a query is never
    read into a score that counts."""
    if not rows_tile(k_rows.shape[1]):
        raise ValueError(f"flash_attention_rows: no block of {LADDER} tiles a row of {k_rows.shape[1]}")
    return _rows_impl(q, k_rows, v_rows, offset, window=int(window), interpret=_interpret())


def flash_attention_rows_reference(q, k_rows, v_rows, offset, *, window: int = 0):
    """The plain formulation, same arguments and result: the masked einsum over the whole row."""
    B, T, Hq, D = q.shape
    S, Hkv = k_rows.shape[1], k_rows.shape[2]
    k = jnp.repeat(k_rows, Hq // Hkv, axis=2)
    v = jnp.repeat(v_rows, Hq // Hkv, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * D ** -0.5
    q_pos = offset + jnp.arange(T)[:, None]
    k_pos = jnp.arange(S)[None, :]
    valid = k_pos <= q_pos
    if window:
        valid = jnp.logical_and(valid, k_pos > q_pos - window)
    probs = jax.nn.softmax(jnp.where(valid[None, None], logits, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

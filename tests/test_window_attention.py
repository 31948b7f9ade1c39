"""A window of keys in the attention kernels, on the CPU at small sizes (the
Pallas kernels interpreted): ``flash_attention`` with a window against the XLA
mask, forward and gradients, the window below, equal to and above a block and
the sequence below the window; ``flash_attention_rows`` (a pass over a row cache
at a runtime offset) and ``paged_attention`` with a start a row against their
plain formulations; the selection bias of the router; the shares of a routed
layer with the bias adding up to the uncut layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import moe
from fedml_tpu.models.transformer import TransformerConfig, repeat_kv, xla_attention
from fedml_tpu.ops import flash_attention as fa
from fedml_tpu.ops import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_trinity  # noqa: E402


def _qkv(seed, B, T, H, Hkv, D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, T, H, D), jnp.float32), jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32),
            jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32))


def _masked_xla(q, k, v, window):
    kk, vv = repeat_kv(k, v, q.shape[2])
    return xla_attention(q, kk, vv, causal=True, window=window)


def test_the_xla_mask_keeps_the_window_newest_keys():
    q, k, v = _qkv(0, 1, 12, 2, 2, 8)
    out = np.asarray(xla_attention(q, k, v, causal=True, window=4))
    for t in range(12):  # row t from its own 4 newest keys alone
        lo = max(0, t - 3)
        alone = xla_attention(q[:, t:t + 1], k[:, lo:t + 1], v[:, lo:t + 1], causal=False)
        np.testing.assert_allclose(out[:, t], np.asarray(alone)[:, 0], atol=1e-5)


# window below a block (32), equal to it, above it and off the grid, a block from the end, T and more (no window)
@pytest.mark.parametrize("T,block,window", [(128, 32, 8), (128, 32, 32), (128, 32, 33), (128, 32, 50), (128, 32, 96),
                                            (128, 32, 127), (128, 32, 128), (128, 32, 500), (64, 64, 16),
                                            (256, 32, 70)])
def test_flash_window_equals_the_xla_mask_forward_and_gradients(T, block, window):
    """``T / block`` of 2 and 4 take the loop-free programs, 8 the loops with runtime bounds."""
    q, k, v = _qkv(T + window, 2, T, 4, 2, 16)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_q=block, block_k=block, window=window)

    with jax.default_matmul_precision("highest"):
        want = _masked_xla(q, k, v, window if window < T else 0)
        got = flash(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
        g_got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
        g_want = jax.grad(loss(lambda q, k, v: _masked_xla(q, k, v, window if window < T else 0)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("blocks", [(64, 32), (32, 64)])
def test_flash_window_with_unequal_blocks(blocks):
    q, k, v = _qkv(5, 1, 128, 2, 1, 16)
    with jax.default_matmul_precision("highest"):
        got = fa.flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1], window=40)
        g = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1],
                                                                 window=40) ** 2), (0, 1, 2))(q, k, v)
        gw = jax.grad(lambda q, k, v: jnp.sum(_masked_xla(q, k, v, 40) ** 2), (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(_masked_xla(q, k, v, 40)), atol=2e-5, rtol=2e-5)
    for a, b in zip(g, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_a_window_visits_only_the_blocks_it_crosses():
    """The blocks a q-block's loop streams: ``T x window`` work, not ``T^2 / 2``."""
    seen = []

    def step(j, carry, masked):
        seen.append((int(j), masked))
        return carry

    fa._visit_blocks(step, None, 6, 32, 32, 8, True, True, window=40)        # rows 192..223 see columns 153..223
    assert seen == [(4, True), (5, True), (6, True)]
    seen.clear()
    fa._visit_blocks(step, None, 6, 32, 32, 8, True, True, window=100)       # columns 93..223: 2 and 3 hold a horizon
    assert seen == [(2, True), (3, True), (4, False), (5, False), (6, True)]
    seen.clear()
    fa._visit_blocks(step, None, 2, 32, 32, 8, True, False, window=40)       # columns 64..95 are seen by rows 64..134
    assert seen == [(2, True), (3, True), (4, True)]
    seen.clear()
    fa._visit_blocks(step, None, 2, 32, 32, 8, True, True)                   # causal only: as it always was
    assert seen == [(0, False), (1, False), (2, True)]
    with pytest.raises(ValueError):
        fa.flash_attention(*_qkv(0, 1, 32, 1, 1, 8), causal=False, window=4)


@pytest.mark.parametrize("T,offset,window", [(32, 0, 0), (32, 0, 20), (48, 64, 0), (48, 64, 20), (48, 80, 100),
                                             (16, 112, 40), (100, 16, 33)])
def test_flash_rows_equals_the_masked_einsum_over_the_row(T, offset, window):
    """A pass of T queries at a runtime offset of a 128-token row cache; blocks of 128 (one) and,
    with a 256-token row, of 128 (two); T off the block grid is filled up inside the call."""
    for S in (128, 256):
        ks = jax.random.split(jax.random.PRNGKey(T + offset + window + S), 3)
        q = jax.random.normal(ks[0], (2, T, 4, 16), jnp.float32)
        kr = jax.random.normal(ks[1], (2, S, 2, 16), jnp.float32)
        vr = jax.random.normal(ks[2], (2, S, 2, 16), jnp.float32)
        with jax.default_matmul_precision("highest"):
            got = fa.flash_attention_rows(q, kr, vr, jnp.int32(offset), window=window)
            want = fa.flash_attention_rows_reference(q, kr, vr, jnp.int32(offset), window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_rows_takes_its_offset_at_run_time():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16), jnp.float32)
    kr, vr = (jax.random.normal(k, (1, 128, 1, 16), jnp.float32) for k in ks[1:])
    before = fa._rows_impl._cache_size()
    for off in (0, 16, 96):
        fa.flash_attention_rows(q, kr, vr, jnp.int32(off), window=24)
    assert fa._rows_impl._cache_size() == before + 1


def _pool(seed, n_pages=24, ps=4, n_kv=2, D=16, B=5, n_blocks=12, H=4):
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_pages, ps, n_kv, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (n_pages, ps, n_kv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, D), jnp.float32)
    tables = rng.integers(1, n_pages, (B, n_blocks)).astype(np.int32)
    return q, k_pool, v_pool, tables


@pytest.mark.parametrize("window", [3, 4, 9, 16, 100])
def test_paged_attention_with_a_start_equals_the_reference(window):
    q, k_pool, v_pool, tables = _pool(window)
    lengths = np.asarray([0, 1, 7, 30, 48], np.int32)
    starts = np.maximum(lengths - window, 0).astype(np.int32)
    # the entries behind a row's start point at the trash page: the engine has taken those pages back
    for b in range(5):
        tables[b, :starts[b] // 4] = 0
    with jax.default_matmul_precision("highest"):
        got = pa.paged_attention(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(starts))
        want = pa.paged_attention_reference(q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths),
                                            jnp.asarray(starts))
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[0].any()  # a free slot (length 0) reads no page and returns zeros
    # and the same as attending to the window's keys alone, row by row
    for b in (2, 3, 4):
        pos = np.arange(starts[b], lengths[b])
        kk = np.asarray(k_pool)[tables[b, pos // 4], pos % 4]      # [n, kv, D]
        vv = np.asarray(v_pool)[tables[b, pos // 4], pos % 4]
        alone = xla_attention(q[b][None, None], jnp.repeat(kk, 2, 1)[None], jnp.repeat(vv, 2, 1)[None], causal=False)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(alone)[0, 0], atol=2e-5, rtol=2e-5)


def test_paged_attention_without_a_start_is_the_program_it_was():
    q, k_pool, v_pool, tables = _pool(1)
    lengths = jnp.asarray([0, 1, 7, 30, 48], jnp.int32)
    text = jax.jit(pa.paged_attention).lower(q, k_pool, v_pool, jnp.asarray(tables), lengths).as_text()
    zero = jax.jit(pa.paged_attention).lower(q, k_pool, v_pool, jnp.asarray(tables), lengths,
                                             jnp.zeros((5,), jnp.int32)).as_text()
    assert text != zero  # a start is one more scalar operand; without it none is prefetched
    np.testing.assert_allclose(np.asarray(pa.paged_attention(q, k_pool, v_pool, jnp.asarray(tables), lengths)),
                               np.asarray(pa.paged_attention(q, k_pool, v_pool, jnp.asarray(tables), lengths,
                                                             jnp.zeros((5,), jnp.int32))), atol=1e-6)


# ---- the router's selection bias ----------------------------------------------------------------------

def test_the_selection_bias_moves_the_choice_and_not_the_gates():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 16)), jnp.float32)
    bias = jnp.zeros((16,)).at[3].set(10.0)
    experts, gates = moe.route(logits, 4, 2.826, True, select_bias=bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    for n in range(6):
        picked = np.asarray(experts[n])
        assert 3 in picked                                                    # the bias put expert 3 among the picks
        assert set(picked) - {3} <= set(np.argsort(-s[n])[:4])
        want = 2.826 * s[n, picked] / s[n, picked].sum()                      # gates of the scores WITHOUT it
        np.testing.assert_allclose(np.asarray(gates[n]), want, rtol=1e-6)
    same, g0 = moe.route(logits, 4, 2.826, True, select_bias=jnp.zeros((16,)))
    plain, g1 = moe.route(logits, 4, 2.826, True)
    assert np.array_equal(np.asarray(same), np.asarray(plain)) and np.allclose(np.asarray(g0), np.asarray(g1))


CFG = TransformerConfig(d_model=32, dtype=jnp.float32, moe_routed_experts=16, moe_held_experts=4, moe_rank=0,
                        moe_top_k=4, moe_d_ff=16, moe_shared_experts=1, moe_routed_scaling=2.826, moe_select_bias=True)
REF = {"num_experts": 4, "router_width": 16, "expert_rank": 0, "num_experts_per_tok": 4, "route_scale": 2.826,
       "route_norm": True}


@pytest.mark.parametrize("bias_std", [0.0, 0.01, 0.5])
def test_the_shares_of_a_routed_layer_with_the_selection_bias_add_up_to_the_uncut_layer(bias_std):
    """16 experts over 4 ranks of 4: the routed parts of all ranks' results plus the shared expert counted
    ONCE are the uncut layer's result (``reference_trinity.routed`` given all 16), whatever the bias."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 23, 32)), jnp.float32)
    whole_cfg = dataclasses.replace(CFG, moe_held_experts=0)
    layer = moe.RoutedMoE(whole_cfg)
    live = jnp.ones((1, 23), bool)
    whole = dict(layer.init(jax.random.PRNGKey(0), x, live)["params"])
    whole["router_bias"] = bias_std * jax.random.normal(jax.random.PRNGKey(7), (16,), jnp.float32)
    want = reference_trinity.routed(whole, x[0], dict(REF, num_experts=16), None)
    sh = whole["shared"]
    shared = reference_trinity.swiglu(x[0], sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"], sh["down_proj"]["kernel"], None)
    total = shared
    for rank in range(4):
        part = dict(whole, **{k: whole[k][rank * 4:(rank + 1) * 4] for k in ("w_gate", "w_up", "w_down")})
        y = moe.RoutedMoE(dataclasses.replace(CFG, moe_rank=rank)).apply({"params": part}, x, live)
        total = total + (y[0] - shared)
        ref_part = reference_trinity.routed(part, x[0], dict(REF, expert_rank=rank), None)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(ref_part), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(np.asarray(layer.apply({"params": whole}, x, live)[0]), np.asarray(want), atol=5e-5, rtol=5e-5)

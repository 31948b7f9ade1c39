"""The dropless top-k routed layer (``models/moe.RoutedMoE``) and its grouped
matmul, on the CPU at small sizes: the router's rule, no token dropped at any
imbalance, the kernel against the plain formulation, and the test that ties a
SHARE of the experts to the model: all ranks' routed parts plus the shared
expert counted once are the uncut layer."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import moe
from fedml_tpu.models.transformer import TransformerConfig
from fedml_tpu.ops import grouped_matmul as gm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_pangu  # noqa: E402

CFG = TransformerConfig(d_model=32, dtype=jnp.float32, moe_routed_experts=32, moe_held_experts=8, moe_rank=0,
                        moe_top_k=4, moe_d_ff=16, moe_shared_experts=1, moe_routed_scaling=2.5)
REF = {"n_routed_experts": 8, "router_width": 32, "expert_rank": 0, "num_experts_per_tok": 4,
       "routed_scaling_factor": 2.5, "norm_topk_prob": True}


def _layer(cfg, x, live=None, seed=0, stats=False):
    live = jnp.ones(x.shape[:2], bool) if live is None else live
    layer = moe.RoutedMoE(cfg)
    params = layer.init(jax.random.PRNGKey(seed), x, live)["params"]
    return params, layer.apply({"params": params}, x, live, mutable=[moe.ROUTING_STATS] if stats else False)


def _ref_params(p):
    return {"router": p["router"], "w_gate": p["w_gate"], "w_up": p["w_up"], "w_down": p["w_down"],
            "shared": p["shared"]}


def test_the_router_is_sigmoid_top_k_of_the_full_width_normalised_and_scaled():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 32)), jnp.float32)
    experts, gates = moe.route(logits, 4, 2.5, True)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    for n in range(6):
        top = np.argsort(-s[n])[:4]
        assert set(np.asarray(experts[n])) == set(top)                      # the 4 largest of all 32
        want = 2.5 * s[n, np.asarray(experts[n])] / s[n, top].sum()
        np.testing.assert_allclose(np.asarray(gates[n]), want, rtol=1e-6)
    assert np.allclose(np.asarray(gates).sum(-1), 2.5)                       # normalised over the picks, then scaled
    _, raw = moe.route(logits, 4, 2.5, False)
    np.testing.assert_allclose(np.asarray(raw), 2.5 * np.take_along_axis(s, np.asarray(experts), -1), rtol=1e-6)


def test_the_layer_equals_the_reference_s_loop_over_the_held_experts():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 19, 32)), jnp.float32)
    params, y = _layer(CFG, x)
    want = reference_pangu.routed(_ref_params(params), x.reshape(-1, 32), REF, None).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_the_share_ties_to_the_model():
    """32 experts over 4 ranks of 8: the routed parts of all ranks' results plus the shared expert
    counted ONCE are the uncut layer's result (the reference given all 32 experts)."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 23, 32)), jnp.float32)
    whole_cfg = dataclasses.replace(CFG, moe_held_experts=0)
    whole, _ = _layer(whole_cfg, x)
    want = reference_pangu.routed(_ref_params(whole), x[0], dict(REF, n_routed_experts=32), None)
    shared = reference_pangu.swiglu(x[0], whole["shared"]["gate_proj"]["kernel"], whole["shared"]["up_proj"]["kernel"],
                                    whole["shared"]["down_proj"]["kernel"], None)
    total = shared
    for rank in range(4):
        cfg = dataclasses.replace(CFG, moe_rank=rank)
        part = dict(whole, **{k: whole[k][rank * 8:(rank + 1) * 8] for k in ("w_gate", "w_up", "w_down")})
        y = moe.RoutedMoE(cfg).apply({"params": part}, x, jnp.ones((1, 23), bool))
        total = total + (y[0] - shared)  # this rank's routed part
        ref_part = reference_pangu.routed(_ref_params(part), x[0], dict(REF, expert_rank=rank), None)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(ref_part), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5, rtol=5e-5)
    # and the program's own uncut layer says the same
    np.testing.assert_allclose(np.asarray(moe.RoutedMoE(whole_cfg).apply({"params": whole}, x, jnp.ones((1, 23), bool))[0]),
                               np.asarray(want), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("case", ["all_to_one_held_expert", "none_to_a_held_expert", "half_the_tokens_dead"])
def test_no_token_is_dropped_at_any_imbalance(case):
    """The router's rows are planted so that every token picks held expert 3 (and three absent
    ones), or only absent ones; nothing overflows, nothing is dropped, the loads say what happened."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(np.abs(rng.normal(size=(1, 40, 32))) + 0.1, jnp.float32)  # positive: a positive column wins
    live = jnp.ones((1, 40), bool)
    params, _ = _layer(CFG, x)
    router = np.full((32, 32), -1.0, np.float32)
    picked = [3, 20, 21, 22] if case != "none_to_a_held_expert" else [20, 21, 22, 23]
    router[:, picked] = 1.0
    router[:, picked[0]] = 2.0
    params = dict(params, router=jnp.asarray(router))
    if case == "half_the_tokens_dead":
        live = jnp.asarray(np.arange(40) % 2 == 0)[None]
    y, sown = moe.RoutedMoE(CFG).apply({"params": params}, x, live, mutable=[moe.ROUTING_STATS])
    load = np.asarray(sown[moe.ROUTING_STATS]["load"][0])
    want = reference_pangu.routed(_ref_params(params), x[0], REF, None)
    keep = np.asarray(live[0])
    np.testing.assert_allclose(np.asarray(y[0])[keep], np.asarray(want)[keep], atol=2e-5, rtol=2e-5)
    expect = {"all_to_one_held_expert": 40, "none_to_a_held_expert": 0, "half_the_tokens_dead": 20}[case]
    assert load.tolist() == [0, 0, 0, expect, 0, 0, 0, 0]
    stats = np.asarray(moe.routing_stats(sown[moe.ROUTING_STATS], int(keep.sum())))
    assert stats[:3].tolist() == [int(keep.sum()), expect, int(expect > 0)] and stats[3:].tolist() == load.tolist()
    if case == "half_the_tokens_dead":  # a dead token's routed part is nothing: the shared expert alone
        shared = reference_pangu.swiglu(x[0], params["shared"]["gate_proj"]["kernel"], params["shared"]["up_proj"]["kernel"],
                                        params["shared"]["down_proj"]["kernel"], None)
        np.testing.assert_allclose(np.asarray(y[0])[~keep], np.asarray(shared)[~keep], atol=2e-5, rtol=2e-5)


def test_sort_pairs_lays_every_live_held_pair_in_a_tile_of_its_expert():
    rng = np.random.default_rng(4)
    N, k, held, first, tm = 50, 4, 8, 8, 16
    experts = jnp.asarray(np.stack([rng.permutation(32)[:k] for _ in range(N)]), jnp.int32)
    live = jnp.asarray(rng.random(N) < 0.8)
    row_token, pair_row, mine, tile_group, n_live, load = (np.asarray(a) for a in
                                                          moe.sort_pairs(experts, live, first, held, tm))
    local = np.asarray(experts) - first
    want_mine = (local >= 0) & (local < held) & np.asarray(live)[:, None]
    assert (mine == want_mine).all() and load.tolist() == [int((local[want_mine] == e).sum()) for e in range(held)]
    assert row_token.shape[0] % tm == 0 and row_token.shape[0] >= N * k + held * (tm - 1)
    assert int(n_live[0]) == sum(-(-int(c) // tm) for c in load)
    rows = pair_row[mine]
    assert len(set(rows.tolist())) == len(rows)                                  # a row a pair
    assert (row_token[rows] == np.nonzero(mine)[0]).all()                        # holding that pair's token
    assert (tile_group[rows // tm] == local[mine]).all() and (rows // tm < n_live[0]).all()  # in its expert's live tile
    assert moe.row_tile(64, 8, 256) == 16 and moe.row_tile(1280, 8, 256) == 128 and moe.row_tile(272, 8, 256) == 32


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_the_grouped_matmul_kernel_equals_the_plain_formulation(dtype, tol):
    """Interpreted: tiles of three experts out of five (one expert twice, one never), dead tiles behind."""
    rng = np.random.default_rng(5)
    tm, K, N, E = 16, 256, 384, 5
    tile_group = jnp.asarray([0, 2, 2, 4, 4, 4, 4, 4], jnp.int32)  # 4 live tiles; the rest repeat the last
    n_live = jnp.asarray([4], jnp.int32)
    x = jnp.asarray(rng.normal(size=(8 * tm, K)), dtype)
    w = jnp.asarray(rng.normal(size=(E, K, N)) / np.sqrt(K), dtype)
    got = gm.grouped_matmul(x, w, tile_group, n_live, tm=tm)
    want = gm.grouped_matmul_reference(x, w, tile_group, n_live, tm=tm)
    assert got.shape == (8 * tm, N) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:4 * tm], np.float32), np.asarray(want[:4 * tm], np.float32), atol=tol, rtol=tol)
    none = gm.grouped_matmul(x, w, tile_group, jnp.asarray([0], jnp.int32), tm=tm)  # nothing live: nothing to read
    assert none.shape == (8 * tm, N)
    assert gm.tiles(7680, 2048, 16, jnp.bfloat16) and gm.tiles(2048, 7680, 128, jnp.bfloat16)
    assert not gm.tiles(7680, 2048, 8, jnp.bfloat16) and gm._block(7680, 1536) == 1536 and gm._block(2048, 1536) == 1024

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

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models import moe
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.ops import grouped_matmul as gm
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine

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
    tiles = -(-expect // moe.row_tile(40, 4, 32))  # one group: its pairs in whole row tiles
    assert int(np.asarray(sown[moe.ROUTING_STATS]["row_tiles"][0])[0]) == tiles
    head = dict(zip(moe.ROUTING_HEAD, stats.tolist()))
    assert head == {"tokens_routed": int(keep.sum()), "local_picks": expect, "experts_hit": int(expect > 0), "row_tiles": tiles}
    assert head["experts_hit"] <= head["row_tiles"] and stats[len(moe.ROUTING_HEAD):].tolist() == load.tolist()
    if case == "half_the_tokens_dead":  # a dead token's routed part is nothing: the shared expert alone
        shared = reference_pangu.swiglu(x[0], params["shared"]["gate_proj"]["kernel"], params["shared"]["up_proj"]["kernel"],
                                        params["shared"]["down_proj"]["kernel"], None)
        np.testing.assert_allclose(np.asarray(y[0])[~keep], np.asarray(shared)[~keep], atol=2e-5, rtol=2e-5)


def test_the_row_tiles_of_every_pass_land_on_its_span_and_in_the_counter():
    """Two experts, both picked by every token: a prefill of 300 tokens lays each expert's 300 pairs in
    three 128-row tiles, a decode step in one 16-row tile. Both passes say so on their spans
    (``row_tiles`` beside ``experts_hit``); the counter and ``stats()`` hold the sum."""
    tel.reset()
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64, max_seq_len=512,
                            dtype=jnp.float32, remat=False, moe_routed_experts=2, moe_top_k=2, moe_d_ff=16)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = PagedContinuousBatchingEngine(params, cfg, num_slots=2, chunk=4, page_size=16, num_pages=65)
    try:
        assert len(eng.generate(np.random.default_rng(6).integers(1, 64, 300).tolist(), 6)) == 6
        spans = tel.snapshot()["spans"]
        (prefill,) = [s["attrs"] for s in spans if s["name"] == "serving.cb.prefill"]
        chunks = [s["attrs"] for s in spans if s["name"] == "serving.cb.chunk"]
        assert moe.row_tile(304, 2, 2) == 128  # the 304-token bucket's tile
        assert (prefill["local_picks"], prefill["experts_hit"], prefill["row_tiles"]) == (300 * 2 * 2, 2 * 2, 3 * 2 * 2)
        # a chunk of 4 token-steps: one tile an expert a step a layer, no tile shares a block with the one before
        assert chunks and all(c["row_tiles"] == c["experts_hit"] == 4 * 2 * 2 for c in chunks)
        total = prefill["row_tiles"] + sum(c["row_tiles"] for c in chunks)
        assert tel.counter("serving.moe.row_tiles").value == eng.stats()["moe_row_tiles"] == total
        assert eng.stats()["moe_experts_hit"] == total - 2 * 2 * 2  # the prefill's second and third tiles
    finally:
        eng.shutdown()


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


# tile_group of the live tiles, dead tiles behind; K, N: "one_block" is the matrix whole, "cut" (6 MiB and
# more at either dtype) is BLOCK_K x BLOCK_N blocks with two contraction steps into the accumulator
GROUPS = {
    "one_block_groups_of_1_2_1_tiles": dict(K=256, N=384, E=5, tiles=[0, 2, 2, 4], dead=4),
    "one_block_groups_of_0_1_3_9_tiles": dict(K=256, N=384, E=5, tiles=[1] + [2] * 3 + [4] * 9, dead=3),
    "cut_blocks_groups_of_0_1_3_tiles": dict(K=3072, N=1024, E=4, tiles=[1, 3, 3, 3], dead=2),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_grouped_matmul_kernel_equals_the_plain_formulation(case, dtype, tol):
    """Interpreted: groups of 0, 1, 3 and 9 tiles (an expert nobody picked, one tile, a block several
    tiles share), the last tile of a group padded with rows of token 0, dead tiles behind."""
    c = GROUPS[case]
    rng = np.random.default_rng(5)
    tm, K, N, E = 16, c["K"], c["N"], c["E"]
    n_live, n_tiles = len(c["tiles"]), len(c["tiles"]) + c["dead"]
    assert (gm.block_sizes(K, N, dtype) == (K, N)) == case.startswith("one_block")
    tile_group = jnp.asarray(c["tiles"] + [c["tiles"][-1]] * c["dead"], jnp.int32)  # the rest repeat the last
    x = rng.normal(size=(n_tiles * tm, K))
    x[n_live * tm - 5:n_live * tm] = x[0]  # a padded last tile: rows of token 0, read by nobody
    x, w = jnp.asarray(x, dtype), jnp.asarray(rng.normal(size=(E, K, N)) / np.sqrt(K), dtype)
    live = jnp.asarray([n_live], jnp.int32)
    got = gm.grouped_matmul(x, w, tile_group, live, tm=tm)
    want = gm.grouped_matmul_reference(x, w, tile_group, live, tm=tm)
    assert got.shape == (n_tiles * tm, N) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:n_live * tm], np.float32), np.asarray(want[:n_live * tm], np.float32),
                               atol=tol, rtol=tol)
    none = gm.grouped_matmul(x, w, tile_group, jnp.asarray([0], jnp.int32), tm=tm)  # nothing live: nothing to read
    assert none.shape == (n_tiles * tm, N)


@pytest.mark.parametrize("K,N,blocks", [
    (2048, 1024, (2048, 1024)), (1024, 2048, (1024, 2048)),    # trinity-mini's gate / up and down: 4 MiB, whole
    (7680, 2048, (1536, 1024)), (2048, 7680, (1024, 768)),     # openPangu's: 31.5 MB, the cut they have had since PR 33
], ids=["trinity_gate_up", "trinity_down", "pangu_gate_up", "pangu_down"])
def test_the_weight_block_follows_the_matrix(K, N, blocks):
    assert gm.block_sizes(K, N, jnp.bfloat16) == blocks
    assert K * N * 2 <= gm.WHOLE_MATRIX_BYTES or blocks == gm.cut_blocks(K, N)
    assert gm.tiles(K, N, 16, jnp.bfloat16) and gm.tiles(K, N, 128, jnp.bfloat16) and not gm.tiles(K, N, 8, jnp.bfloat16)
    assert 2 * blocks[0] * blocks[1] * 2 + 4 * 128 * (K + 2 * N) < gm.VMEM_LIMIT  # two blocks in flight beside the row tiles


# --- the Mosaic form, compiled for a described chip ---------------------------


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.mark.parametrize("tm", [16, 128], ids=["decode_step_tiles", "prefill_tiles"])
@pytest.mark.parametrize("K,N", [(2048, 1024), (1024, 2048)], ids=["gate_up", "down"])
def test_the_kernel_alone_compiles_at_trinity_s_matrices(one_chip, no_cache, K, N, tm):
    """128 experts of a layer, bf16, 40 tiles: the whole-matrix blocks lower through Mosaic inside
    ``VMEM_LIMIT`` (what the chip's compiler would refuse, it refuses here), into one call that holds
    no temporaries outside the kernel."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert gm.block_sizes(K, N, jnp.bfloat16) == (K, N)
    compiled = jax.jit(lambda x, w, tg, nl: gm._grouped_matmul(x, w, tg, nl, tm=tm, interpret=False)).lower(
        s((40 * tm, K), jnp.bfloat16), s((128, K, N), jnp.bfloat16), s((40,), jnp.int32), s((1,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20

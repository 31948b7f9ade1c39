"""A model with latent attention (MLA) and routed experts through the paged
serving engine, on the CPU at small sizes: ONE latent leaf a layer in the page
pool, prefix hits staged from shared LATENT pages, a dense layer before routed
ones, a share of the experts. The yardstick is the benchmark's plain reference
(``benchmark/reference_pangu.py``): one full forward over the prompt and the
served tokens, expanded attention, a loop over the held experts, no cache."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models import mla, moe
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.ops import paged_attention as pa
from fedml_tpu.serving import paged_kv
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.serving.fedml_predictor import LLMPredictor
from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys
from fedml_tpu.train.llm.generation import generate
from tests._engine_gate import hold

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_pangu  # noqa: E402
import weights_pangu  # noqa: E402

HF = {"model_type": "pangu_ultra_moe", "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
      "kv_lora_rank": 32, "moe_intermediate_size": 32, "n_routed_experts": 8, "n_shared_experts": 1,
      "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 4, "num_hidden_layers": 3,
      "num_key_value_heads": 4, "num_nextn_predict_layers": 0, "q_lora_rank": 32, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "rms_norm_eps": 1e-05, "rope_theta": 10000.0, "routed_scaling_factor": 2.5,
      "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 16, "vocab_size": 101,
      "router_width": 32, "expert_rank": 2}
CFG = config_from_hf_keys(HF, max_seq_len=128, dtype=jnp.float32, remat=False)
PS = 16
# float32 program, float32 reference: a served token may lie below the reference's best only by the
# rounding of two orders of float32 sums (and of the absorbed against the expanded form)
GAP_TOL = 1e-4


def _params(cfg, seed=11):
    shapes = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    return weights_pangu.make_params(weights_pangu.shapes_of(shapes), seed, jnp.float32)


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


def _engine(params, **kw):
    opts = dict(num_slots=4, chunk=4, page_size=PS, num_pages=48)
    opts.update(kw)
    return PagedContinuousBatchingEngine(params, CFG, **opts)


def _toks(n, seed):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], n).tolist()


def _gap(params, prompt, served):
    """The most by which a served token's reference logit lies below the
    reference's best, over every served position of one request."""
    seq = np.asarray(prompt + served[:-1], np.int32)
    rows = len(prompt) - 1 + np.arange(len(served))
    lg = np.asarray(reference_pangu.logits_at(params, jnp.asarray(seq), jnp.asarray(rows), reference_pangu.norm_cfg(HF)))
    return float((lg.max(axis=-1) - lg[np.arange(len(served)), served]).max())


def test_the_config_comes_from_the_family_s_keys_and_carries_the_share():
    assert CFG.layer_pattern == ("mla",) * 3 and [CFG.ffn_kind(i) for i in range(3)] == ["dense", "routed", "routed"]
    assert (CFG.q_lora_rank, CFG.kv_lora_rank, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, CFG.v_head_dim) == (32, 32, 16, 8, 16)
    assert (CFG.moe_routed_experts, CFG.moe_held_experts, CFG.moe_rank, CFG.moe_top_k, CFG.moe_d_ff) == (32, 8, 2, 4, 32)
    assert CFG.sandwich_norm and CFG.latent_width == 40 and CFG.latent_row_width == 128 and CFG.head_dim == 16
    whole = config_from_hf_keys({k: v for k, v in HF.items() if k not in ("router_width", "expert_rank")})
    assert (whole.moe_routed_experts, whole.moe_held_experts, whole.moe_rank) == (8, 8, 0)  # no share: all held
    with pytest.raises(ValueError, match="prediction"):
        config_from_hf_keys(dict(HF, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="expert layers inside a hybrid pattern are not wired"):
        config_from_hf_keys({"attn_layer_period": 4, "attn_layer_offset": 1, "num_experts": 16, "num_experts_per_tok": 2,
                             "vocab_size": 8, "hidden_size": 8, "num_hidden_layers": 4, "num_attention_heads": 2,
                             "intermediate_size": 8})


def test_the_cache_free_forward_equals_the_reference(params):
    toks = jnp.asarray(_toks(37, 3), jnp.int32)
    got = TransformerLM(CFG).apply({"params": params}, toks[None])[0]
    want = reference_pangu.logits_at(params, toks, jnp.arange(37), reference_pangu.norm_cfg(HF))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_a_fresh_prompt_then_decode_through_the_latent_pool_equals_the_reference(params):
    eng = _engine(params)
    try:
        prompt = _toks(21, 5)  # padded to a 32-token bucket
        served = eng.generate(prompt, 11)
        assert len(served) == 11 and _gap(params, prompt, served) < GAP_TOL
        assert served == [int(t) for t in generate(params, CFG, jnp.asarray([prompt]), 11)[0]]
        pool = eng._cache["layer_1"]["attn"]
        assert set(pool) == {"latent", "idx"} and pool["latent"].shape == (48, PS, 128)  # ONE leaf a layer
        st = eng.stats()
        # 2 routed layers; the 10 decoded tokens take 3 chunks of 4 steps, the slot live to each chunk's end
        assert st["kv_latent_bytes_live"] == 0 and st["moe_tokens_routed"] == (21 + 12) * 2
        assert sum(st["moe_expert_load"]) == st["moe_local_picks"] > 0
        leaks = eng._alloc.check_leaks()
        assert leaks["accounted"] and not leaks["leaked"]
    finally:
        eng.shutdown()


def test_a_prefix_hit_is_a_suffix_pass_over_shared_latent_pages(params):
    tel.reset()  # prefill spans a dense or hybrid test left in this worker's registry carry no routing
    eng = _engine(params)
    try:
        system = _toks(32, 7)
        first, second = system + _toks(9, 8), system + _toks(14, 9)
        a = eng.generate(first, 6)
        b = eng.generate(second, 6)  # two shared latent pages, a 14-token suffix pass
        assert eng.stats()["kv_prefix_hits"] == 1
        for prompt, served in ((first, a), (second, b)):
            assert _gap(params, prompt, served) < GAP_TOL
        spans = [s for s in tel.snapshot()["spans"] if s["name"] == "serving.cb.prefill"
                 and s.get("attrs", {}).get("shared") == 32]
        assert spans and all("local_picks" in s["attrs"] and "experts_hit" in s["attrs"] for s in spans)
    finally:
        eng.shutdown()


def test_a_batch_of_two_lengths_equals_the_reference_and_counts_its_routing(params):
    """Requests of different lengths decode side by side; one finishes first, so a freed slot
    (cache_idx -1: routed to no expert) sits beside a live one."""
    tel.reset()
    eng = _engine(params)
    try:
        prompts, new = [_toks(5, 1), _toks(40, 2)], [13, 5]
        handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
        served = [h.result(timeout=300) for h in handles]
        assert [len(s) for s in served] == new
        for p, s in zip(prompts, served):
            assert _gap(params, p, s) < GAP_TOL
        st = eng.stats()
        # every live token is routed in each of the 2 routed layers; a freed slot's and a padded
        # position's are not. The decode chunks run to their end (4 steps) for a live slot.
        chunks = [s["attrs"] for s in tel.snapshot()["spans"] if s["name"] == "serving.cb.chunk"]
        assert all({"tokens_routed", "local_picks", "experts_hit"} <= set(c) for c in chunks)
        decode_routed = sum(c["tokens_routed"] for c in chunks)
        assert decode_routed == sum(c["slots"] for c in chunks) * 4 * 2
        assert st["moe_tokens_routed"] == decode_routed + (5 + 40) * 2
        assert tel.counter("serving.moe.tokens_routed").value == st["moe_tokens_routed"]
        assert tel.counter("serving.moe.local_picks").value == st["moe_local_picks"]
        assert tel.counter("serving.moe.experts_hit").value == st["moe_experts_hit"] <= st["moe_local_picks"]
    finally:
        eng.shutdown()


def test_a_chunks_routing_lands_a_chunk_later_on_the_span_that_launched_it(params):
    """The loop runs one chunk ahead: chunk n's packed routing is fetched with
    chunk n's tokens, while chunk n+1 runs, and noted on chunk n's OWN span.
    Riders join while a chunk is in flight, so consecutive chunks differ."""
    tel.reset()
    eng = _engine(params, num_slots=3)
    try:
        reached, release = hold(eng, "_land_chunk")  # chunk 2 is launched, chunk 1 not yet fetched
        reqs = [(_toks(21, 5), 18), (_toks(7, 6), 6), (_toks(40, 7), 11)]
        handles = [eng.submit(*reqs[0])]
        assert reached.wait(timeout=60)
        (unlanded,) = [s["attrs"] for s in tel.snapshot()["spans"] if s["name"] == "serving.cb.chunk"]
        assert "tokens_routed" not in unlanded  # chunk 1's span closed at its launch; chunk 2's is still open
        handles += [eng.submit(p, n) for p, n in reqs[1:]]
        release.set()
        for (p, n), h in zip(reqs, handles):
            served = h.result(timeout=300)
            assert served == [int(t) for t in generate(params, CFG, jnp.asarray([p]), n)[0]]
            assert _gap(params, p, served) < GAP_TOL
        chunks = [s["attrs"] for s in sorted(tel.snapshot()["spans"], key=lambda s: s["t0_ns"])
                  if s["name"] == "serving.cb.chunk"]
        assert [c["slots"] for c in chunks[:3]] == [1, 1, 3] and len({c["slots"] for c in chunks}) > 1
        for c in chunks:  # every live row's 4 steps through the 2 routed layers: this chunk's rows, not its neighbour's
            assert c["tokens_routed"] == c["slots"] * 4 * 2 and 0 < c["experts_hit"] <= c["local_picks"]
        st = eng.stats()
        assert st["moe_tokens_routed"] == sum(c["tokens_routed"] for c in chunks) + (21 + 7 + 40) * 2
        spans = tel.snapshot()["spans"]
        launched = {s["seq"] for s in spans if s["name"] == "serving.cb.chunk"}
        ahead = [s for s in spans if s["name"] == "serving.cb.chunk.sync" and s["parent_seq"] in launched]
        assert len(ahead) == len(chunks) - 1  # one start from nothing in flight
        leaks = eng._alloc.check_leaks()
        assert leaks["accounted"] and not leaks["leaked"]
    finally:
        eng.shutdown()


def test_the_predictor_serves_it_with_no_option_beyond_the_config(params):
    from fedml_tpu.train.llm.tokenizer import BPETokenizer

    tok = BPETokenizer({chr(0x10000 + i): i for i in range(HF["vocab_size"])}, [], mode="metaspace")
    pred = LLMPredictor(params, CFG, tok, paged=True, num_slots=2, decode_chunk=4, page_size=PS, num_pages=32)
    try:
        prompt = _toks(19, 4)
        out = pred.predict({"prompt": "".join(chr(0x10000 + t) for t in prompt), "max_new_tokens": 5})
        assert _gap(params, prompt, out["token_ids"]) < GAP_TOL
    finally:
        pred.engine.shutdown()


# ---- absorbed = expanded; the kernel = the plain formulation ----------------------------------------

def test_absorbed_decode_equals_expanded_attention(params):
    """One token's attention over a written row: the paged absorbed form (q~ = W_uk^T q_n over the
    latent pool) against the expanded form over the same latents as a row cache."""
    rng = np.random.default_rng(0)
    B, H, r, dn, dr, dv, S = 3, 4, 32, 16, 8, 16, 64
    W = 128
    lengths = np.asarray([17, 64, 1], np.int32)
    latents = np.zeros((B, S, W), np.float32)
    latents[..., :r + dr] = rng.normal(size=(B, S, r + dr))
    w_kv = jnp.asarray(rng.normal(size=(r, H, dn + dv)) / np.sqrt(r), jnp.float32)
    q_n = jnp.asarray(rng.normal(size=(B, 1, H, dn)), jnp.float32)
    q_r = jnp.asarray(rng.normal(size=(B, 1, H, dr)), jnp.float32)
    scale = (dn + dr) ** -0.5
    want = jnp.stack([mla.expanded_latent_attention(q_n[b:b + 1], q_r[b:b + 1], jnp.asarray(latents[b:b + 1]), w_kv,
                                                    int(lengths[b]) - 1, int(lengths[b]), rank=r, d_nope=dn,
                                                    scale=scale)[0, 0] for b in range(B)])
    # the same latents as pages: row b's position l at page 1 + b * 4 + l // 16
    pool = np.zeros((1 + B * 4, PS, W), np.float32)
    pool[1:] = latents.reshape(B * 4, PS, W)
    tables = 1 + np.arange(B * 4, dtype=np.int32).reshape(B, 4)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_n[:, 0], w_kv[..., :dn])
    q_full = jnp.concatenate([q_abs, q_r[:, 0], jnp.zeros((B, H, W - r - dr))], axis=-1)
    for attend in (pa.paged_latent_attention, pa.paged_latent_attention_reference):
        o_lat = attend(q_full, jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(lengths), rank=r, scale=scale)
        got = jnp.einsum("bhr,rhd->bhd", o_lat, w_kv[..., dn:])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_the_latent_kernel_equals_the_plain_formulation_on_a_shared_pool(dtype, tol):
    """Interpreted: rows of several lengths (0, inside a page, page-aligned, past a block of pages),
    two rows sharing their leading pages, unowned table entries on the trash page."""
    rng = np.random.default_rng(1)
    B, H, r, W, n_pages, n_blocks = 5, 8, 128, 256, 80, 40  # 40 blocks of 16: a 512-token block and a second one
    pool = jnp.asarray(rng.normal(size=(n_pages, PS, W)), dtype)
    q = jnp.asarray(rng.normal(size=(B, H, W)), dtype)
    lengths = np.asarray([0, 7, 32, 600, 45], np.int32)
    tables = np.zeros((B, n_blocks), np.int32)
    free = iter(range(1, n_pages))
    for b, n in enumerate(lengths):
        for j in range(-(-int(n) // PS)):
            tables[b, j] = next(free)
    tables[4, :2] = tables[2, :2]  # a shared prefix
    got = pa.paged_latent_attention(q, pool, jnp.asarray(tables), jnp.asarray(lengths), rank=r, scale=0.07)
    want = pa.paged_latent_attention_reference(q, pool, jnp.asarray(tables), jnp.asarray(lengths), rank=r, scale=0.07)
    assert got.shape == (B, H, r) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[1:], np.float32), np.asarray(want[1:], np.float32), atol=tol, rtol=tol)
    assert not np.asarray(got[0], np.float32).any()  # a row of length 0 reads no page
    assert pa.latent_tiles(512, 640, 16, jnp.bfloat16) and not pa.latent_tiles(512, 576, 16, jnp.bfloat16)


def test_the_seam_takes_the_latent_leaf_without_a_fork(params):
    """``_paged_admit_fn`` scatters a row's latent pages by the leaf's own trailing shape and
    ``_paged_gather_fn`` brings shared ones back: the page round trip is the identity."""
    pcfg = paged_kv.paged_config(paged_kv.row_config(CFG), page_size=PS, num_pages=12)
    pool = paged_kv.paged_pool_init(params, pcfg, 2)
    rng = np.random.default_rng(2)
    row = {f"layer_{i}": {"attn": {"latent": jnp.asarray(rng.normal(size=(1, 128, 128)), jnp.float32),
                                   "idx": jnp.int32(40)}} for i in range(3)}
    write = np.zeros((8,), np.int32)
    write[:3] = [5, 2, 9]
    carry = (jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), jnp.zeros((2, 2), jnp.uint32))
    pool, _, _ = paged_kv._paged_admit_fn(pcfg)(pool, row, write, np.int32(0), jnp.zeros((1, 101)), np.uint32(0),
                                               np.float32(0), carry, np.int32(40), np.array([[0, 3]], np.int32))
    back = paged_kv._paged_gather_fn(pcfg)(pool, write, np.int32(32))
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(back[f"layer_{i}"]["attn"]["latent"][0, :48]),
                                      np.asarray(row[f"layer_{i}"]["attn"]["latent"][0, :48]))
        assert int(back[f"layer_{i}"]["attn"]["idx"]) == 32

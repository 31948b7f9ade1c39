"""A model with window and full attention layers through the paged engine, on
the CPU at a small size (window 8, page 4, 2 kv heads, 8 experts; seeded
weights): the model against ``benchmark/reference_trinity.py`` for a prefill,
for prefill + paged decode ACROSS the horizon and for a suffix pass behind a
shared prefix that has left the window; the two page groups of the allocator
and of the engine (a request's bound, pages released while it lives and used
again, no leak, admission deferring on either group, zero retrace with two
tables); a one-group model's tables unchanged."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.serving import paged_kv
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.serving.paged_kv import TRASH_PAGE, PagedKVAllocator, window_bound
from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys
from fedml_tpu.train.llm.generation import generate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_trinity  # noqa: E402
import weights_trinity  # noqa: E402

HF = {
    "model_type": "afmoe", "head_dim": 32, "hidden_size": 64, "intermediate_size": 128, "vocab_size": 512,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention",
                    "sliding_attention"],
    "moe_intermediate_size": 32, "mup_enabled": True, "n_group": 1, "topk_group": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 5, "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 8, "tie_word_embeddings": False,
}
S, PS, C, W = 128, 4, 4, 8
CFG = config_from_hf_keys(HF, max_seq_len=S, dtype=jnp.float32, remat=False)
REF = reference_trinity.norm_cfg(HF)
BOUND = window_bound(W, C, PS)
GAP_TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    tree = jax.eval_shape(lambda k: TransformerLM(CFG).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                          jax.random.PRNGKey(0))
    return weights_trinity.make_params(weights_trinity.shapes_of(tree), 11, jnp.float32)


def _toks(n, seed):
    return np.random.default_rng(seed).integers(1, 512, n).tolist()


def _gaps(params, prompt, served):
    """How far each served token lies below the reference's best at its position."""
    seq = np.zeros((S,), np.int32)
    seq[:len(prompt) + len(served) - 1] = prompt + served[:-1]
    rows = jnp.arange(len(prompt) - 1, len(prompt) + len(served) - 1)
    lg = np.asarray(reference_trinity.logits_at(params, jnp.asarray(seq), rows, REF))
    return lg.max(-1) - lg[np.arange(len(served)), np.asarray(served)]


def _engine(params, **kw):
    kw = {"num_slots": 4, "chunk": C, "page_size": PS, "num_pages": 129, **kw}
    return PagedContinuousBatchingEngine(params, CFG, **kw)


# ---- the config and the model against the plain reference -----------------------------------------------

def test_the_family_s_keys_give_the_layers_the_issue_wrote_down():
    assert CFG.attn_kinds == ("window", "window", "full", "window", "window") and CFG.sliding_window == 8
    assert CFG.window_layers == (0, 1, 3, 4) and CFG.head_dim == 32 != CFG.d_model // CFG.n_heads
    assert CFG.qk_norm and CFG.attn_gate and CFG.sandwich_norm and not CFG.use_rope and CFG.embed_scale == 8.0
    assert (CFG.first_k_dense_replace, CFG.moe_routed_experts, CFG.moe_held_experts, CFG.moe_top_k, CFG.moe_d_ff,
            CFG.moe_shared_experts, CFG.moe_routed_scaling, CFG.moe_norm_topk, CFG.moe_select_bias) == \
        (1, 8, 8, 2, 32, 1, 2.826, True, True)
    # every config from before builds what it built: head_dim is d_model / n_heads unless the config says otherwise
    old = TransformerConfig(d_model=96, n_heads=6)
    assert old.head_dim == 16 and old.attn_kinds == () and old.attn_kind(3) == "full" and old.window_layers == ()
    with pytest.raises(ValueError):
        config_from_hf_keys(dict(HF, score_func="softmax"))
    with pytest.raises(ValueError):
        config_from_hf_keys(dict(HF, layer_types=["full_attention"] * 4))


def test_the_forward_pass_equals_the_reference(params):
    toks = np.asarray(_toks(64, 0), np.int32)
    with jax.default_matmul_precision("highest"):
        got = TransformerLM(CFG).apply({"params": params}, jnp.asarray(toks)[None])[0]
        only = TransformerLM(CFG).apply({"params": params}, jnp.asarray(toks)[None], logit_rows=jnp.asarray([40]))[0]
    want = reference_trinity.logits_at(params, jnp.asarray(toks), jnp.arange(64), REF)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(only[0]), np.asarray(want[40]), atol=2e-5, rtol=2e-5)  # the head over one row


def test_generate_honours_the_window(params):
    """The engine's reference path (contiguous rows): a prompt of 5 windows, 12 tokens on."""
    prompt = _toks(40, 1)
    out = [int(t) for t in generate(params, CFG, jnp.asarray([prompt], jnp.int32), 12)[0]]
    assert _gaps(params, prompt, out).max() < GAP_TOL


def test_prefill_and_paged_decode_across_the_horizon_equal_the_reference(params):
    """Prompts below, at and over the window, decoded far enough that every one's horizon moves over
    page boundaries; several in one batch."""
    eng = _engine(params)
    try:
        prompts = [_toks(n, 10 + n) for n in (3, 8, 9, 27, 60)]
        handles = [eng.submit(p, 26) for p in prompts]
        for p, h in zip(prompts, handles):
            assert _gaps(params, p, h.result(timeout=600)).max() < GAP_TOL, len(p)
        assert eng.stats()["kv_window_pages_released"] > 0
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


def test_a_suffix_pass_behind_a_shared_prefix_that_has_left_the_window(params):
    """A 24-token shared prefix (three windows): a later request's suffix pass starts behind it, sees its
    last 7 tokens in the window layers and all of it in the full layer; a long suffix's own window then
    leaves the prefix behind altogether."""
    eng = _engine(params)
    try:
        system = _toks(24, 3)
        first = eng.generate(system + _toks(2, 4), 5)        # registers the prefix: its window reaches over all of it
        assert _gaps(params, system + _toks(2, 4), first).max() < GAP_TOL
        hits0 = eng.stats()["kv_prefix_hits"]
        for n in (1, 5, 30):
            p = system + _toks(n, 50 + n)
            assert _gaps(params, p, eng.generate(p, 14)).max() < GAP_TOL, n
        assert eng.stats()["kv_prefix_hits"] == hits0 + 3
        spans = [s for s in tel.snapshot()["spans"] if s["name"] == "serving.cb.prefill"][-3:]
        assert [s["attrs"]["shared"] for s in spans] == [24, 24, 24]
        assert eng._alloc.check_leaks()["accounted"]
    finally:
        eng.shutdown()


# ---- the allocator's window group ------------------------------------------------------------------------

def test_a_one_group_allocator_is_what_it_was():
    a = PagedKVAllocator(9, 4)
    assert a.window_pages == 0 and "kv_window_pages_total" not in a.stats() and a.group_pages() == {"full": (0, 8)}
    m = a.match(list(range(12)))
    assert m.pages == [] and m.window_pages == []
    a.free_window([])  # nothing to free, nothing raised
    assert a.check_leaks()["accounted"]


def test_the_window_group_allocates_frees_and_refuses_on_its_own():
    a = PagedKVAllocator(9, 4, window_pages=5, window=8)
    got = a.alloc_window(3)
    assert sorted(got) == [1, 2, 3] and a.group_pages() == {"full": (0, 8), "window": (3, 1)}
    assert a.alloc_window(2) is None and a.stats()["kv_window_alloc_deferred"] == 1
    a.free_window(got[:2], released=True)
    assert a.stats()["kv_window_pages_released"] == 2 and a.stats()["kv_window_pages_free"] == 3
    with pytest.raises(RuntimeError):
        a.free_window(got[:1])
    a.free_window(got[2:])
    assert a.check_leaks()["accounted"] and a.stats()["kv_pages_free"] == 8  # the full group never moved


def test_a_match_needs_the_window_pages_of_its_tail_alone():
    """Window 8, pages of 4: a pass behind n shared blocks sees the last 7 tokens of them: blocks n - 2 and n - 1."""
    a = PagedKVAllocator(33, 4, window_pages=17, window=8, watermark_frac=0.0)
    toks = list(range(100, 124))                                 # 6 chunks
    full = a.alloc(6)
    win = a.alloc_window(2)                                      # the request's window reached chunks 4 and 5 only
    a.register_prefix(toks, full, [TRASH_PAGE] * 4 + win)
    m = a.match(toks + [1, 2])
    assert m.pages == full and m.window_pages == [TRASH_PAGE] * 4 + win       # a reference on the two it can see
    assert [a._wref[p] for p in win] == [3, 3] and [a._ref[p] for p in full] == [3] * 6
    a.free(m.pages)
    a.free_window(m.window_pages)
    m = a.match(toks[:20] + [7, 7, 7, 7, 7])                     # 5 chunks match, chunk 3 of the tail has no window page
    assert m.pages == [] and m.window_pages == []                # ... nor does any shorter match: its tail neither
    # another request computes chunks 0-3 itself and its window reaches 2 and 3: the nodes take its window pages
    own = a.alloc_window(2)
    other_full = a.alloc(4)
    a.register_prefix(toks[:16], other_full, [TRASH_PAGE] * 2 + own)
    m = a.match(toks[:16] + [9])
    assert m.pages == full[:4] and m.window_pages == [TRASH_PAGE] * 2 + own
    for pages, free in ((m.pages + full + other_full, a.free), (m.window_pages + win + own, a.free_window)):
        free(pages)
    leaks = a.check_leaks()
    assert leaks["leaked"] == [] and leaks["accounted"]          # what is left is the trie's, one reference a page


def test_a_short_window_group_takes_the_trie_s_window_pages_and_the_node_stays():
    a = PagedKVAllocator(33, 4, window_pages=5, window=8, watermark_frac=0.0)
    toks = list(range(8))
    full, win = a.alloc(2), a.alloc_window(2)
    a.register_prefix(toks, full, win)
    a.free(full)
    a.free_window(win)                                          # the request is gone: the trie alone holds the four pages
    got = a.alloc_window(4)                                     # 2 free + the trie's 2
    assert got is not None and a.stats()["kv_window_prefix_evictions"] == 2 and a.stats()["kv_prefix_nodes"] == 2
    assert a.match(toks + [1]).pages == []                      # the nodes are there, their window pages are not
    a.free_window(got)
    assert a.check_leaks()["accounted"]
    a._evict_locked(2)                                          # and a node that goes takes its window page along
    b = PagedKVAllocator(33, 4, window_pages=5, window=8, watermark_frac=0.0)
    full, win = b.alloc(2), b.alloc_window(2)
    b.register_prefix(toks, full, win)
    b.free(full)
    b.free_window(win)
    assert b.alloc(31) is not None and b.stats()["kv_window_pages_free"] == 4 and b.stats()["kv_prefix_nodes"] == 0


# ---- the engine's two groups ------------------------------------------------------------------------------

def test_a_long_request_never_holds_more_than_its_bound_and_its_pages_are_used_again(params, monkeypatch):
    """A window group of ONE bound: a request of 30 + 60 tokens needs 23 window blocks in all, so the
    pages its horizon passed are what it is given again; it holds at most the bound at every launch."""
    held = []
    real = PagedContinuousBatchingEngine._slide_windows

    def watched(self, active):
        real(self, active)
        held.extend(int(self._wspan[b, 1] - self._wspan[b, 0]) for b in np.flatnonzero(active))

    monkeypatch.setattr(PagedContinuousBatchingEngine, "_slide_windows", watched)
    assert BOUND == 4
    eng = _engine(params, num_slots=2, num_window_pages=BOUND + 1)
    try:
        prompt = _toks(30, 8)
        out = eng.generate(prompt, 60)
        assert _gaps(params, prompt, out).max() < GAP_TOL
        st = eng.stats()
        assert held and max(held) <= BOUND and st["kv_window_bound_pages"] == BOUND
        assert st["kv_window_pages_released"] >= 12 and st["kv_window_pages_total"] == BOUND
        assert eng._alloc.check_leaks()["accounted"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("short", ["window", "full"])
def test_admission_defers_when_either_group_is_short_and_resumes(params, short):
    kw = {"num_window_pages": BOUND + 1} if short == "window" else {"num_pages": 14, "watermark_frac": 0.0}
    eng = _engine(params, num_slots=2, **kw)
    try:
        prompts = [_toks(20, 30 + i) for i in range(3)]          # 20 + 16 tokens: 9 full pages a request, 13 in the pool
        handles = [eng.submit(p, 16) for p in prompts]
        for p, h in zip(prompts, handles):
            assert _gaps(params, p, h.result(timeout=600)).max() < GAP_TOL
        st = eng.stats()
        assert st["kv_admit_deferred_" + short] >= 1 and st["requests_done"] == 3
        assert st["kv_admit_deferred_" + ("full" if short == "window" else "window")] == 0
        assert eng._alloc.check_leaks()["accounted"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("tail,hit", [(3, False), (8, False), (27, False), (60, False), (1, True), (5, True), (30, True)])
def test_an_admission_moves_the_blocks_it_owns_and_its_spans_say_how_many(params, tail, hit):
    """The page handoff follows the prompt, a group a range: the full layer's (k, v) take the prompt's blocks
    behind the shared ones, the four window layers' the blocks of its last 8 tokens (never more than 3 blocks of
    4, whatever the prompt), and a hit gathers the shared blocks (6 in the full layer, the 2 its pass can see
    in a window layer) where the row has 32 blocks in each of 10 leaves; what is served is the reference's."""
    eng = _engine(params)
    try:
        system = _toks(24, 3) if hit else []
        if hit:
            eng.generate(system + _toks(2, 4), 5)                # registers the prefix: 6 blocks
        prompt = system + _toks(tail, 70 + tail)
        assert _gaps(params, prompt, eng.generate(prompt, 9)).max() < GAP_TOL
        spans = tel.snapshot()["spans"]
        moved = [s for s in spans if s["name"] == "serving.paged.transfer"][-1]["attrs"]
        prefill = [s for s in spans if s["name"] == "serving.cb.prefill"][-1]["attrs"]
        P, n_shared = len(prompt), 6 if hit else 0
        last = -(-P // PS)
        first_w = max((P - W + 1) // PS, n_shared) if P >= W else n_shared
        assert moved["blocks_row"] == (S // PS) * 10
        assert moved["blocks_full"] == (last - n_shared) * 2 and moved["blocks_window"] == (last - first_w) * 8
        assert moved["blocks_window"] <= 3 * 8 < moved["blocks_row"]
        assert prefill.get("blocks_gathered") == (6 * 2 + 2 * 8 if hit else None) and prefill["shared"] == n_shared * PS
        assert eng._alloc.check_leaks()["accounted"]
    finally:
        eng.shutdown()


def test_a_mixed_run_with_sharing_leaks_nothing_and_compiles_nothing_after_warm_up(params):
    labels = ("prefill", "paged_step", "paged_admit", "paged_gather", "paged_suffix_prefill")
    eng = _engine(params)
    try:
        system = _toks(16, 5)
        eng.generate(system, 5)   # alone first: its window reaches over the blocks a pass behind it will see
        for tail in (3, 20, 40):                                 # suffix buckets 16, 32, 48 behind the shared prefix
            eng.generate(system + _toks(tail, tail), 5)
        for n in (5, 30, 50):                                    # and whole prefills in buckets 16, 32, 64
            eng.generate(_toks(n, 60 + n), 5)
        before = {k: tel.compile_count(k) for k in labels}
        handles = [eng.submit(system + _toks(n, 100 + n), 9, temperature=t, seed=n)
                   for n, t in ((2, 0.0), (13, 0.7), (25, 0.0), (6, 0.0), (38, 0.0), (44, 0.0))]
        handles += [eng.submit(_toks(n, 9 + n), 21) for n in (11, 27, 61)]
        for h in handles:
            h.result(timeout=600)
        assert {k: tel.compile_count(k) for k in labels} == before   # one executable serves both tables' every mix
        st = eng.stats()
        assert st["slots_active"] == 0 and st["kv_window_pages_held"] == 0 and st["kv_window_pages_unbounded"] == 0
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["accounted"]
        gauges = tel.snapshot()
        # the registry's counter is the process's: every engine of this module counts into it
        assert gauges["counters"]["serving.kv.window_pages_released"] >= st["kv_window_pages_released"] > 0
        chunk = [s for s in gauges["spans"] if s["name"] == "serving.cb.chunk"][-1]["attrs"]
        assert chunk["kv_tokens_window"] <= chunk["kv_tokens_full"] and chunk["kv_tokens_window"] <= chunk["slots"] * C * W
    finally:
        eng.shutdown()


def test_a_model_with_no_window_layers_has_one_group_and_the_tables_it_had(params):
    plain = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=64,
                              dtype=jnp.float32, remat=False)
    p = TransformerLM(plain).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = PagedContinuousBatchingEngine(p, plain, num_slots=2, chunk=4, page_size=4, num_pages=33)
    try:
        assert eng._wtables is None and eng._paged_cfg.kv_window_pages == 0 and eng._alloc.window_pages == 0
        out = eng.generate(list(range(1, 12)), 6)
        ref = [int(t) for t in generate(p, plain, jnp.asarray([list(range(1, 12))], jnp.int32), 6)[0]]
        assert out == ref
        st = eng.stats()
        assert "kv_window_pages_total" not in st and "kv_admit_deferred_window" not in st
        # pages are handed out from the top of the free list down, as they always were: 11 + 6 tokens are 5 pages
        assert sorted(n.page for n in eng._alloc._nodes) == [1, 2]
        pool = paged_kv.paged_pool_init(p, eng._paged_cfg, 2)
        assert pool["layer_0"]["attn"]["k"].shape == (33, 4, 2, 8)
    finally:
        eng.shutdown()
    with pytest.raises(ValueError):
        paged_kv.paged_config(plain, page_size=4, num_pages=33, window_pages=9)
    with pytest.raises(ValueError):
        paged_kv.paged_config(CFG, page_size=4, num_pages=33)


def test_a_pass_too_large_for_the_einsum_takes_the_rows_kernel_and_says_the_same(params, monkeypatch):
    """``_row_attention_impl`` decides from shapes; told "pallas" at this small size (the kernel interpreted),
    a whole prefill, a suffix pass behind a shared prefix and ``generate()`` serve what the reference says."""
    from fedml_tpu.models import transformer
    from fedml_tpu.train.llm import generation

    assert transformer._row_attention_impl("cpu", 16640, 16896, 32, 128) == "xla"      # interpreted kernels: never by itself
    assert transformer._row_attention_impl("tpu", 16640, 16896, 32, 128) == "pallas"   # 36 GB of scores
    assert transformer._row_attention_impl("tpu", 512, 16896, 32, 128) == "pallas"     # 1.1 GB
    assert transformer._row_attention_impl("tpu", 1280, 2048, 32, 128) == "xla"        # every pass of a 2,048-token row
    assert transformer._row_attention_impl("tpu", 1, 16896, 32, 128) == "xla"          # generate()'s token steps
    monkeypatch.setattr(generation, "_COMPILED", {})
    monkeypatch.setattr(transformer, "_row_attention_impl", lambda platform, T, S, h, d: "pallas" if T > 1 else "xla")
    eng = _engine(params)
    try:
        system = _toks(24, 3)
        for p in (system + _toks(2, 4), system + _toks(30, 80), _toks(45, 81)):
            assert _gaps(params, p, eng.generate(p, 10)).max() < GAP_TOL
        assert eng.stats()["kv_prefix_hits"] >= 1
    finally:
        eng.shutdown()
    prompt = _toks(40, 1)
    out = [int(t) for t in generate(params, CFG, jnp.asarray([prompt], jnp.int32), 6)[0]]
    assert _gaps(params, prompt, out).max() < GAP_TOL
    monkeypatch.setattr(generation, "_COMPILED", {})

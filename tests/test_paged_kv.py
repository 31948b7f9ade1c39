"""Paged KV cache (serving/paged_kv.py + PagedContinuousBatchingEngine):
token-exactness vs the reference ``generate()`` path on ragged lengths
(including through the prefix-sharing suffix-prefill), zero-recompile
admission, refcount lifecycle under randomized workloads (no leak, no
double-free), mid-chunk EOS page reclamation, and the allocator's
watermark / eviction behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.serving.paged_kv import TRASH_PAGE, PagedKVAllocator
from fedml_tpu.train.llm.generation import generate

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=64, dtype=jnp.float32, remat=False, lora_rank=0,
)


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(CFG)
    return model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]


@pytest.fixture()
def engine(params):
    eng = PagedContinuousBatchingEngine(params, CFG, num_slots=2, chunk=4)
    yield eng
    eng.shutdown()


def _prompt(length, seed):
    return list(np.random.default_rng(seed).integers(1, CFG.vocab_size, length))


def _ref(params, prompt, max_new, cfg=CFG):
    return np.asarray(
        generate(params, cfg, jnp.asarray([prompt], jnp.int32), max_new)
    )[0].tolist()


def _hybrid_model():
    """tests/test_hybrid_serving.py's model (Mamba layers beside attention) and its weights."""
    from tests.test_hybrid_serving import CFG as HYBRID
    import weights_jamba  # on the path once that module is imported

    shapes = jax.eval_shape(lambda k: TransformerLM(HYBRID).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    return HYBRID, weights_jamba.make_params(weights_jamba.shapes_of(shapes), 11, jnp.float32)


# --- allocator ---------------------------------------------------------------


def test_allocator_alloc_free_and_watermark():
    a = PagedKVAllocator(num_pages=9, page_size=16, watermark_frac=0.25)
    # 8 usable pages, watermark 2: an alloc that would dip into the
    # reserve defers (returns None) instead of draining the pool
    assert a.watermark == 2
    pages = a.alloc(6)
    assert pages is not None and len(pages) == 6
    assert TRASH_PAGE not in pages and len(set(pages)) == 6
    assert a.alloc(1) is None  # 2 free == watermark: defer
    assert a.stats()["kv_alloc_deferred"] == 1
    a.free(pages)
    assert a.stats()["kv_pages_free"] == 8
    assert a.check_leaks()["accounted"]


def test_allocator_double_free_and_dead_incref_raise():
    a = PagedKVAllocator(num_pages=5, page_size=16)
    (p,) = a.alloc(1)
    a.free([p])
    with pytest.raises(RuntimeError, match="double-free"):
        a.free([p])
    with pytest.raises(RuntimeError, match="dead page"):
        a.incref([p])


def test_prefix_register_match_and_eviction():
    ps = 4
    a = PagedKVAllocator(num_pages=10, page_size=ps, watermark_frac=0.0)
    toks = list(range(1, 1 + 3 * ps))  # 3 full chunks
    pages = a.alloc(3)
    a.register_prefix(toks, pages)
    assert a.stats()["kv_prefix_nodes"] == 3
    # the registering request releases its references; retention keeps the
    # pages alive for future matches
    a.free(pages)
    shared = a.match_prefix(toks + [7, 8])
    assert shared == pages  # full-prefix hit, in chunk order
    assert a.stats()["kv_prefix_hits"] == 1
    a.free(shared)
    # a diverging second chunk only matches the first
    assert a.match_prefix(toks[:ps] + [88] * ps) == pages[:1]
    a.free(pages[:1])
    # allocation pressure evicts LRU retentions (leaves first) and the
    # evicted chunks stop matching (9 usable pages, floor watermark 1:
    # an 8-page grab must reclaim all 3 retained chunks)
    big = a.alloc(8)
    assert big is not None and len(big) == 8
    assert a.stats()["kv_prefix_evictions"] >= 1
    a.free(big)
    assert a.check_leaks()["accounted"]


def test_allocator_randomized_lifecycle_no_leaks():
    """Randomized workload over the full allocator surface: every page is
    accounted for at the end (leak or double-free would have raised or
    shows in check_leaks)."""
    rng = np.random.default_rng(0)
    ps = 4
    a = PagedKVAllocator(num_pages=33, page_size=ps, watermark_frac=0.05)
    live = []  # (pages, tokens or None)
    for step in range(400):
        op = rng.integers(0, 3)
        if op == 0 and len(live) < 8:
            toks = list(rng.integers(1, 50, int(rng.integers(1, 4)) * ps))
            shared = a.match_prefix(toks)
            n_more = len(toks) // ps - len(shared)
            fresh = a.alloc(n_more)
            if fresh is None:
                a.free(shared)
                continue
            table = list(shared) + fresh
            a.register_prefix(toks, table)
            live.append((table, toks))
        elif op == 1 and live:
            pages, _ = live.pop(int(rng.integers(0, len(live))))
            a.free(pages)
        elif op == 2:
            extra = a.alloc(int(rng.integers(1, 4)))
            if extra is not None:
                a.free(extra)
    for pages, _ in live:
        a.free(pages)
    leaks = a.check_leaks()
    assert leaks["leaked"] == [] and leaks["bad_free"] == []
    assert leaks["accounted"]


# --- engine ------------------------------------------------------------------


# the fixture's geometry, and tests/test_continuous_batching.py's (its pages of 8)
GEOMETRIES = [pytest.param(dict(page_size=16), id="page16"),
              pytest.param(dict(page_size=8), id="page8")]


# a hybrid model over tests/test_hybrid_serving.py's four slots: replies of several lengths free slots
# mid-run, and the step moves the live slots' state alone (ops/ssm_step.py, interpreted)
HYBRID_GEOMETRY = pytest.param(dict(page_size=16, num_slots=4, num_pages=48, state_snapshots=4), id="hybrid_page16")


@pytest.mark.parametrize("geometry", GEOMETRIES + [HYBRID_GEOMETRY])
def test_paged_engine_greedy_matches_generate_ragged(params, geometry):
    """Keystone: the engine (block-table scatter/gather decode, requests
    interleaved across 2 slots, 4 for the hybrid model) is token-exact vs the
    contiguous reference path across ragged prompt lengths spanning page
    boundaries."""
    hybrid = "state_snapshots" in geometry
    cfg, params = _hybrid_model() if hybrid else (CFG, params)
    new = (12, 5, 9, 12, 3, 7) if hybrid else (12,) * 6
    skipped = tel.counter("serving.state.step_slots_skipped").value
    eng = PagedContinuousBatchingEngine(params, cfg, **{"num_slots": 2, "chunk": 4, **geometry})
    try:
        prompts = [_prompt(n, i) for i, n in enumerate((3, 15, 16, 17, 31, 40))]
        handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
        for p, n, h in zip(prompts, new, handles):
            assert h.result(timeout=120) == _ref(params, p, n, cfg)
        assert (tel.counter("serving.state.step_slots_skipped").value > skipped) == hybrid
        # all pages returned (no retention yet for <1-page prompts; longer
        # prompts retain their full chunks at refcount exactly 1)
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


def test_prefix_sharing_is_token_exact_and_skips_prefill(engine, params):
    """Two prompts sharing a 32-token system prefix: the second maps the
    shared pages (prefix hit) and still decodes token-exactly through the
    rewound suffix prefill."""
    system = _prompt(32, 777)
    a = system + _prompt(9, 1)
    b = system + _prompt(5, 2)
    assert engine.generate(a, 10) == _ref(params, a, 10)
    hits0 = engine._alloc.stats()["kv_prefix_hits"]
    assert engine.generate(b, 10) == _ref(params, b, 10)
    st = engine.stats()
    assert st["kv_prefix_hits"] == hits0 + 1
    assert st["kv_prefix_nodes"] >= 2  # the system prefix stayed resident
    leaks = engine._alloc.check_leaks()
    assert leaks["leaked"] == [] and leaks["accounted"]


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_paged_executables_compile_once_across_mixed_admissions(params, geometry):
    """Zero-recompile acceptance: one executable each for step / admit /
    gather / suffix-prefill serves every mix of prompt lengths, sampling
    settings, and prefix hit/miss — per-request state is runtime data
    (block tables ride the jitted step as arguments)."""
    eng = PagedContinuousBatchingEngine(params, CFG, num_slots=2, chunk=4, **geometry)
    try:
        system = _prompt(16, 5)
        eng.generate(system + _prompt(3, 0), 5)   # warm: miss path
        eng.generate(system + _prompt(7, 1), 5)   # warm: hit path
        counts0 = {k: tel.compile_count(k) for k in (
            "paged_step", "paged_admit", "paged_gather",
            "paged_suffix_prefill")}
        assert all(v >= 1 for v in counts0.values()), counts0
        hs = [
            eng.submit(_prompt(3, 11), 6),
            eng.submit(system + _prompt(4, 12), 7, temperature=0.7, seed=9),
            eng.submit(_prompt(19, 13), 4, eos_id=1),
            eng.submit(system + _prompt(9, 14), 5),
        ]
        for h in hs:
            h.result(timeout=120)
        counts1 = {k: tel.compile_count(k) for k in counts0}
        assert counts1 == counts0, (counts0, counts1)
    finally:
        eng.shutdown()


def test_eos_releases_pages_and_counts_waste(engine, params):
    """Mid-chunk EOS: the slot's pages free at the chunk boundary and the
    decoded-past-EOS overshoot lands in serving.wasted_tokens."""
    prompt = _prompt(5, 7)
    ref = _ref(params, prompt, 16)
    eos = ref[3]
    wasted0 = tel.counter("serving.wasted_tokens").value
    got = engine.generate(prompt, 16, eos_id=eos)
    assert got == ref[: ref.index(eos) + 1]
    assert tel.counter("serving.wasted_tokens").value >= wasted0
    st = engine.stats()
    assert st["slots_active"] == 0
    # nothing is live: every used page is a prefix retention, not a slot's
    assert st["kv_tokens_live"] == 0 and st["kv_pages_per_token"] == 0.0
    leaks = engine._alloc.check_leaks()
    assert leaks["leaked"] == [] and leaks["accounted"]


def test_stale_table_rows_cannot_corrupt_reused_pages(engine, params):
    """After a request finishes, its slot's table row points at the trash
    page — the next occupant of the SAME pages decodes exactly (a stale
    row would keep scattering into reused pages every chunk)."""
    outs = {}
    for i in range(6):  # cycle pages through slots repeatedly
        p = _prompt(10 + i, 50 + i)
        outs[i] = (p, engine.generate(p, 8))
    for i, (p, got) in outs.items():
        assert got == _ref(params, p, 8), f"round {i} diverged"
    assert np.all(engine._tables == TRASH_PAGE)


def test_pool_exhaustion_defers_then_completes(params):
    """A pool sized for ~one request at a time still completes a burst:
    admission defers on alloc failure and resumes as decode frees pages."""
    eng = PagedContinuousBatchingEngine(
        params, CFG, num_slots=2, chunk=4, num_pages=4, watermark_frac=0.0)
    try:
        hs = [eng.submit(_prompt(17, 70 + i), 12) for i in range(4)]
        outs = [h.result(timeout=120) for h in hs]
        assert [len(o) for o in outs] == [12] * 4
        assert eng.stats()["kv_alloc_deferred"] >= 1
    finally:
        eng.shutdown()


def test_engine_stats_and_gauges_have_kv_series(engine):
    engine.generate(_prompt(33, 90), 6)
    st = engine.stats()
    for k in ("kv_pages_total", "kv_pages_free", "kv_page_size",
              "kv_pages_in_use", "kv_pages_per_token", "kv_watermark_pages",
              "kv_prefix_nodes"):
        assert k in st, k
    names = {g[0] for g in engine.prom_gauges()}
    assert {"serving_kv_pages", "serving_kv_prefix_nodes"} <= names


# --- the admit program alone: donation, and the key it makes from the seed ---------


def _admit_operands(params, seed=3, temp=0.8, slot=0):
    """A pool, a prefilled row and the rest of ``_paged_admit_fn``'s operands
    for the fixture's geometry, as the engine's ``_stage_transfer`` passes them."""
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _prefill_fn

    pcfg = paged_kv.paged_config(CFG, page_size=16, num_pages=9)
    pool = paged_kv.paged_pool_init(params, pcfg, 2)
    ids = np.zeros((1, 32), np.int32)
    ids[0, :19] = _prompt(19, 5)
    row, first = _prefill_fn(CFG, 1, 32)(params, ids, np.int32(19))
    write_ids = np.array([3, 4, 0, 0], np.int32)
    carry = (jnp.asarray([7, 8], jnp.int32), jnp.asarray([30, 40], jnp.int32),
             jnp.asarray([[1, 2], [3, 4]], jnp.uint32))  # the decode step's (tok, lengths, keys), both rows another request's
    spans = np.array([[0, 2]], np.int32)  # the full group's (first, count): the prompt's two blocks
    return pcfg, (pool, row, write_ids, np.int32(slot), first, np.uint32(seed), np.float32(temp), carry, np.int32(19),
                  spans)


def test_paged_admit_donates_the_pool_where_the_backend_donates(params, monkeypatch):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm import generation

    pcfg, args = _admit_operands(params)
    plain = paged_kv._paged_admit_fn(pcfg)  # this backend's: the CPU's is not donated
    assert not any(a.donated for a in jax.tree_util.tree_leaves(plain.lower(*args).args_info))
    want_pool, want_tok, want_carry = plain(*args)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(args[0]))

    monkeypatch.setattr(generation, "_COMPILED", {})  # build again, as on the chip
    with monkeypatch.context() as built_for_the_chip:  # the build alone: the page moves' kernel is traced for this backend
        built_for_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        donating = paged_kv._paged_admit_fn(pcfg)
    assert donating is not plain
    lowered = donating.lower(*args)
    pool_info, *rest = lowered.args_info[0]
    assert all(a.donated for a in jax.tree_util.tree_leaves(pool_info))
    assert not any(a.donated for a in jax.tree_util.tree_leaves(rest))
    n_pool = len(jax.tree_util.tree_leaves(args[0]))
    header = lowered.compile().as_text().split("\n", 1)[0]
    if "input_output_alias" in header:  # a backend that aliases says so: every pool leaf, and nothing else
        assert header.count("-alias)") == n_pool, header
    got_pool, got_tok, got_carry = donating(*args)
    jax.tree_util.tree_map(np.testing.assert_array_equal, (got_pool, got_carry), (want_pool, want_carry))
    assert int(got_tok) == int(want_tok)
    assert not any(x.is_deleted() for x in args[7])  # the carry is small: taken and returned, not donated


@pytest.mark.parametrize("seed", [0, 42, 2**31 + 5, 2**32 - 1, 2**32 + 7, -1])
def test_admit_program_makes_the_requests_key_from_its_seed(params, seed):
    """``jax.random.PRNGKey(seed)`` is built inside the program from a uint32
    (the engine passes ``seed & 0xFFFFFFFF``): the key the slot decodes on and
    the first sampled token are what the eager key gave, and they are the
    slot's row of the carry the decode step takes next, beside the prompt's
    length; the other slot's row is as it came."""
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _sample

    slot = seed % 2
    pcfg, args = _admit_operands(params, seed=seed & 0xFFFFFFFF, slot=slot)
    _, tok0, (tok, lengths, keys) = paged_kv._paged_admit_fn(pcfg)(*args)
    want_key2, sub = jax.random.split(jax.random.PRNGKey(seed))
    assert np.array_equal(keys[slot], want_key2)
    assert int(tok0) == int(tok[slot]) == int(_sample(args[4][0], sub, args[6])) and int(lengths[slot]) == 19
    for got, came in zip((tok, lengths, keys), args[7]):
        assert got.dtype == came.dtype and np.array_equal(got[1 - slot], came[1 - slot])


# --- the page handoff alone: what the admit program moves, what the gather program brings back ------------


def _handoff_kinds():
    import dataclasses

    from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys
    from tests.test_hybrid_serving import HF as HYBRID
    from tests.test_latent_moe_serving import HF as LATENT
    from tests.test_window_serving import HF as WINDOW

    hf = lambda keys: config_from_hf_keys(keys, max_seq_len=128, dtype=jnp.float32, remat=False)  # noqa: E731
    # kind -> (config, page size): one group dense; two groups (window 8); state leaves beside pages; latent pages
    return {"dense": (dataclasses.replace(CFG, max_seq_len=128), 16), "window": (hf(WINDOW), 4),
            "hybrid": (hf(HYBRID), 16), "latent": (hf(LATENT), 16)}


class _Handoff:
    """A model kind's pool (random pages), its programs, and a prefilled row (a real prefill over 128 random
    tokens, so every position of the row holds something), as ``_stage_transfer`` / ``_stage_prefill`` see them."""

    B, SLOT = 3, 1

    def __init__(self, kind):
        from fedml_tpu.models.mamba import STATE_LEAVES, unpack_state
        from fedml_tpu.serving import paged_kv
        from fedml_tpu.train.llm.generation import _leaf_at, _leaf_name, _prefill_fn

        self.pk, self.leaf_at = paged_kv, _leaf_at
        cfg, self.ps = _handoff_kinds()[kind]
        self.cfg = cfg
        self.params = TransformerLM(cfg).init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"]
        self.window = cfg.sliding_window if cfg.window_layers else 0
        self.n_blocks = cfg.max_seq_len // self.ps
        self.pcfg = paged_kv.paged_config(cfg, page_size=self.ps, num_pages=4 * self.n_blocks + 1,
                                          **({"window_pages": 2 * self.n_blocks + 1} if self.window else {}))
        rng = np.random.default_rng(5)
        self.pool = jax.tree_util.tree_map(
            lambda x: x if x.ndim == 0 else jnp.asarray(rng.normal(size=x.shape), x.dtype),
            paged_kv.paged_pool_init(self.params, self.pcfg, self.B))
        self.stateful = cfg.has_recurrent_state
        self.ids = rng.integers(1, cfg.vocab_size, (1, cfg.max_seq_len)).astype(np.int32)
        self.prefill = lambda P, snap: _prefill_fn(cfg, 1, cfg.max_seq_len)(
            self.params, self.ids, np.int32(P), np.int32(snap) if self.stateful else None)[:2]
        self.unpack = lambda row: unpack_state(self.pcfg, row)
        # (path, is a window layer's) of every page leaf; the paths of the state leaves
        self.pages = [(p, bool(g)) for g, paths in enumerate(paged_kv._page_groups(self.pcfg, self.pool)) for p in paths]
        self.states = [p for p, _ in jax.tree_util.tree_flatten_with_path(self.pool)[0] if _leaf_name(p) in STATE_LEAVES]
        self.rng = rng

    def tables(self, P, n_shared):
        """Write tables and spans as ``_stage_transfer`` builds them, over distinct pages."""
        last = -(-P // self.ps)
        t = {"write": np.zeros((self.n_blocks,), np.int32), "spans": [(n_shared, last - n_shared)]}
        t["write"][n_shared:last] = self.rng.permutation(np.arange(1, 4 * self.n_blocks + 1))[:last - n_shared]
        if self.window:
            first_w = max(max(0, P - self.window + 1) // self.ps, n_shared)
            t["wwrite"] = np.zeros((self.n_blocks,), np.int32)
            t["wwrite"][first_w:last] = self.rng.permutation(np.arange(1, 2 * self.n_blocks + 1))[:last - first_w]
            t["spans"].append((first_w, last - first_w))
        return t

    def admit(self, pool, row, first, t, P):
        carry = (jnp.arange(self.B, dtype=jnp.int32), jnp.arange(self.B, dtype=jnp.int32) + 50,
                 jnp.ones((self.B, 2), jnp.uint32))
        more = (t["wwrite"],) if self.window else ()
        return self.pk._paged_admit_fn(self.pcfg)(
            pool, row, t["write"], np.int32(self.SLOT), first, np.uint32(9), np.float32(0.0), carry, np.int32(P),
            np.asarray(t["spans"], np.int32), *more)


@pytest.fixture(scope="module", params=["dense", "window", "hybrid", "latent"])
def handoff(request):
    return _Handoff(request.param)


# (prompt tokens as a share of the row, shared blocks in front): a prompt ending mid-page behind a shared
# prefix, one that fills max_seq_len, a short one behind nothing
@pytest.mark.parametrize("prompt,shared", [(0.43, 2), (1.0, 0), (0.11, 0)], ids=["mid_page", "whole_row", "short"])
def test_the_admit_program_moves_the_owned_blocks_and_nothing_else(handoff, prompt, shared):
    """After the admit program the pages the request owns hold the row's blocks bit for bit in every leaf of
    their group; every other page (shared prefix pages among them) is what it was; a recurrent layer's state
    lands whole at the slot and nowhere else; the carry's row is the request's."""
    h = handoff
    P = int(prompt * h.cfg.max_seq_len) if prompt < 1 else h.cfg.max_seq_len
    row, first = h.prefill(P, shared * h.ps)
    t = h.tables(P, shared)
    before = jax.tree_util.tree_map(np.asarray, h.pool)
    pool, tok0, (tok, lengths, _) = h.admit(h.pool, row, first, t, P)
    src = h.unpack(row)
    for path, win in h.pages:
        first_blk, count = t["spans"][int(win)]
        assert count > 0
        ids = t["wwrite" if win else "write"][first_blk:first_blk + count]
        got, old, r = np.asarray(h.leaf_at(pool, path)), h.leaf_at(before, path), np.asarray(h.leaf_at(src, path))[0]
        want = r[first_blk * h.ps:(first_blk + count) * h.ps].reshape((count, h.ps) + r.shape[1:])
        np.testing.assert_array_equal(got[ids], want)
        rest = np.setdiff1d(np.arange(got.shape[0]), np.append(ids, TRASH_PAGE))
        np.testing.assert_array_equal(got[rest], old[rest])
    for path in h.states:
        got, old, r = np.asarray(h.leaf_at(pool, path)), h.leaf_at(before, path), np.asarray(h.leaf_at(src, path))
        np.testing.assert_array_equal(got[h.SLOT], r[0])
        np.testing.assert_array_equal(np.delete(got, h.SLOT, 0), np.delete(old, h.SLOT, 0))
    assert int(tok[h.SLOT]) == int(tok0) == int(np.argmax(first[0])) and int(lengths[h.SLOT]) == P
    assert [int(v) for v in np.delete(np.asarray(lengths), h.SLOT)] == [50, 52]


def test_a_range_of_no_blocks_writes_no_page(handoff):
    """``count`` 0 in every group: not one page of the pool changes, the trash page included, wherever
    ``first`` points and whatever the tables hold; a range that runs past the row is cut at its end."""
    h = handoff
    row, first = h.prefill(40, 0)
    t = h.tables(40, 0)
    t["spans"] = [(first_blk, 0) for first_blk, _ in t["spans"]]
    before = jax.tree_util.tree_map(np.asarray, h.pool)
    pool, _, _ = h.admit(h.pool, row, first, t, 40)
    for path, _ in h.pages:
        np.testing.assert_array_equal(np.asarray(h.leaf_at(pool, path)), h.leaf_at(before, path))
    t = h.tables(h.cfg.max_seq_len, 0)
    last = h.n_blocks - 1
    t["spans"] = [(last, 5)] * len(t["spans"])   # only block ``last`` exists behind ``last``
    pool, _, _ = h.admit(h.pool, row, first, t, 40)
    src = h.unpack(row)
    for path, win in h.pages:
        got, old = np.asarray(h.leaf_at(pool, path)), h.leaf_at(before, path)
        page = t["wwrite" if win else "write"][last]
        np.testing.assert_array_equal(got[page], np.asarray(h.leaf_at(src, path))[0, last * h.ps:])
        rest = np.setdiff1d(np.arange(got.shape[0]), [page])
        np.testing.assert_array_equal(got[rest], old[rest])


def test_the_gather_program_brings_the_shared_blocks_and_nothing_behind_them_is_read(handoff):
    """Gather behind ``k`` shared blocks: the row holds those pages at their positions (a window layer's:
    the ones a pass behind the prefix can see), its write index at the prefix; and a suffix pass over it
    gives, bit for bit, the logits it gives over a row that holds the shared blocks ALONE (zeros wherever the
    whole-table gather left the trash page's contents): nothing behind the prefix, and in a window layer
    nothing behind the horizon, reaches a query."""
    from fedml_tpu.models.mamba import PACKED

    h = handoff
    k = 3
    P = k * h.ps + 1   # the registering request: its window (8, over pages of 4) reaches over what a later pass sees
    row, first = h.prefill(P, k * h.ps)
    t = h.tables(P, 0)
    pool, _, _ = h.admit(h.pool, row, first, t, P)
    tail = max(0, k * h.ps - h.window + 1) // h.ps if h.window else 0
    table, wtable = np.zeros((h.n_blocks,), np.int32), np.zeros((h.n_blocks,), np.int32)
    table[:k] = t["write"][:k]
    if h.window:
        assert t["spans"][1][0] <= tail < k - 1
        wtable[tail:k] = t["wwrite"][tail:k]
    state = h.pk.snapshot_of(row) if h.stateful else None
    got = h.pk._paged_gather_fn(h.pcfg)(pool, table, np.int32(k * h.ps), state, *((wtable,) if h.window else ()))
    alone = jax.tree_util.tree_map(lambda x: x, got)
    for path, win in h.pages:
        leaf, tab, lo = np.asarray(h.leaf_at(pool, path)), (wtable if win else table), (tail if win else 0)
        shared = leaf[tab[lo:k]].reshape((-1,) + leaf.shape[2:])
        np.testing.assert_array_equal(np.asarray(h.leaf_at(got, path))[0, lo * h.ps:k * h.ps], shared)
        only = np.zeros((1, h.cfg.max_seq_len) + leaf.shape[2:], leaf.dtype)
        only[0, lo * h.ps:k * h.ps] = shared
        node = alone
        for key in path[:-1]:
            node = node[key.key]
        node[path[-1].key] = jnp.asarray(only)
    idx = [x for p, x in jax.tree_util.tree_flatten_with_path(got)[0] if getattr(p[-1], "key", None) == "idx"]
    assert idx and all(int(x) == k * h.ps for x in idx)
    assert (PACKED in got) == h.stateful
    suffix = np.zeros((1, 16), np.int32)
    suffix[0, :7] = h.rng.integers(1, h.cfg.vocab_size, 7)
    total = k * h.ps + 7
    snap = np.int32(0) if h.stateful else None
    run = h.pk._suffix_prefill_fn(h.pcfg, 16)
    a = run(h.params, got, suffix, np.int32(k * h.ps), np.int32(total), snap)[1]
    b = run(h.params, alone, suffix, np.int32(k * h.ps), np.int32(total), snap)[1]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("tail,dtype", [((1, 128), jnp.bfloat16), ((4, 128), jnp.bfloat16), ((640,), jnp.bfloat16),
                                        ((2, 8), jnp.float32)], ids=["one_kv_head", "four_kv_heads", "latent", "f32"])
@pytest.mark.parametrize("span", [(0, 8), (3, 2), (5, 0)], ids=["whole_row", "inside", "nothing"])
def test_the_page_moves_kernel_and_its_plain_formulation_agree(tail, dtype, span):
    """``ops/page_handoff.rows_to_pages`` (interpreted here) against the loop of ``dynamic_update_slice`` it
    falls back to where a page is not whole tiles, and both against NumPy: the span's blocks land on their
    pages in every pool, nothing else changes; and which shapes the compiled kernel takes."""
    from fedml_tpu.ops import page_handoff as ph

    ps, n_blocks, pages = 16, 8, 20
    rng = np.random.default_rng(3)
    rows = [jnp.asarray(rng.normal(size=(1, ps * n_blocks) + tail), dtype) for _ in range(3)]
    pools = [jnp.asarray(rng.normal(size=(pages, ps) + tail), dtype) for _ in range(3)]
    ids = rng.permutation(np.arange(1, pages))[:n_blocks].astype(np.int32)
    got = ph.rows_to_pages(rows, pools, ids, np.asarray(span, np.int32), page_size=ps)
    width, page_rows = tail[-1], ps * int(np.prod(tail[:-1]))
    plain = ph._plain([r.reshape(-1, width) for r in rows], [p.reshape(pages, page_rows, width) for p in pools],
                      ids, jnp.asarray(span, jnp.int32), [page_rows] * 3)
    for row, pool, a, b in zip(rows, pools, got, plain):
        want = np.array(pool)
        for blk in range(span[0], span[0] + span[1]):
            want[ids[blk]] = np.asarray(row)[0, blk * ps:(blk + 1) * ps]
        np.testing.assert_array_equal(np.asarray(a), want)
        np.testing.assert_array_equal(np.asarray(b).reshape(want.shape), want)
    assert ph.tiles(page_rows, width, dtype) == (tail != (2, 8))   # 8 lanes wide: the plain formulation on the chip
    assert not ph.tiles(8, 128, jnp.bfloat16) and ph.tiles(8, 128, jnp.float32)
    with pytest.raises(ValueError):
        ph.rows_to_pages(rows, [p[:, :8] for p in pools], ids, np.asarray(span, np.int32), page_size=ps)

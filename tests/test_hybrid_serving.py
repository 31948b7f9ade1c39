"""A model with recurrent layers through the paged serving engine, on the CPU
at small sizes: two kinds of state in one cache manager (K/V pages and per-slot
recurrent state), prefix hits that start from a state snapshot, and the
allocator's bookkeeping of snapshots. The yardstick is the benchmark's plain
reference (``benchmark/reference_jamba.py``): one full forward over the prompt
and the served tokens, no cache."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.serving.paged_kv import PagedKVAllocator
from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys
from tests._engine_gate import hold

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (REPO, os.path.join(REPO, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_jamba  # noqa: E402
import weights_jamba  # noqa: E402

HF = {"attn_layer_offset": 1, "attn_layer_period": 4, "hidden_size": 64, "intermediate_size": 128,
      "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2, "num_attention_heads": 4,
      "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 4, "num_key_value_heads": 1,
      "rms_norm_eps": 1e-06, "tie_word_embeddings": True, "vocab_size": 101}
CFG = config_from_hf_keys(HF, max_seq_len=128, dtype=jnp.float32, remat=False)
PS = 16
# float32 program, float32 reference: a served token may lie below the reference's best only by the
# rounding of two orders of float32 sums (measured under 1e-5 on logits of size 1-10)
GAP_TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    shapes = jax.eval_shape(lambda k: TransformerLM(CFG).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    return weights_jamba.make_params(weights_jamba.shapes_of(shapes), 11, jnp.float32)


def _engine(params, **kw):
    opts = dict(num_slots=4, chunk=4, page_size=PS, num_pages=48, state_snapshots=4)
    opts.update(kw)
    return PagedContinuousBatchingEngine(params, CFG, **opts)


def _toks(n, seed):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], n).tolist()


def _gap(params, prompt, served):
    """The most by which a served token's reference logit lies below the
    reference's best, over every served position of one request."""
    seq = np.asarray(prompt + served[:-1], np.int32)
    rows = len(prompt) - 1 + np.arange(len(served))
    lg = np.asarray(reference_jamba.logits_at(params, jnp.asarray(seq), jnp.asarray(rows), reference_jamba.norm_cfg(HF)))
    return float((lg.max(axis=-1) - lg[np.arange(len(served)), served]).max())


def test_a_batch_of_lengths_equals_the_references_full_forward(params):
    """Prefill (padded to 16-token buckets, so padded positions must leave the
    state alone) then decode of five requests in one batch over four slots:
    requests finish at different chunks, so freed slots (cache_idx -1) and a
    reused slot sit beside live ones."""
    eng = _engine(params)
    try:
        prompts = [_toks(n, n) for n in (5, 17, 33, 48, 21)]
        new = [9, 4, 13, 6, 7]
        handles = [eng.submit(p, n) for p, n in zip(prompts, new)]
        served = [h.result(timeout=300) for h in handles]
        assert [len(s) for s in served] == new
        for p, s in zip(prompts, served):
            assert _gap(params, p, s) < GAP_TOL
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["state_leaked"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


def test_three_requests_sharing_two_pages_unprimed_the_third_is_a_state_hit(params):
    """No priming: the first request finds nothing; the second finds the two
    shared pages and NO snapshot, is prefilled whole and leaves the state at
    the divergence; the third starts from that state. Served from a snapshot
    = served from nothing = the reference."""
    system = _toks(2 * PS, 3)
    reqs = [system + _toks(n, 40 + n) for n in (7, 19, 12)]
    tel.reset()
    eng = _engine(params)
    try:
        seen = []
        for r in reqs:
            out = eng.generate(r, 8)
            st = eng.stats()
            seen.append((st["state_prefix_hits"], st["state_prefix_misses"], st["state_snapshots"],
                         st["kv_prefix_hits"], st["kv_prefix_misses"]))
            assert _gap(params, r, out) < GAP_TOL
        assert seen == [(0, 1, 0, 0, 1), (0, 2, 1, 1, 1), (1, 2, 1, 2, 1)]
        # the same prompt again: a state hit at the system prompt; its own third page, matched
        # without a snapshot, gets one (a repeated prompt is a prefix too)
        from_snapshot = eng.generate(reqs[1], 8)
        assert eng.stats()["state_prefix_hits"] == 2
        assert eng.stats()["state_snapshot_bytes"] == 2 * eng._state_bytes > 0
        snap = tel.snapshot()
        spans = [s for s in snap["spans"] if s["name"] == "serving.state.snapshot"]
        assert [s["attrs"]["position"] for s in spans] == [2 * PS, 3 * PS]
        assert all(s["attrs"]["bytes"] == eng._state_bytes for s in spans)
        hits = [s["attrs"]["state_hit"] for s in snap["spans"] if s["name"] == "serving.cb.prefill"]
        assert hits == [False, False, True, True]
        assert all(s["attrs"]["state_slots"] == s["attrs"]["slots"]
                   for s in snap["spans"] if s["name"] == "serving.cb.chunk")
        for name, want in (("serving.state.prefix_hits", 2), ("serving.state.prefix_misses", 2),
                           ("serving.state.snapshots", 2)):
            assert snap["counters"][name] == want, name
    finally:
        eng.shutdown()
    cold = _engine(params, state_snapshots=0)  # a budget of nothing: every admission starts from zero
    try:
        assert cold.generate(reqs[1], 8) == from_snapshot
        cold.generate(reqs[2], 8)
        assert cold.stats()["state_prefix_hits"] == 0 and cold.stats()["state_snapshots"] == 0
    finally:
        cold.shutdown()


def test_one_wave_of_a_snapshot_hit_an_unshared_and_a_snapshot_leaving_rider(params):
    """Three riders launched back to back on the worker's thread, the pool
    rebound by each one's transfer before the next one's gather reads it: the
    rider that starts from a snapshot, the one that shares nothing and the one
    that finds pages without a snapshot (and leaves one) are each served
    ``generate()``'s tokens, which are the reference's."""
    import threading

    from fedml_tpu.train.llm.generation import generate

    sys_a, sys_b = _toks(2 * PS, 3), _toks(PS, 4)
    eng = _engine(params)
    try:
        eng.generate(sys_a + _toks(7, 47), 4)   # registers sys_a's pages
        eng.generate(sys_a + _toks(19, 59), 4)  # finds them without a snapshot, leaves one
        eng.generate(sys_b + _toks(5, 61), 4)   # registers sys_b's page: no snapshot there yet
        riders = [sys_a + _toks(12, 52), _toks(23, 7), sys_b + _toks(9, 63)]
        tel.reset()
        gate, inner = threading.Event(), eng._admit_all

        def held():  # the worker waits at the door until all three are queued: one wave
            assert gate.wait(timeout=60)
            inner()

        eng._admit_all = held
        handles = [eng.submit(r, 8) for r in riders]
        eng._admit_all = inner
        gate.set()
        served = [h.result(timeout=300) for h in handles]
        snap = tel.snapshot()
        assert [s["attrs"]["n"] for s in snap["spans"] if s["name"] == "serving.paged.admit_wave"] == [3]
        prefills = [s["attrs"] for s in snap["spans"] if s["name"] == "serving.cb.prefill"]
        assert [(a["state_hit"], a["shared"]) for a in prefills] == [(True, 2 * PS), (False, 0), (False, 0)]
        (wave,) = [s for s in snap["spans"] if s["name"] == "serving.paged.admit_wave"]
        launches = [s for s in snap["spans"] if s["name"] == "serving.paged.transfer" and s["parent_seq"] == wave["seq"]]
        assert len(launches) == 3  # the second and third launched behind the first's unfetched token
        assert snap["counters"]["serving.state.snapshots"] == 1  # the third rider's, at sys_b's boundary
        for r, out in zip(riders, served):
            assert out == [int(t) for t in generate(params, CFG, jnp.asarray([r], jnp.int32), 8)[0]]
            assert _gap(params, r, out) < GAP_TOL
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["state_leaked"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_riders_written_by_slot_while_a_chunk_is_in_flight_are_served_generates_tokens(params, temperature):
    """The loop runs one chunk ahead: a rider's recurrent state is written at
    its slot, and its row of the carry on the device, while the chunk before
    is still unfetched; it rides the next chunk beside the row that was live."""
    from fedml_tpu.train.llm.generation import generate

    kw = [({"temperature": temperature, "seed": 70 + i} if temperature else {}) for i in range(4)]
    reqs = [(_toks(21, 70), 22, kw[0]), (_toks(5, 71), 9, kw[1]), (_toks(33, 72), 1, kw[2]), (_toks(17, 73), 6, kw[3])]
    eng = _engine(params, num_slots=3)
    try:
        reached, release = hold(eng, "_land_chunk")  # chunk 2 launched, chunk 1 not yet fetched
        handles = [eng.submit(reqs[0][0], reqs[0][1], **reqs[0][2])]
        assert reached.wait(timeout=60)
        assert eng._inflight is not None
        handles += [eng.submit(p, n, **k) for p, n, k in reqs[1:]]
        release.set()
        for (p, n, k), h in zip(reqs, handles):
            want = generate(params, CFG, jnp.asarray([p], jnp.int32), n, temperature=k.get("temperature", 0.0),
                            key=jax.random.PRNGKey(k.get("seed", 0)))
            out = h.result(timeout=300)
            assert out == [int(t) for t in want[0]]
            if not temperature:
                assert _gap(params, p, out) < GAP_TOL
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["state_leaked"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


def test_a_row_past_its_eos_updates_the_slots_state_and_the_next_rider_overwrites_it(params):
    """An EOS is seen a chunk late: the chunk already queued still advances the
    ended request's state at its slot. The slot's next rider is admitted behind
    that chunk (program order) and writes the slot's state whole."""
    eng = _engine(params, num_slots=1)
    try:
        first, second = _toks(30, 1), _toks(9, 2)
        ref = eng.generate(first, 12)
        eos = ref[2]
        a = eng.submit(first, 12, eos_id=eos)
        b = eng.submit(second, 10)
        assert a.result(timeout=300) == ref[:ref.index(eos) + 1]
        assert _gap(params, second, b.result(timeout=300)) < GAP_TOL
        leaks = eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["state_leaked"] == [] and leaks["accounted"]
    finally:
        eng.shutdown()


def test_a_slot_reused_after_release_starts_clean(params):
    eng = _engine(params, num_slots=1)
    try:
        first = eng.generate(_toks(30, 1), 10)
        second = eng.generate(_toks(9, 2), 10)   # the one slot again, a shorter prompt over the old state
        assert _gap(params, _toks(30, 1), first) < GAP_TOL and _gap(params, _toks(9, 2), second) < GAP_TOL
    finally:
        eng.shutdown()


def test_eviction_under_a_one_snapshot_budget(params):
    tel.reset()
    eng = _engine(params, state_snapshots=1)
    try:
        sys_a, sys_b = _toks(PS, 5), _toks(PS, 6)
        for system in (sys_a, sys_b, sys_a):
            # the second request of a system prompt leaves its snapshot and the third uses it; back at
            # sys_a its node has pages and no snapshot any more, so the first request restores it at once
            for tail in (3, 8, 5):
                r = system + _toks(tail, tail + len(system) + system[0])
                assert _gap(params, r, eng.generate(r, 5)) < GAP_TOL
        st = eng.stats()
        assert st["state_snapshots"] == 1 and st["state_snapshot_bytes"] == eng._state_bytes
        assert st["state_snapshot_evictions"] == 2 and st["state_prefix_hits"] == 4
        assert tel.snapshot()["counters"]["serving.state.snapshot_evictions"] == 2
        assert eng._alloc.check_leaks()["state_leaked"] == []
        assert ("serving_state_snapshot_bytes", None, float(eng._state_bytes)) in eng.prom_gauges()
    finally:
        eng.shutdown()


def test_zero_compiles_after_warm_up(params):
    labels = ("prefill", "paged_step", "paged_admit", "paged_gather", "paged_suffix_prefill")
    eng = _engine(params)
    try:
        system = _toks(PS, 8)
        for tail in (3, 9, 20):           # miss, snapshot-leaving miss (bucket 32), hit (suffix bucket 32)
            eng.generate(system + _toks(tail, tail), 5)
        eng.generate(system + _toks(4, 77), 5)   # hit, suffix bucket 16
        eng.generate(_toks(5, 1), 5)             # no system prompt: full prefill, bucket 16
        before = {k: tel.compile_count(k) for k in labels}
        handles = [eng.submit(system + _toks(n, 100 + n), 6, temperature=t, seed=n)
                   for n, t in ((2, 0.0), (13, 0.7), (25, 0.0), (6, 0.0), (30, 0.0))]
        handles.append(eng.submit(_toks(11, 9), 6))
        for h in handles:
            h.result(timeout=300)
        assert {k: tel.compile_count(k) for k in labels} == before
    finally:
        eng.shutdown()


def test_generate_carries_the_state_too(params):
    """The plain generate() loop, the engine's reference, runs the same
    mixer: per-row state, one token a step."""
    from fedml_tpu.train.llm.generation import generate

    prompt = _toks(21, 4)
    out = [int(t) for t in generate(params, CFG, jnp.asarray([prompt], jnp.int32), 7)[0]]
    assert _gap(params, prompt, out) < GAP_TOL


# ---- the allocator's bookkeeping of snapshots, no device --------------------------------------------

def _alloc_with(budget):
    a = PagedKVAllocator(32, 4, state_budget_bytes=budget)
    toks = list(range(100, 112))            # three full chunks
    pages = a.alloc(3)
    a.register_prefix(toks, pages)
    a.free(pages)
    return a, toks, pages


def test_match_is_cut_to_the_deepest_snapshot_and_names_where_one_is_wanted():
    a, toks, pages = _alloc_with(100)
    m = a.match(toks + [1, 2], need_state=True)
    assert m.pages == [] and m.state is None and m.snap_blocks == 3      # pages matched, no state: recompute, snapshot at 3
    assert a.attach_state(toks, 2, "S2", 60) and not a.attach_state(toks, 2, "again", 60)
    m = a.match(toks + [1, 2], need_state=True)
    assert m.pages == pages[:2] and m.state == "S2" and m.snap_blocks == 3
    a.free(m.pages)
    m = a.match(toks + [1, 2], max_blocks=2, need_state=True)
    assert m.pages == pages[:2] and m.snap_blocks == 0                    # nothing deeper is wanted
    a.free(m.pages)
    assert a.match(toks, need_state=False).pages == pages                 # a dense model's match is the old one
    a.free(pages)
    st = a.stats()
    assert (st["state_prefix_hits"], st["state_prefix_misses"], st["kv_prefix_hits"]) == (2, 1, 4)
    assert a.check_leaks() == {"leaked": [], "bad_free": [], "state_leaked": [], "accounted": True}


def test_snapshots_are_budgeted_in_bytes_evicted_lru_and_go_with_their_nodes():
    a, toks, pages = _alloc_with(100)
    assert not a.attach_state(toks, 1, "big", 101)            # one snapshot over the whole budget: refused
    assert not a.attach_state([9, 9, 9, 9], 1, "x", 10)       # no such node
    assert a.attach_state(toks, 1, "S1", 60)
    a.free(a.match(toks[:8] + [0], need_state=True).pages)    # S1 used: the younger of the two below
    assert a.attach_state(toks, 3, "S3", 40) and a.stats()["state_snapshot_bytes"] == 100
    assert a.attach_state(toks, 2, "S2", 60)                  # 160 > 100: LRU first, S1 (tick older than S3's)
    st = a.stats()
    assert st["state_snapshots"] == 2 and st["state_snapshot_bytes"] == 100 and st["state_snapshot_evictions"] == 1
    assert a.match(toks[:4] + [0], need_state=True).state is None
    a._evict_locked(1)                                        # page pressure takes the leaf node, S3 with it
    st = a.stats()
    assert st["state_snapshots"] == 1 and st["state_snapshot_bytes"] == 60 and st["state_snapshot_evictions"] == 2
    assert a.check_leaks()["state_leaked"] == []
    a._state_bytes += 1                                       # a planted miscount is a leak
    assert a.check_leaks()["state_leaked"] and not a.check_leaks()["accounted"]

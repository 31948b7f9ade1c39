"""Continuous batching (serving/continuous_batching.py): the engine against
the reference ``generate()`` path: join/leave at token boundaries, EOS,
the budget clamp at ``max_seq_len``, fail-fast rejects, the HTTP runner in
front of it, the request's span record, and the loop's schedule: one decode
chunk ahead of the host, riders launched before anything is waited for.
(Greedy exactness on ragged lengths and compile-once are
tests/test_paged_kv.py's, at this file's geometry too.)"""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.train.llm.generation import generate
from tests._engine_gate import hold

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
    # pages of 8: the compiled programs' cache keys are this file's own (see served_requests)
    eng = PagedContinuousBatchingEngine(params, CFG, num_slots=2, chunk=4, page_size=8)
    yield eng
    eng.shutdown()


def _prompt(length, seed):
    return list(np.random.default_rng(seed).integers(1, CFG.vocab_size, length))


def test_engine_join_leave_more_requests_than_slots(engine):
    """6 requests through 2 slots: admission happens at token boundaries
    (freed slots re-admit from the FIFO), every future completes and every
    page comes back."""
    handles = [engine.submit(_prompt(4 + i, 100 + i), 6 + i) for i in range(6)]
    outs = [h.result(timeout=120) for h in handles]
    assert [len(o) for o in outs] == [6 + i for i in range(6)]
    st = engine.stats()
    assert st["requests_done"] == 6
    assert st["slots_active"] == 0 and st["queue_depth"] == 0
    assert st["tokens_out"] == sum(len(o) for o in outs)
    assert st["kv_tokens_live"] == 0 and engine._alloc.check_leaks()["accounted"]


def test_engine_eos_truncates_like_generate(engine, params):
    """Engine output stops AT the first EOS token (inclusive), matching the
    reference stream up to that point; generate() instead fills the tail
    (static shapes), so compare the truncated prefix."""
    prompt = _prompt(5, 7)
    ref = np.asarray(
        generate(params, CFG, jnp.asarray([prompt], jnp.int32), 16)
    )[0].tolist()
    eos = ref[3]  # guaranteed to appear mid-stream
    got = engine.generate(prompt, 16, eos_id=eos)
    cut = ref.index(eos)
    assert got == ref[: cut + 1]
    # multi-EOS: any id in the tuple stops the stream
    got2 = engine.generate(prompt, 16, eos_id=(eos, CFG.vocab_size - 1))
    assert got2[-1] in (eos, CFG.vocab_size - 1)


def test_engine_sampled_same_seed_deterministic(engine):
    prompt = _prompt(6, 11)
    a = engine.generate(prompt, 10, temperature=0.8, seed=42)
    b = engine.generate(prompt, 10, temperature=0.8, seed=42)
    c = engine.generate(prompt, 10, temperature=0.8, seed=43)
    assert a == b
    assert len(c) == 10  # different seed still a full stream


def test_engine_rejects_bad_requests_fast(engine):
    with pytest.raises(ValueError, match="at least one token"):
        engine.generate([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.generate([1, 2], 0)
    with pytest.raises(ValueError, match="no decode room"):
        engine.generate(list(range(1, CFG.max_seq_len + 1)), 4)


def test_engine_budget_clamped_to_cache_capacity(engine, params):
    """A near-capacity prompt gets its stream clamped to the room
    ``max_seq_len`` leaves instead of scattering out of bounds (or
    erroring), and the clamped stream is still the reference's."""
    prompt = _prompt(CFG.max_seq_len - 3, 21)
    out = engine.generate(prompt, 50)
    assert len(out) == 3  # S - P
    want = generate(params, CFG, jnp.asarray([prompt], jnp.int32), 3)
    assert out == np.asarray(want)[0].tolist()


def test_engine_queue_cap_and_shutdown_fail_fast(params):
    eng = PagedContinuousBatchingEngine(params, CFG, num_slots=1, chunk=2,
                                        page_size=8, max_queue=0)
    h = eng.submit([1, 2, 3], 4)
    with pytest.raises(RuntimeError, match="queue_full"):
        h.result(timeout=5)
    eng.shutdown()
    h2 = eng.submit([1, 2, 3], 4)
    with pytest.raises(RuntimeError, match="shutting down"):
        h2.result(timeout=5)


def test_runner_serves_engine_and_exports_gauges(params):
    """The HTTP runner routes through the engine, /metrics exports the slot/queue gauges the autoscaler and load bench
    read, and /statusz carries the stats() snapshot."""
    from fedml_tpu.serving.fedml_inference_runner import FedMLInferenceRunner
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    class _Tok:  # minimal encode/decode for the predictor contract
        special_tokens = {}

        def encode(self, s):
            return [1 + (ord(c) % (CFG.vocab_size - 1)) for c in s] or [1]

        def decode(self, ids):
            return " ".join(str(i) for i in ids)

    pred = LLMPredictor(params, CFG, _Tok(), default_max_new_tokens=4,
                        paged=True, num_slots=2, decode_chunk=2, page_size=8)
    assert pred.engine is not None
    runner = FedMLInferenceRunner(pred, port=0)
    port = runner.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"prompt": "hi there", "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert isinstance(out.get("text"), str) and out["text"]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            metrics = r.read().decode()
        for g in ("serving_cb_slots_total", "serving_cb_slot_occupancy",
                  "serving_cb_queue_depth", "serving_kv_pages"):
            assert f"fedml_{g}" in metrics, g
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/statusz", timeout=10
        ) as r:
            doc = json.loads(r.read())
        cb = doc["continuous_batching"]
        assert cb["slots_total"] == 2 and cb["requests_done"] >= 1
    finally:
        runner.stop()


# -- the request record: one id, spans from the gateway to the last token -----


class _CharTok:  # one token a character: the prompt's length is its encoding's
    special_tokens = {}

    def encode(self, s):
        return [1 + (ord(c) % (CFG.vocab_size - 1)) for c in s] or [1]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


REQUEST_SPANS = (
    "serving.endpoint.predict", "serving.http.request", "serving.predict.encode",
    "serving.predict.wait", "serving.predict.decode_text", "serving.request.queue",
    "serving.request.admit", "serving.request.decode", "serving.cb.prefill",
    "serving.paged.transfer", "serving.paged.first_token_wait", "serving.paged.admit")

#: the per-request stages of an admission wave, in the order a rider passes them
LAUNCH_STAGES = ("serving.cb.prefill", "serving.paged.transfer")  # under the wave's span, waited for by nothing
LANDING_STAGES = ("serving.paged.first_token_wait", "serving.paged.admit")  # behind the launch of the chunk that carries the rider
WAVE_STAGES = LAUNCH_STAGES + LANDING_STAGES
#: what the worker adds to ``serving.engine.iteration`` at its end
ITERATION_ACCOUNT = ("cpu_ns", "blocked_ns", "lock_wait_ns", "starved_ns")


CALLER_ID = "cd" * 16


@pytest.fixture(scope="module")
def served_requests(params):
    """Six requests (three share a 32-token prefix) through Endpoint.predict ->
    HTTP replica -> LLMPredictor -> a tiny paged engine; the spans they left."""
    from fedml_tpu.serving.endpoint import Endpoint
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    # chunks of 16 steps: the spans' own cost stays small beside the work they tile.
    # Pages of 8: the compiled programs' cache keys are this file's own (test_paged_kv.py
    # counts the traces of the page-16 programs of the same CFG in whatever process it shares)
    pred = LLMPredictor(params, CFG, _CharTok(), default_max_new_tokens=4,
                        paged=True, num_slots=2, decode_chunk=16, page_size=8)
    ep = Endpoint("req_record", lambda: pred)
    registry = tel.get_telemetry()
    was = registry.enabled
    registry.set_enabled(True)  # whatever an earlier file of this worker left it at
    seq0 = registry.snapshot()["spans"][-1:]
    seq0 = seq0[0]["seq"] if seq0 else 0
    try:
        system = "s" * 32
        prompts = [system + "abc", "cold prompt one", system + "defgh", "x" * 20,
                   system + "ij", "another cold prompt"]
        replies = [None] * len(prompts)

        def send(i):
            replies[i] = ep.predict({"prompt": prompts[i], "max_new_tokens": 5 + i})

        import threading
        from fedml_tpu.core.telemetry import trace_context
        with trace_context.activated(trace_context.TraceContext(CALLER_ID)):
            send(0)  # registers the shared prefix pages; its caller already opened a context
        threads = [threading.Thread(target=send, args=(i,)) for i in range(1, len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        ep.shutdown()
        registry.set_enabled(was)
    spans = [s for s in tel.snapshot()["spans"] if s["seq"] > seq0]
    return replies, spans


def test_every_span_of_a_request_carries_one_request_id(served_requests):
    replies, spans = served_requests
    ids = [r["timing"]["request_id"] for r in replies]
    assert len(set(ids)) == len(ids) and all(len(i) == 32 for i in ids)
    for rid in ids:
        mine = [s for s in spans if (s.get("attrs") or {}).get("request_id") == rid]
        names = {s["name"] for s in mine}
        assert set(REQUEST_SPANS) <= names, names
    # a span that belongs to a request names it: none of these families is anonymous
    for s in spans:
        if s["name"] in REQUEST_SPANS:
            assert (s.get("attrs") or {}).get("request_id") in ids, s
    # the spans of the handler thread carry the id as their trace context too
    http = [s for s in spans if s["name"] == "serving.http.request"]
    assert all(s["trace_id"] == s["attrs"]["request_id"] for s in http)


def test_endpoint_keeps_the_id_of_a_caller_that_opened_a_context(served_requests):
    replies, spans = served_requests
    assert replies[0]["timing"]["request_id"] == CALLER_ID  # not a fresh one minted over it
    mine = {s["name"] for s in spans if (s.get("attrs") or {}).get("request_id") == CALLER_ID}
    assert {"serving.endpoint.predict", "serving.http.request", "serving.request.queue"} <= mine


def test_queue_plus_admit_is_ttft_and_the_reply_says_the_same(served_requests):
    replies, spans = served_requests
    for reply in replies:
        timing = reply["timing"]
        mine = {s["name"]: s for s in spans
                if (s.get("attrs") or {}).get("request_id") == timing["request_id"]}
        queue, admit = mine["serving.request.queue"], mine["serving.request.admit"]
        assert queue["t0_ns"] + queue["dur_ns"] == admit["t0_ns"]  # popped: one reading
        assert abs((queue["dur_ns"] + admit["dur_ns"]) / 1e9 - timing["ttft_s"]) < 1e-3
        assert abs(queue["dur_ns"] / 1e9 - timing["queue_wait_s"]) < 1e-3
        decode = mine["serving.request.decode"]
        assert decode["t0_ns"] == admit["t0_ns"] + admit["dur_ns"]  # first token: one reading
        n = decode["attrs"]["tokens"]
        assert n == len(reply["token_ids"]) and decode["attrs"]["wasted"] >= 0
        assert abs(decode["dur_ns"] / 1e9 / (n - 1) - timing["tpot_s"]) < 1e-3
        # the entry's spans nest: gateway > handler > wait > (queue + admit + decode)
        assert mine["serving.endpoint.predict"]["dur_ns"] >= mine["serving.http.request"]["dur_ns"]
        assert mine["serving.http.request"]["dur_ns"] >= mine["serving.predict.wait"]["dur_ns"]
    shared = [s["attrs"]["shared"] for s in spans if s["name"] == "serving.request.admit"]
    assert sorted(shared) == [0, 0, 0, 0, 32, 32]  # the first sharer fills the prefix cache


def test_worker_loop_spans_tile_an_iteration(served_requests):
    _, spans = served_requests
    iters = [s for s in spans if s["name"] == "serving.engine.iteration"]
    assert iters and all(set(s["attrs"]) == {"slots", "queue_depth", *ITERATION_ACCOUNT} for s in iters)
    for it in iters:  # the worker's own account of the pass: parts of its wall time, each
        a = it["attrs"]
        assert all(a[k] >= 0 for k in ITERATION_ACCOUNT)
        assert a["cpu_ns"] + a["blocked_ns"] <= it["dur_ns"] and a["lock_wait_ns"] <= it["dur_ns"]
        assert a["starved_ns"] <= it["dur_ns"]
    worker = {s["tid"] for s in iters}
    assert len(worker) == 1
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent_seq"], []).append(s)
    covered = 0
    landing = ["serving.cb.chunk.sync", "serving.cb.chunk.post"]  # of the chunk launched before
    for it in iters:
        kids = by_parent.get(it["seq"], [])
        # a wave's launches, the chunk (its launch, then the landing of the one before), or the
        # last chunk's landing where nothing was left to launch, then the riders' first tokens
        assert {k["name"] for k in kids} <= {"serving.engine.collect_wave", "serving.paged.admit_wave",
                                             "serving.cb.chunk", *landing, *LANDING_STAGES}
        assert kids[0]["name"] == "serving.engine.collect_wave"  # the loop's top has a name
        assert all(k["tid"] == it["tid"] for k in kids)
        assert not ({"serving.cb.chunk", *landing} <= {k["name"] for k in kids})  # one or the other
        covered += sum(k["dur_ns"] for k in kids)
    assert covered >= 0.95 * sum(s["dur_ns"] for s in iters)
    chunks = [s for s in spans if s["name"] == "serving.cb.chunk"]
    assert chunks and all(1 <= s["attrs"]["slots"] <= 2 for s in chunks)
    for ch in chunks:
        parts = [p["name"] for p in by_parent.get(ch["seq"], [])]
        assert parts in (["serving.cb.chunk.dispatch"], ["serving.cb.chunk.dispatch"] + landing), parts
    parts_ns = sum(p["dur_ns"] for ch in chunks for p in by_parent[ch["seq"]])
    assert parts_ns >= 0.95 * sum(ch["dur_ns"] for ch in chunks)
    # every launched chunk is landed once: inside the span that launched the next, or on its own
    assert len([s for s in spans if s["name"] == landing[0]]) == len(chunks)
    # a wave's launches run on the worker's own thread, as children of its wave; a rider's
    # first token lands later in the same pass of the loop, behind the chunk's launch
    waves = {s["seq"]: s for s in spans if s["name"] == "serving.paged.admit_wave"}
    assert waves and all(w["tid"] in worker for w in waves.values())
    # every wave was collected by the pass's `collect_wave` just before it; a pass ends its
    # admissions on a collect that took nobody
    collects = [s for s in spans if s["name"] == "serving.engine.collect_wave"]
    assert all(set(c["attrs"]) == {"n", "deferred"} and c["tid"] in worker for c in collects)
    assert sorted(c["attrs"]["n"] for c in collects if c["attrs"]["n"]) == sorted(w["attrs"]["n"] for w in waves.values())
    assert sum(c["attrs"]["n"] == 0 for c in collects) == len(iters)
    its = {s["seq"]: s for s in iters}
    for name in WAVE_STAGES:
        stages = [s for s in spans if s["name"] == name]
        assert len(stages) == 6, name  # one a request
        for st in stages:
            w = (waves if name in LAUNCH_STAGES else its)[st["parent_seq"]]
            assert st["tid"] == w["tid"], st
            assert w["t0_ns"] <= st["t0_ns"] and st["t0_ns"] + st["dur_ns"] <= w["t0_ns"] + w["dur_ns"], st
    for w in waves.values():  # the wave's riders land in the pass that launched them
        mine = {s["attrs"]["request_id"] for s in by_parent[w["seq"]] if s["name"] == "serving.paged.transfer"}
        landed = {s["attrs"]["request_id"] for s in by_parent[w["parent_seq"]] if s["name"] == "serving.paged.admit"}
        assert mine <= landed
    assert any(s["name"] == "serving.engine.idle" and s["tid"] in worker for s in spans)


# -- the admission wave's schedule: one thread, launches ahead of fetches ------


class _Wave:
    """An engine whose worker is held at the door of its next admission while
    ``burst`` submits, so the burst rides ONE wave (slots and pages allowing);
    ``spans()`` / ``count()`` give what the registry saw since construction."""

    def __init__(self, params, **kw):
        opts = dict(num_slots=3, chunk=4, page_size=8)
        opts.update(kw)
        self.registry = tel.get_telemetry()
        self._was = self.registry.enabled
        self.registry.set_enabled(True)  # whatever an earlier file of this worker left it at
        last = self.registry.snapshot()["spans"][-1:]
        self._seq0 = last[0]["seq"] if last else 0
        self._counters0 = dict(self.registry.snapshot()["counters"])
        self.eng = PagedContinuousBatchingEngine(params, CFG, **opts)

    def burst(self, requests):
        gate, inner = threading.Event(), self.eng._admit_all

        def held():
            assert gate.wait(timeout=60)
            inner()

        self.eng._admit_all = held
        try:
            return [self.eng.submit(p, n, **kw) for p, n, kw in requests]
        finally:
            self.eng._admit_all = inner
            gate.set()

    def spans(self, name=None):
        found = [s for s in self.registry.snapshot()["spans"]
                 if s["seq"] > self._seq0 and name in (None, s["name"])]
        return sorted(found, key=lambda s: s["t0_ns"])

    def chunks(self):
        """(its ``.dispatch``, the ``.sync`` that landed it) of every chunk,
        in launch order: chunks land in the order they were launched."""
        return list(zip(self.spans("serving.cb.chunk.dispatch"), self.spans("serving.cb.chunk.sync")))

    def count(self, counter):
        return self.registry.snapshot()["counters"].get(counter, 0) - self._counters0.get(counter, 0)

    def close(self):
        self.eng.shutdown()
        self.registry.set_enabled(self._was)


@pytest.fixture()
def wave(params):
    w = _Wave(params)
    yield w
    w.close()


def _ref(params, prompt, n):
    return np.asarray(generate(params, CFG, jnp.asarray([prompt], jnp.int32), n))[0].tolist()


def _end(span):
    return span["t0_ns"] + span["dur_ns"]


def test_wave_launches_every_rider_and_the_chunk_that_carries_them_before_it_waits_for_a_first_token(wave):
    handles = wave.burst([(_prompt(5 + 3 * i, 200 + i), 6, {}) for i in range(3)])
    for h in handles:
        h.result(timeout=120)
    (w,) = wave.spans("serving.paged.admit_wave")
    assert w["attrs"]["n"] == 3
    at = {(s["name"], s["attrs"]["request_id"]): s["t0_ns"] for s in wave.spans() if s["name"] in WAVE_STAGES}
    ids = [h.request_id for h in handles]
    for rid in ids:  # a rider passes its four stages in order
        assert [at[(name, rid)] for name in WAVE_STAGES] == sorted(at[(name, rid)] for name in WAVE_STAGES)
    for earlier, later in zip(ids, ids[1:]):  # launched in the wave's order, landed in the same
        assert at[("serving.paged.transfer", earlier)] < at[("serving.cb.prefill", later)]
        assert at[("serving.paged.first_token_wait", earlier)] < at[("serving.paged.first_token_wait", later)]
    # both programs of every rider, then the chunk that carries all three, are launched before the
    # engine waits for anybody's first token: each wait has that chunk queued behind it
    first_wait = at[("serving.paged.first_token_wait", ids[0])]
    assert at[("serving.paged.transfer", ids[-1])] < _end(w) <= first_wait
    chunk, dispatch = wave.spans("serving.cb.chunk")[0], wave.spans("serving.cb.chunk.dispatch")[0]
    assert chunk["attrs"]["slots"] == 3 and dispatch["parent_seq"] == chunk["seq"]
    assert _end(w) <= dispatch["t0_ns"] and _end(dispatch) <= first_wait
    assert at[("serving.paged.first_token_wait", ids[-1])] > dispatch["t0_ns"]  # the last rider's wait most of all


def test_every_span_of_a_wave_is_the_workers_and_no_thread_outlives_it(wave):
    seen = []
    inner = wave.eng._stage_admit

    def spy(w):
        seen.append({t.name for t in threading.enumerate()})
        inner(w)

    wave.eng._stage_admit = spy
    for h in wave.burst([(_prompt(4 + i, 210 + i), 5, {}) for i in range(3)]):
        h.result(timeout=120)
    (w,) = wave.spans("serving.paged.admit_wave")
    staged = [s for s in wave.spans() if s["name"] in WAVE_STAGES]
    assert len(staged) == 3 * len(WAVE_STAGES)
    assert all(s["tid"] == w["tid"] == wave.eng._worker.ident for s in staged)
    # the launches are the wave's children, the landings its siblings: the same pass of the loop
    assert all(s["parent_seq"] == (w["seq"] if s["name"] in LAUNCH_STAGES else w["parent_seq"]) for s in staged)
    names = set().union(*seen) | {t.name for t in threading.enumerate()}
    assert len(seen) == 3 and not [n for n in names if n.startswith("paged_admit")], names


def test_continuous_batching_imports_nothing_of_the_round_pipeline():
    import ast
    import inspect

    from fedml_tpu.serving import continuous_batching

    tree = ast.parse(inspect.getsource(continuous_batching))
    froms = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    plain = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in froms + plain if "pipeline" in m]
    assert not hasattr(continuous_batching, "PipelinedExecutor")


def test_a_burst_is_admitted_in_waves_of_whatever_was_free(wave):
    """Seven requests through three slots: the burst's first wave takes three,
    the rest are admitted as slots free, in waves of whatever was free. The
    waves' ``n`` say how many riders were launched behind an earlier rider's
    unfetched first token: riders less waves."""
    handles = wave.burst([(_prompt(3 + i, 220 + i), 3 + 2 * i, {}) for i in range(7)])
    assert [len(h.result(timeout=120)) for h in handles] == [3 + 2 * i for i in range(7)]
    waves = wave.spans("serving.paged.admit_wave")
    assert sum(w["attrs"]["n"] for w in waves) == wave.count("serving.cb.admissions") == 7
    assert waves[0]["attrs"]["n"] == 3 and len(waves) >= 3
    transfers = wave.spans("serving.paged.transfer")
    assert [sum(t["parent_seq"] == w["seq"] for t in transfers) for w in waves] == [w["attrs"]["n"] for w in waves]


@pytest.fixture(scope="module")
def one_wave_of_three_kinds(params):
    """One wave whose riders are a sharer of a resident 16-token prefix, an
    unshared prompt, and a sampled sharer; what each was served."""
    w = _Wave(params)
    try:
        system = _prompt(16, 230)
        w.eng.generate(system + _prompt(3, 231), 4)  # leaves the prefix pages resident
        riders = {"shared": (system + _prompt(6, 232), 9, {}),
                  "unshared": (_prompt(21, 233), 7, {}),
                  "shared_sampled": (system + _prompt(11, 234), 8, {"temperature": 0.9, "seed": 5})}
        handles = w.burst(list(riders.values()))
        served = {k: h.result(timeout=120) for k, h in zip(riders, handles)}
        waves = w.spans("serving.paged.admit_wave")
        shared = {s["attrs"]["request_id"]: s["attrs"]["shared"] for s in w.spans("serving.cb.prefill")}
        return riders, served, [x["attrs"]["n"] for x in waves], [shared[h.request_id] for h in handles]
    finally:
        w.close()


@pytest.mark.parametrize("kind", ["shared", "unshared", "shared_sampled"])
def test_riders_of_one_wave_are_served_generates_tokens(one_wave_of_three_kinds, params, kind):
    riders, served, wave_sizes, shared = one_wave_of_three_kinds
    assert wave_sizes == [1, 3] and shared == [16, 0, 16]
    prompt, n, kw = riders[kind]
    want = generate(params, CFG, jnp.asarray([prompt], jnp.int32), n, temperature=kw.get("temperature", 0.0),
                    key=jax.random.PRNGKey(kw.get("seed", 0)))
    assert served[kind] == np.asarray(want)[0].tolist()


@pytest.mark.parametrize("stage", ["_stage_prefill", "_stage_transfer", "_stage_admit"])
def test_a_failure_in_rider_two_of_three_is_rider_twos_alone(wave, params, stage):
    system = _prompt(16, 240)
    wave.eng.generate(system + _prompt(2, 241), 3)  # rider two holds shared pages when it fails
    prompts = [_prompt(9, 242), system + _prompt(5, 243), _prompt(13, 244)]
    inner = getattr(wave.eng, stage)

    def planted(w):
        if w.item.prompt == prompts[1]:
            raise RuntimeError("planted in rider two")
        inner(w)

    setattr(wave.eng, stage, planted)
    handles = wave.burst([(p, 6, {}) for p in prompts])
    assert handles[0].result(timeout=120) == _ref(params, prompts[0], 6)
    assert handles[2].result(timeout=120) == _ref(params, prompts[2], 6)
    with pytest.raises(RuntimeError, match="planted in rider two"):
        handles[1].result(timeout=120)
    assert [w["attrs"]["n"] for w in wave.spans("serving.paged.admit_wave")] == [1, 3]
    # rider three was launched behind rider one whichever stage of rider two failed
    second = wave.spans("serving.paged.admit_wave")[1]
    launched = [t for t in wave.spans("serving.paged.transfer") if t["parent_seq"] == second["seq"]]
    assert len(launched) == (3 if stage == "_stage_admit" else 2)
    leaks = wave.eng._alloc.check_leaks()
    assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["state_leaked"] == [] and leaks["accounted"]
    assert np.all(wave.eng._tables == 0) and wave.eng.stats()["slots_active"] == 0
    setattr(wave.eng, stage, inner)
    assert wave.eng.generate(prompts[1], 6) == _ref(params, prompts[1], 6)  # the engine serves on


def test_a_failure_that_consumed_the_pool_fails_the_wave_and_the_live_riders(wave):
    """What donation makes possible: the admit program raised after it took
    the pool. Nobody can be served from a deleted pool, so nobody hangs: the
    wave fails its unlaunched rider (three), the loop's boundary the two that
    hold slots (launched, their first tokens not yet fetched)."""
    prompts = [_prompt(5 + i, 250 + i) for i in range(3)]
    inner = wave.eng._stage_transfer

    def consumed(w):
        if w.item.prompt != prompts[2]:
            return inner(w)
        for leaf in jax.tree_util.tree_leaves(wave.eng._cache):
            leaf.delete()
        raise RuntimeError("planted after the pool was donated")

    wave.eng._stage_transfer = consumed
    handles = wave.burst([(p, 30, {}) for p in prompts])
    for h in handles:
        with pytest.raises(RuntimeError, match="planted after the pool was donated"):
            h.result(timeout=120)
    assert wave.count("serving.cb.admissions") == 0 and wave.spans("serving.cb.chunk.sync") == []  # no chunk went out
    assert wave.eng.stats()["slots_active"] == 0 and wave.eng._alloc.check_leaks()["accounted"]


# -- the loop's schedule: one decode chunk ahead of the host ---------------------

C = 4  # the fixture's chunk


def _generate(params, prompt, n, kw):
    want = generate(params, CFG, jnp.asarray([prompt], jnp.int32), n, temperature=kw.get("temperature", 0.0),
                    key=jax.random.PRNGKey(kw.get("seed", 0)))
    return np.asarray(want)[0].tolist()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_riders_admitted_while_a_chunk_is_in_flight_are_served_generates_tokens(wave, params, temperature):
    """Mixed lengths and budgets (one of a single token, one past the first's
    end, more riders than free slots): the carried rows never leave the device
    and an admission writes its row there, the same tokens come out."""
    kw = [({"temperature": temperature, "seed": 300 + i} if temperature else {}) for i in range(6)]
    first = (_prompt(7, 300), 30, kw[0])
    later = [(_prompt(4 + 5 * i, 301 + i), n, kw[i + 1]) for i, n in enumerate((5, 17, 1, 9, 12))]
    reached, release = hold(wave.eng, "_land_chunk")  # chunk 2 is launched, chunk 1 not yet fetched
    handles = [wave.eng.submit(first[0], first[1], **first[2])]
    assert reached.wait(timeout=60)
    handles += [wave.eng.submit(p, n, **k) for p, n, k in later]
    release.set()
    for (prompt, n, k), h in zip([first] + later, handles):
        assert h.result(timeout=120) == _generate(params, prompt, n, k)
    # the two riders the free slots took were launched between chunk 2's launch and its fetch,
    # and rode chunk 3, launched before their first tokens were waited for
    transfer = {s["attrs"]["request_id"]: s["t0_ns"] for s in wave.spans("serving.paged.transfer")}
    wait = {s["attrs"]["request_id"]: s["t0_ns"] for s in wave.spans("serving.paged.first_token_wait")}
    flights = wave.chunks()
    for h in handles[1:3]:
        assert flights[1][0]["t0_ns"] < transfer[h.request_id] < _end(flights[1][1])
        assert transfer[h.request_id] < flights[2][0]["t0_ns"] < wait[h.request_id]
    assert [c["attrs"]["slots"] for c in wave.spans("serving.cb.chunk")][:3] == [1, 1, 3]
    leaks = wave.eng._alloc.check_leaks()
    assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["accounted"]
    assert np.all(wave.eng._tables == 0) and wave.eng.stats()["kv_tokens_live"] == 0


def test_chunk_n_plus_1_is_launched_before_chunk_n_is_fetched_and_the_syncs_parents_say_how_often(wave):
    for requests in ([(_prompt(6, 310), 14, {}), (_prompt(9, 311), 7, {})], [(_prompt(5, 312), 10, {})]):
        for h in wave.burst(requests):  # the second burst finds the loop with nothing in flight again
            h.result(timeout=120)
    flights = wave.chunks()
    chunks = wave.spans("serving.cb.chunk")
    by_parent = {}
    for s in wave.spans():
        by_parent.setdefault(s["parent_seq"], []).append(s["name"])
    ahead = [c for c in chunks if "serving.cb.chunk.sync" in by_parent[c["seq"]]]
    alone = [s for s in wave.spans("serving.cb.chunk.sync") if s["parent_seq"] not in {c["seq"] for c in chunks}]
    # 14 and 7 tokens: 1 + 4 chunks; 10 tokens: 1 + 3 chunks; each burst's last chunk lands with nothing to launch
    assert len(chunks) == len(flights) == 4 + 3 and len(alone) == 2
    assert len(ahead) == len(chunks) - len(alone)  # a `.sync` under a chunk's span: that chunk was launched ahead of the fetch
    for (d0, s0), (d1, _) in zip(flights, flights[1:]):
        if d1["t0_ns"] < _end(s0):  # launched ahead: before the chunk before it was fetched, not merely before it ended
            assert d1["t0_ns"] < s0["t0_ns"]
    assert sum(d1["t0_ns"] < s0["t0_ns"] for (_, s0), (d1, _) in zip(flights, flights[1:])) == len(ahead)
    # every rider's first token is waited for behind the launch of the chunk that carries it
    firsts = [d for d in wave.spans("serving.cb.chunk.dispatch") if by_parent[d["parent_seq"]] == ["serving.cb.chunk.dispatch"]]
    waits = wave.spans("serving.paged.first_token_wait")
    assert len(firsts) == 2 and len(waits) == 3
    assert _end(firsts[0]) <= waits[0]["t0_ns"] and _end(firsts[1]) <= waits[2]["t0_ns"]


def test_an_eos_is_seen_a_chunk_late_and_the_rows_later_tokens_reach_nobody(params):
    w = _Wave(params, num_slots=1)
    try:
        prompt, then = _prompt(5, 7), _prompt(11, 320)
        ref = _ref(params, prompt, 24)
        eos = ref[2]  # among the first chunk's tokens
        cut = ref.index(eos)
        a, b = w.burst([(prompt, 24, {"eos_id": eos}), (then, 10, {})])  # one slot: the second rides it next
        assert a.result(timeout=120) == ref[:cut + 1]
        assert b.result(timeout=120) == _ref(params, then, 10)
        decode = {s["attrs"]["request_id"]: s["attrs"] for s in w.spans("serving.request.decode")}
        # chunk 2 was queued when chunk 1's tokens (the EOS among them) were seen; an EOS that is
        # the first token itself is seen at the rider's landing, behind chunk 1's launch
        assert decode[a.request_id]["wasted"] == (1 + 2 * C - (cut + 1) if cut else C) <= 2 * C - 1
        assert decode[a.request_id]["tokens"] == cut + 1 and decode[b.request_id]["wasted"] == 1 + 3 * C - 10
        assert w.count("serving.wasted_tokens") == decode[a.request_id]["wasted"] + decode[b.request_id]["wasted"]
        # the second rider was launched with the chunk that still carried the first one's row in
        # flight; what that chunk decoded for the row went to neither of them
        transfer = w.spans("serving.paged.transfer")[1]["t0_ns"]
        flights = w.chunks()
        assert flights[1][0]["t0_ns"] < transfer < _end(flights[1][1])
        st = w.eng.stats()
        assert st["tokens_out"] == cut + 1 + 10 and st["requests_done"] == 2 and st["kv_tokens_live"] == 0
        leaks = w.eng._alloc.check_leaks()
        assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["accounted"]
        assert np.all(w.eng._tables == 0)
    finally:
        w.close()


@pytest.mark.parametrize("budget", [1, 2, 5, 6, 12])
def test_a_budget_end_wastes_the_rest_of_its_last_chunk_and_no_more(wave, params, budget):
    """The host counts a budget's end without seeing a token: the row is out of
    the first chunk launched after the one that holds its last token."""
    prompt = _prompt(6, 330 + budget)
    h = wave.eng.submit(prompt, budget)
    assert h.result(timeout=120) == _ref(params, prompt, budget)
    (decode,) = wave.spans("serving.request.decode")
    n_chunks = -(-(budget - 1) // C)
    assert decode["attrs"]["wasted"] == 1 + C * n_chunks - budget == wave.count("serving.wasted_tokens")
    assert len(wave.spans("serving.cb.chunk")) == n_chunks


def test_a_chunk_that_raises_at_fetch_fails_both_chunks_riders_and_the_engine_serves_on(wave, params):
    inner, reached, release, calls = wave.eng._land_chunk, threading.Event(), threading.Event(), [0]

    def planted(chunk):
        calls[0] += 1
        if calls[0] == 1:  # chunk 2 is launched: the second request joins chunk 3
            reached.set()
            assert release.wait(timeout=60)
        if calls[0] == 2:  # chunk 2's fetch, with chunk 3 queued behind it
            raise RuntimeError("planted at the fetch")
        return inner(chunk)

    wave.eng._land_chunk = planted
    a = wave.eng.submit(_prompt(6, 340), 30)
    assert reached.wait(timeout=60)
    b = wave.eng.submit(_prompt(9, 341), 30)
    release.set()
    for h in (a, b):  # a rode both chunks, b the one queued behind
        with pytest.raises(RuntimeError, match="planted at the fetch"):
            h.result(timeout=120)
    assert [c["attrs"]["slots"] for c in wave.spans("serving.cb.chunk")] == [1, 1, 2]
    leaks = wave.eng._alloc.check_leaks()
    assert leaks["leaked"] == [] and leaks["bad_free"] == [] and leaks["accounted"]
    assert np.all(wave.eng._tables == 0) and wave.eng.stats()["slots_active"] == 0 and wave.eng._inflight is None
    fresh = _prompt(8, 342)
    assert wave.eng.generate(fresh, 9) == _ref(params, fresh, 9)  # the unfetched chunk's rows are nobody's


def test_shutdown_with_a_chunk_in_flight_returns_and_fails_its_riders(params):
    w = _Wave(params)
    reached, release = hold(w.eng, "_land_chunk")
    h = w.eng.submit(_prompt(6, 350), 30)
    assert reached.wait(timeout=60)
    stopper = threading.Thread(target=w.close)
    stopper.start()
    release.set()
    stopper.join(timeout=60)
    assert not stopper.is_alive() and not w.eng._worker.is_alive()
    with pytest.raises(RuntimeError, match="shutting down"):
        h.result(timeout=5)
    assert w.eng._inflight is None and w.eng.stats()["slots_active"] == 0
    assert w.eng._alloc.check_leaks()["accounted"] and np.all(w.eng._tables == 0)


def test_kv_tokens_live_is_the_live_rows_lengths_as_of_the_last_launched_chunk(wave):
    reached, release = hold(wave.eng, "_land_chunk", nth=2)  # chunk 3 is launched, chunk 2 not yet fetched
    handles = wave.burst([(_prompt(5, 360), 30, {}), (_prompt(9, 361), 30, {})])
    assert reached.wait(timeout=60)
    st = wave.eng.stats()
    seen = [len(s.tokens) for s in wave.eng._slots if s is not None]
    release.set()
    assert st["slots_active"] == 2 and st["kv_tokens_live"] == (5 + 9) + 2 * 3 * C
    assert seen == [1 + C, 1 + C]  # what the host has of them: the device is two chunks past it
    for h in handles:
        assert len(h.result(timeout=120)) == 30
    assert wave.eng.stats()["kv_tokens_live"] == 0


# -- the device-starvation ledger: when the chip had nothing queued, by the worker's account ------


class _Out:
    """Stands in for the newest launch's output: ``is_ready`` is the test's."""

    def __init__(self, ready=False, deleted=False):
        self.ready, self.deleted = ready, deleted

    def is_deleted(self):
        return self.deleted

    def is_ready(self):
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        return self.ready


@pytest.fixture()
def ledger(monkeypatch):
    """A ledger on a clock the test turns (``tick``), with what it recorded
    since (``pieces`` in order of recording, ``count``)."""
    import types

    from fedml_tpu.serving import continuous_batching as cb

    clock = types.SimpleNamespace(now=1_000, cpu=0)

    def tick(ns):
        clock.now += ns
        return clock.now

    monkeypatch.setattr(cb, "time", types.SimpleNamespace(perf_counter_ns=lambda: clock.now,
                                                          thread_time_ns=lambda: clock.cpu))
    registry = tel.get_telemetry()
    was = registry.enabled
    registry.set_enabled(True)
    seq0 = max((r["seq"] for r in registry.snapshot()["spans"]), default=0)
    counters0 = dict(registry.snapshot()["counters"])
    led = cb._DeviceLedger(())

    def pieces():
        return [r for r in registry.snapshot()["spans"] if r["seq"] > seq0 and r["name"] == "serving.device.starved"]

    def count(name):
        return registry.snapshot()["counters"].get(name, 0) - counters0.get(name, 0)

    yield types.SimpleNamespace(led=led, tick=tick, clock=clock, pieces=pieces, count=count, cb=cb, registry=registry)
    registry.set_enabled(was)


def test_a_starvation_opens_at_the_first_ready_answer_and_closes_at_the_next_launchs_return(ledger):
    led, tick, cb = ledger.led, ledger.tick, ledger.cb
    out = _Out()
    led.mark(cb.NO_WORK)                 # t = 1,000: nothing launched yet: starved from here, as an engine starts
    led.launched(out)                    # the first launch returns at once: a piece of no length, the chip has work
    tick(50), led.mark(cb.LAND)          # 1,050: still busy
    tick(70), led.mark(cb.LAND)          # 1,120: still busy: the look before the one that reads ready
    out.ready = True
    tick(30), led.mark(cb.COLLECT)       # 1,150: ready: the interval opens HERE, in `collect`
    assert len(ledger.pieces()) == 1     # nothing more is cut until a boundary is crossed
    tick(400), led.mark(cb.LAUNCH)       # 1,550: a piece of `collect`
    tick(25), led.mark(cb.LAUNCH)        # 1,575: a piece of `launch`
    tick(600), led.launched(_Out())      # 2,175: the launch call returned: closed
    tick(90), led.mark(cb.LAND)          # busy again: no piece
    tick(10), led.launched(_Out())       # a launch with nothing open: no piece either
    got = [(p["attrs"]["phase"], p["dur_ns"], p["attrs"]["first"], p["attrs"].get("unseen_ns")) for p in ledger.pieces()]
    assert got == [("no_work", 0, True, 0),
                   ("collect", 400, True, 30), ("launch", 25, False, None), ("launch", 600, False, None)]
    starts = [p["t0_ns"] for p in ledger.pieces()[1:]]
    assert [b - a for a, b in zip(starts, starts[1:])] == [400, 25]  # the pieces tile the interval
    assert ledger.count("serving.device.starved_ns") == 1_025 and ledger.count("serving.device.starvations") == 2
    assert all(p["attrs"]["phase"] in cb.STARVED_PHASES for p in ledger.pieces())


def test_every_boundary_crossed_while_starved_cuts_a_piece_in_the_phase_it_ends(ledger):
    led, tick, cb = ledger.led, ledger.tick, ledger.cb
    led.mark(cb.NO_WORK)                 # nothing was ever launched: the queue is empty
    for ns, phase in ((7_000, cb.COLLECT), (300, cb.LAUNCH), (40, cb.LAND), (5, cb.NO_WORK)):
        tick(ns), led.mark(phase)
    tick(11), led.launched(_Out())
    assert [(p["attrs"]["phase"], p["dur_ns"]) for p in ledger.pieces()] == [
        ("no_work", 7_000), ("collect", 300), ("launch", 40), ("land", 5), ("no_work", 11)]
    first = [p["attrs"]["first"] for p in ledger.pieces()]
    assert first == [True, False, False, False, False] and ledger.pieces()[0]["attrs"]["unseen_ns"] == 0
    assert ledger.count("serving.device.starvations") == 1 and ledger.count("serving.device.starved_ns") == 7_356


def test_a_queue_that_emptied_and_filled_again_inside_one_launch_leaves_a_piece_of_no_length(ledger):
    """No boundary lies inside a launch: where the launch before is done when
    this one returns and nothing saw it, the doubt is an ``unseen_ns``."""
    led, tick, cb = ledger.led, ledger.tick, ledger.cb
    led.mark(cb.NO_WORK)
    first = _Out()
    led.launched(first)                  # 1,000
    tick(40), led.mark(cb.LAUNCH)        # 1,040: busy: the last look that read so
    first.ready = True                   # done while the worker is inside the next launch call
    second = _Out()
    tick(900), led.launched(second)      # 1,940: returns; nothing was open, the launch before is done
    tick(10), led.launched(_Out())       # `second` still runs: no doubt, no piece
    got = [(p["attrs"]["phase"], p["dur_ns"], p["attrs"]["first"], p["attrs"].get("unseen_ns")) for p in ledger.pieces()[1:]]
    assert got == [("launch", 0, True, 900)]
    assert ledger.count("serving.device.starvations") == 2 and ledger.count("serving.device.starved_ns") == 0


def test_a_deleted_output_reads_as_ready_and_never_raises_on_the_worker(ledger):
    led, tick, cb = ledger.led, ledger.tick, ledger.cb
    led.mark(cb.NO_WORK)
    led.launched(_Out(deleted=True))     # closes the engine's first interval: no length on this clock
    tick(10), led.mark(cb.LAND)          # the deleted output reads ready: opens
    tick(20), led.mark(cb.LAND)
    real = jnp.zeros((3,), jnp.int32)
    real.delete()                        # what a donated array looks like
    led.launched(real)
    tick(5), led.mark(cb.COLLECT)
    tick(8), led.launched(jnp.zeros((3,), jnp.int32))
    assert [(p["dur_ns"], p["attrs"]["first"]) for p in ledger.pieces()] == [(0, True), (20, True), (0, False), (8, True)]


def test_nothing_is_recorded_with_the_registry_off_and_no_interval_survives_it(ledger):
    led, tick, cb = ledger.led, ledger.tick, ledger.cb
    ready = _Out(ready=True)
    led.mark(cb.NO_WORK)
    led.launched(ready)
    tick(10), led.mark(cb.LAND)          # opens
    ledger.registry.set_enabled(False)
    tick(1_000_000), led.mark(cb.COLLECT)
    tick(10), led.launched(ready)
    led.begin_iteration()
    attrs = {}
    led.end_iteration(attrs)
    assert len(ledger.pieces()) == 1 and attrs == {} and led.before_fetch() == 0  # the one from before it went off
    assert ledger.count("serving.device.starved_ns") == 0 and ledger.count("serving.device.starvations") == 1
    ledger.registry.set_enabled(True)
    tick(10), led.mark(cb.LAND)          # a fresh interval from here: nothing of the dark million
    tick(4), led.launched(_Out())
    assert [(p["dur_ns"], p["attrs"]["unseen_ns"]) for p in ledger.pieces()[1:]] == [(4, 0)]


def test_the_iterations_account_counts_the_fetches_off_cpu_time_as_blocked_and_its_own_locks(ledger):
    import types

    led, tick, clock, cb = ledger.led, ledger.tick, ledger.clock, ledger.cb
    lock = types.SimpleNamespace(wait_ns=500)
    led = cb._DeviceLedger((lock,))
    led.launched(_Out(ready=True))
    led.begin_iteration()                # opens an interval, in `collect`
    clock.cpu += 2_000
    tick(2_500)
    lock.wait_ns += 300
    cpu0 = led.before_fetch()            # a boundary: the piece of `collect` ends here
    clock.cpu += 100                     # the copy to NumPy inside a fetch of 10,000 ns
    tick(10_000)
    led.after_fetch(types.SimpleNamespace(duration_ns=10_000), cpu0)
    led.after_fetch(types.SimpleNamespace(duration_ns=None), cpu0)  # a span opened with the registry off: nothing
    attrs = {}
    led.end_iteration(attrs)
    assert attrs == {"cpu_ns": 2_100, "blocked_ns": 9_900, "lock_wait_ns": 300, "starved_ns": 12_500}
    assert attrs["cpu_ns"] + attrs["blocked_ns"] <= 12_500
    assert [(p["attrs"]["phase"], p["dur_ns"]) for p in ledger.pieces()] == [
        ("no_work", 0),  # the first launch found nothing launched before it
        ("collect", 2_500), ("land", 10_000), ("land", 0), ("land", 0)]  # the loop's top follows, still starved


def test_the_engine_marks_its_phases_and_the_ledger_closes_inside_a_launch(wave, params):
    """The live engine with the newest output swapped for a stub that is
    always ready (a chip that finishes everything at once): every interval
    closes inside a launch's span, its pieces tile it, each in one phase of
    the closed set, and the iterations' ``starved_ns`` add up to the pieces
    cut inside them."""
    from fedml_tpu.serving.continuous_batching import STARVED_PHASES

    inner = wave.eng._ledger.launched
    wave.eng._ledger.launched = lambda out: inner(_Out(ready=True))
    for h in wave.burst([(_prompt(5 + 2 * i, 350 + i), 9 + i, {}) for i in range(4)]):  # four riders, three slots
        h.result(timeout=120)
    wave.eng._ledger.launched = inner
    wave.eng.shutdown()
    pieces = wave.spans("serving.device.starved")
    assert pieces and all(p["tid"] == wave.eng._worker.ident and p["depth"] == 0 for p in pieces)
    assert {p["attrs"]["phase"] for p in pieces} == set(STARVED_PHASES) - {"no_work"} | {pieces[0]["attrs"]["phase"]}
    intervals = []
    for p in pieces:
        assert set(p["attrs"]) == ({"phase", "first", "unseen_ns"} if p["attrs"]["first"] else {"phase", "first"})
        if p["attrs"]["first"]:
            intervals.append([p])
        else:
            assert p["t0_ns"] == _end(intervals[-1][-1])  # tiles: starts where the piece before ended
            intervals[-1].append(p)
    assert wave.count("serving.device.starvations") == len(intervals) > 4
    assert wave.count("serving.device.starved_ns") == sum(p["dur_ns"] for p in pieces)
    launches = [s for name in (*LAUNCH_STAGES, "serving.cb.chunk.dispatch") for s in wave.spans(name)]
    for iv in intervals[:-1]:  # the last one is the idle engine's, cut by nothing yet
        end = _end(iv[-1])
        assert iv[-1]["attrs"]["phase"] == "launch" and any(s["t0_ns"] <= end <= _end(s) for s in launches)
    # a launch's return is the only thing that closes one: every launch closed the interval open before it
    assert len(intervals) - 1 <= len(launches)
    for it in wave.spans("serving.engine.iteration"):
        inside = [p for p in pieces if it["t0_ns"] <= p["t0_ns"] and _end(p) <= _end(it)]
        assert sum(p["dur_ns"] for p in inside) <= it["attrs"]["starved_ns"] <= it["dur_ns"]
        assert it["attrs"]["cpu_ns"] + it["attrs"]["blocked_ns"] <= it["dur_ns"]
        assert it["attrs"]["lock_wait_ns"] <= it["dur_ns"]


def test_a_chip_that_is_never_done_starves_nowhere_past_the_first_launch(wave):
    inner = wave.eng._ledger.launched
    wave.eng._ledger.launched = lambda out: inner(_Out(ready=False))
    for h in wave.burst([(_prompt(6, 360), 7, {}), (_prompt(9, 361), 5, {})]):
        h.result(timeout=120)
    pieces = wave.spans("serving.device.starved")
    # the engine had launched nothing when the burst came: one interval, `no_work` until the first launch returned
    assert [p["attrs"]["first"] for p in pieces] == [True] + [False] * (len(pieces) - 1)
    assert pieces[0]["attrs"]["phase"] == "no_work" and pieces[-1]["attrs"]["phase"] == "launch"
    assert wave.count("serving.device.starvations") == 1
    assert all(it["attrs"]["starved_ns"] == 0 for it in wave.spans("serving.engine.iteration")[1:])


def test_the_engine_records_no_starvation_with_the_registry_off(wave):
    wave.registry.set_enabled(False)
    assert len(wave.eng.generate(_prompt(7, 370), 6)) == 6
    wave.registry.set_enabled(True)
    assert wave.spans("serving.device.starved") == [] and wave.spans("serving.engine.iteration") == []
    assert wave.count("serving.device.starved_ns") == 0 == wave.count("serving.device.starvations")
    assert wave.eng._wlock.wait_ns == 0 == wave.eng._alloc.worker_lock.wait_ns


@pytest.mark.parametrize("label", ["prefill", "paged_step", "paged_admit",
                                   "paged_gather", "paged_suffix_prefill"])
def test_serving_programs_lower_under_their_labels_name(params, label):
    from fedml_tpu.serving import paged_kv
    from fedml_tpu.train.llm.generation import _prefill_fn

    pcfg = paged_kv.paged_config(CFG, page_size=8, num_pages=5)
    fn = {"prefill": lambda: _prefill_fn(CFG, 1, 16),
          "paged_step": lambda: paged_kv._paged_step_fn(pcfg, 2, 4),
          "paged_admit": lambda: paged_kv._paged_admit_fn(pcfg),
          "paged_gather": lambda: paged_kv._paged_gather_fn(pcfg),
          "paged_suffix_prefill": lambda: paged_kv._suffix_prefill_fn(pcfg, 16)}[label]()
    fn = getattr(fn, "_fn", fn)  # devperf.instrument wraps the decode steps
    assert fn.__wrapped__.__name__ == label  # what jax.jit names the program after


def test_submit_mints_an_id_when_no_boundary_did(engine):
    from fedml_tpu.core.telemetry import trace_context

    h = engine.submit(_prompt(4, 31), 6)
    assert h.request_id and len(h.request_id) == 32
    given = engine.submit(_prompt(4, 32), 6, request_id="caller-chosen")
    assert given.request_id == "caller-chosen"
    with trace_context.activated(trace_context.TraceContext("ab" * 16)):
        active = engine.submit(_prompt(4, 33), 6)
    assert active.request_id == "ab" * 16
    for handle in (h, given, active):
        assert len(handle.result(timeout=120)) == 6
        assert handle.ttft_s >= handle.queue_wait_s >= 0 and handle.tpot_s > 0


def test_check_serving_lint_clean_and_detects_regressions(tmp_path):
    """tools/check_serving.py: the repo's serving hot loops are span-
    instrumented (rc 0), and the lint actually fires when instrumentation
    is stripped or a registered hot loop disappears."""
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_serving", os.path.join(repo, "tools", "check_serving.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0

    # synthetic tree: _admit_all lost its span, _step_chunk is gone,
    # replica_controller.py does not exist
    (tmp_path / "continuous_batching.py").write_text(
        "class PagedContinuousBatchingEngine:\n"
        "    def _admit_all(self):\n"
        "        return 1\n"
    )
    bad = mod.find_unspanned_hot_loops(str(tmp_path))
    msgs = [m for _, _, m in bad]
    assert any("_admit_all" in m and "no tel.timed" in m for m in msgs)
    assert any("_step_chunk" in m and "missing" in m for m in msgs)
    assert any("replica_controller.py" in m for m in msgs)
    assert mod.main([str(tmp_path)]) == 1

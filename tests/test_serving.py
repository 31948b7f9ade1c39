"""Serving tests: predictor contract, HTTP runner routes, endpoint replica
control + gateway over real localhost HTTP."""

import json
import urllib.request

import numpy as np
import pytest

from fedml_tpu.serving import (
    Endpoint,
    EndpointManager,
    FedMLInferenceRunner,
    FedMLPredictor,
    JaxPredictor,
    ModelCard,
    ModelDB,
)


class EchoPredictor(FedMLPredictor):
    def __init__(self):
        super().__init__()
        self._ready = True
        # unique replica identity: id() % 1000 could collide between two
        # instances depending on heap layout (the round-robin assertion
        # then sees one "who" — the load-dependent flake of VERDICT r2 #3)
        import uuid

        self.who = uuid.uuid4().hex

    def predict(self, request, *args, **kwargs):
        return {"echo": request.get("inputs"), "who": self.who}


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


def test_predictor_must_implement_predict():
    with pytest.raises(NotImplementedError):
        FedMLPredictor()


def test_inference_runner_routes():
    runner = FedMLInferenceRunner(EchoPredictor(), port=0)
    port = runner.start()
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/ready", timeout=10) as r:
            assert json.loads(r.read())["status"] == "Success"
        out = _post(f"http://127.0.0.1:{port}/predict", {"inputs": [1, 2, 3]})
        assert out["echo"] == [1, 2, 3]
    finally:
        runner.stop()


def test_jax_predictor_serves_jitted_forward():
    import jax.numpy as jnp

    params = {"w": jnp.asarray([[2.0], [3.0]])}
    pred = JaxPredictor(lambda p, x: x @ p["w"], params)
    assert not pred.ready()
    pred.warmup(jnp.zeros((1, 2)))
    assert pred.ready()
    out = pred.predict({"inputs": [[1.0, 1.0]]})
    assert out["outputs"] == [[5.0]]


def test_endpoint_round_robin_and_scaling():
    ep = Endpoint("e1", EchoPredictor, num_replicas=2)
    try:
        whos = {ep.predict({"inputs": [i]})["who"] for i in range(4)}
        assert len(whos) == 2  # round robin hit both replicas
        ep.scale_to(1)
        assert len(ep.replicas) == 1
        assert ep.predict({"inputs": [9]})["echo"] == [9]
    finally:
        ep.shutdown()


def test_endpoint_manager_and_model_db(tmp_path):
    db = ModelDB(str(tmp_path / "models.json"))
    db.add(ModelCard(name="m", version="1", model_path="/tmp/x"))
    db.add(ModelCard(name="m", version="2", model_path="/tmp/y"))
    assert db.get("m", "latest").version == "2"
    # reload from disk
    db2 = ModelDB(str(tmp_path / "models.json"))
    assert db2.get("m", "1").model_path == "/tmp/x"

    mgr = EndpointManager(db)
    ep = mgr.deploy("demo", EchoPredictor, num_replicas=1)
    try:
        assert ep.predict({"inputs": "x"})["echo"] == "x"
        with pytest.raises(ValueError):
            mgr.deploy("demo", EchoPredictor)
    finally:
        mgr.undeploy("demo")
    assert "demo" not in mgr.endpoints


# -- LLMPredictor(paged=True) behind the runner: the surface users call --------


class _CharTok:  # one token a character
    special_tokens = {}

    def __init__(self, vocab):
        self.vocab = vocab

    def encode(self, s):
        return [1 + (ord(c) % (self.vocab - 1)) for c in s] or [1]

    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.fixture(scope="module")
def llm():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM

    # a vocabulary no other file builds: the compiled programs' cache keys are this file's own
    cfg = TransformerConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32, remat=False, lora_rank=0,
    )
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    return params, cfg, _CharTok(cfg.vocab_size)


@pytest.fixture()
def llm_runner(llm):
    """A runner over ``LLMPredictor(paged=True)`` (2 slots, chunks of 2), warmed
    up; yields its port and a reader of the spans recorded since."""
    from fedml_tpu.core import telemetry as tel
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    pred = LLMPredictor(*llm, default_max_new_tokens=4, paged=True, num_slots=2,
                        decode_chunk=2, page_size=8)
    runner = FedMLInferenceRunner(pred, port=0)
    port = runner.start()
    registry = tel.get_telemetry()
    was = registry.enabled
    registry.set_enabled(True)  # whatever an earlier file of this worker left it at
    try:
        _post(f"http://127.0.0.1:{port}/predict", {"prompt": "warm up", "max_new_tokens": 3})
        last = registry.snapshot()["spans"][-1:]
        seq0 = last[0]["seq"] if last else 0
        yield port, lambda: [s for s in tel.snapshot()["spans"] if s["seq"] > seq0]
    finally:
        runner.stop()
        registry.set_enabled(was)


def _fire_all(port, payloads):
    """POST every payload at once, a thread each; replies (or HTTP codes) by index."""
    import threading

    results = {}

    def fire(i):
        try:
            results[i] = _post(f"http://127.0.0.1:{port}/predict", payloads[i])
        except urllib.request.HTTPError as e:
            results[i] = {"code": e.code, "body": json.loads(e.read())}

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(len(payloads))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return results


def test_concurrent_requests_share_decode_chunks_and_get_their_own_replies(llm, llm_runner):
    """Concurrent /predict requests ride ONE decode batch (a chunk with two
    live slots), and each gets the reply of its own prompt and budget."""
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    port, spans = llm_runner
    payloads = [{"prompt": "p" * (3 + 4 * i), "max_new_tokens": 40 + i} for i in range(4)]
    results = _fire_all(port, payloads)
    reference = LLMPredictor(*llm)
    for i, payload in enumerate(payloads):
        assert len(results[i]["token_ids"]) == 40 + i
        assert results[i]["text"] == reference.predict(payload)["text"], i
    chunks = [s["attrs"]["slots"] for s in spans() if s["name"] == "serving.cb.chunk"]
    assert chunks and max(chunks) == 2, f"never batched: {chunks}"
    assert len({r["timing"]["request_id"] for r in results.values()}) == 4


def test_a_malformed_request_fails_alone(llm_runner):
    """A request the engine refuses (no decode budget) and one without a
    prompt get their 500s; the requests in flight beside them finish."""
    port, _ = llm_runner
    results = _fire_all(port, [{"prompt": "ok one", "max_new_tokens": 24},
                               {"prompt": "bad", "max_new_tokens": 0},
                               {"max_new_tokens": 5},
                               {"prompt": "ok two", "max_new_tokens": 24}])
    assert len(results[0]["token_ids"]) == 24 and len(results[3]["token_ids"]) == 24
    assert results[1]["code"] == 500 and "max_new_tokens" in results[1]["body"]["error"]
    assert results[2]["code"] == 500 and "prompt" in results[2]["body"]["error"]
    assert _post(f"http://127.0.0.1:{port}/predict", {"prompt": "after"})["text"]


def test_paged_predictor_matches_the_reference_predictor(llm):
    """``LLMPredictor(paged=True)`` and the engine-less ``LLMPredictor``
    (``generate_text``) return the same text for the same greedy requests,
    whatever their lengths and whether they arrive alone or together."""
    import threading

    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    reqs = [{"prompt": "the quick"}, {"prompt": "z"},
            {"prompt": "fox jumps over the lazy dog", "max_new_tokens": 9},
            {"prompt": "a" * 33, "max_new_tokens": 17}]
    reference = LLMPredictor(*llm, default_max_new_tokens=6)
    want = [reference.predict(r)["text"] for r in reqs]
    paged = LLMPredictor(*llm, default_max_new_tokens=6, paged=True, num_slots=2,
                         decode_chunk=2, page_size=8)
    try:
        assert [paged.predict(r)["text"] for r in reqs] == want  # one at a time
        got = [None] * len(reqs)

        def ask(i):
            got[i] = paged.predict(reqs[i])["text"]

        threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert got == want  # interleaved across the two slots
    finally:
        paged.engine.shutdown()


def test_endpoint_least_in_flight_routing():
    """The gateway routes to the replica with the fewest outstanding
    requests (queue depth, not arrival order, is the load signal once
    replicas run continuous batching); ties rotate round-robin."""
    ep = Endpoint("lif", EchoPredictor, num_replicas=2)
    try:
        assert ep.in_flight() == [0, 0]
        # pin replica 0 as "busy": every request must land on replica 1
        ep._clients[0].in_flight = 5
        busy_free_who = {ep.predict({"inputs": [i]})["who"] for i in range(4)}
        assert len(busy_free_who) == 1
        ep._clients[0].in_flight = 0
        # balanced again: ties rotate, both replicas serve
        whos = {ep.predict({"inputs": [i]})["who"] for i in range(4)}
        assert len(whos) == 2
        assert ep.in_flight() == [0, 0]  # decrements survived every path
    finally:
        ep.shutdown()


def test_endpoint_keepalive_reuses_connections():
    """Repeated predicts ride pooled keep-alive connections instead of a
    TCP handshake per request (the pool holds at most one conn here since
    requests are sequential)."""
    ep = Endpoint("ka", EchoPredictor, num_replicas=1)
    try:
        for i in range(3):
            assert ep.predict({"inputs": [i]})["echo"] == [i]
        [client] = ep._clients
        assert len(client._pool) == 1
        conn = client._pool[0]
        assert ep.predict({"inputs": [9]})["echo"] == [9]
        assert client._pool[0] is conn  # same socket came back
    finally:
        ep.shutdown()


def test_autoscaler_latency_policy_reads_gateway_signals():
    """AutoScaler consumes InferenceGateway.signals() — the same values the
    Prometheus scrape exports — and a latency-EWMA breach under load adds a
    replica even when QPS alone looks satisfied."""
    from fedml_tpu.serving.replica_controller import AutoScaler, InferenceGateway

    class _RS:
        desired = 2

    class _GW:
        replica_set = _RS()

        def __init__(self, qps, lat):
            self._sig = {"qps": qps, "latency_ewma_s": lat, "errors": 0.0}

        def signals(self):
            return self._sig

    # qps says 1 replica; the latency breach bumps to desired+1 = 3
    sc = AutoScaler(_GW(10.0, 0.5), target_qps_per_replica=10.0,
                    max_latency_s=0.2, min_replicas=1, max_replicas=8)
    assert sc.desired_replicas() == 3
    # same load, healthy latency: qps policy alone
    sc2 = AutoScaler(_GW(10.0, 0.05), target_qps_per_replica=10.0,
                     max_latency_s=0.2, min_replicas=1, max_replicas=8)
    assert sc2.desired_replicas() == 1
    # no latency policy configured: breach is ignored
    sc3 = AutoScaler(_GW(10.0, 0.5), target_qps_per_replica=10.0,
                     min_replicas=1, max_replicas=8)
    assert sc3.desired_replicas() == 1
    # idle latency spike must NOT scale (qps == 0 gate)
    sc4 = AutoScaler(_GW(0.0, 9.9), target_qps_per_replica=10.0,
                     max_latency_s=0.2, min_replicas=1, max_replicas=8)
    assert sc4.desired_replicas() == 1

    # the scrape and the policy read ONE source: gauge names + values
    class _EmptyRS:
        desired = 0

        def healthy(self):
            return []

    gw = InferenceGateway.__new__(InferenceGateway)
    gw.replica_set = _EmptyRS()
    import threading as _threading
    import time as _time

    from fedml_tpu.serving.replica_controller import GatewayStats

    gw.stats = GatewayStats(window_start=_time.perf_counter())
    gw._rr = 0
    gw._lock = _threading.Lock()
    names = {g[0] for g in gw.prom_gauges()}
    assert names == {"serving_gateway_qps",
                     "serving_gateway_latency_ewma_seconds",
                     "serving_gateway_errors"}
    assert set(gw.signals()) == {"qps", "latency_ewma_s", "errors"}

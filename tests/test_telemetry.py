"""Unified telemetry (core/telemetry): span nesting + ordering, thread-safe
counters, Chrome-trace schema, compile-counter agreement with the bucketed
engine's trace counters, the < 1µs disabled-path contract, the full sp
FedAvg round span lifecycle, and the repo-wide timing-idiom lint."""

import glob
import importlib.util
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.core.telemetry import Telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestSpanNesting:
    def test_nesting_order_and_parentage(self):
        t = Telemetry(enabled=True)
        with t.span("outer", round=0):
            with t.span("inner_a"):
                pass
            with t.span("inner_b", k=2):
                with t.span("leaf"):
                    pass
        spans = t.snapshot()["spans"]
        names = [s["name"] for s in spans]
        # snapshot returns START order (seq assigned at entry)
        assert names == ["outer", "inner_a", "inner_b", "leaf"]
        by_name = {s["name"]: s for s in spans}
        assert by_name["outer"]["parent_seq"] is None
        assert by_name["outer"]["depth"] == 0
        assert by_name["inner_a"]["parent_seq"] == by_name["outer"]["seq"]
        assert by_name["inner_b"]["parent_seq"] == by_name["outer"]["seq"]
        assert by_name["leaf"]["parent_seq"] == by_name["inner_b"]["seq"]
        assert by_name["leaf"]["depth"] == 2
        assert by_name["inner_b"]["attrs"] == {"k": 2}
        assert all(s["dur_ns"] >= 0 for s in spans)

    def test_span_stats_rollup(self):
        t = Telemetry(enabled=True)
        for _ in range(3):
            with t.span("phase"):
                pass
        st = t.snapshot()["span_stats"]["phase"]
        assert st["count"] == 3
        assert st["max_ms"] <= st["total_ms"]

    def test_timed_exposes_duration_even_when_disabled(self):
        t = Telemetry(enabled=False)
        with t.timed("work") as sp:
            pass
        assert sp.duration_s is not None and sp.duration_s >= 0.0
        assert t.snapshot()["spans"] == []  # measured, not recorded


class TestRecordSpan:
    def test_interval_from_two_threads_is_one_record(self):
        """A span whose start is read on one thread and whose end on another:
        same record and roll-up as a ``with`` block, no nesting state touched."""
        t = Telemetry(enabled=True)
        box = {}
        th = threading.Thread(target=lambda: box.update(t0=time.perf_counter_ns()))
        th.start()
        th.join(timeout=10)
        with t.span("worker.pass"):
            t1 = time.perf_counter_ns()
            t.record_span("request.queue", box["t0"], t1, request_id="r1", queue_depth=3)
            with t.span("child"):
                pass
        snap = t.snapshot()
        by_name = {s["name"]: s for s in snap["spans"]}
        rec = by_name["request.queue"]
        assert rec["dur_ns"] == t1 - box["t0"]
        assert rec["attrs"] == {"request_id": "r1", "queue_depth": 3}
        assert rec["parent_seq"] is None and rec["depth"] == 0
        assert rec["tid"] == threading.get_ident()  # the recording thread's lane
        # the open span's nesting is untouched: child still hangs under it
        assert by_name["child"]["parent_seq"] == by_name["worker.pass"]["seq"]
        assert snap["span_stats"]["request.queue"]["count"] == 1
        assert len({s["seq"] for s in snap["spans"]}) == 3

    def test_disabled_registry_records_nothing(self):
        t = Telemetry(enabled=False)
        t.record_span("request.queue", 1, 2, request_id="r")
        assert t.snapshot()["spans"] == [] and t.snapshot()["span_stats"] == {}

    def test_epoch_places_spans_on_the_perf_counter_clock(self):
        t = Telemetry(enabled=True)
        before = time.perf_counter()
        with t.span("placed"):
            pass
        after = time.perf_counter()
        snap = t.snapshot()
        start_s = (snap["epoch_perf_ns"] + snap["spans"][0]["t0_ns"]) / 1e9
        assert before <= start_s <= after
        a = time.perf_counter_ns()
        b = a + 5_000
        t.record_span("from_readings", a, b)
        rec = t.snapshot()["spans"][-1]
        assert snap["epoch_perf_ns"] + rec["t0_ns"] == a and rec["dur_ns"] == 5_000
        t.reset()  # a new epoch, still the clock's own reading
        assert abs(t.snapshot()["epoch_perf_ns"] - time.perf_counter_ns()) < 1e9


class TestProfilerSink:
    def test_spans_reach_the_profilers_host_plane(self, tmp_path):
        """One span, two sinks: under a jax.profiler capture every span and
        timed block is also a ``fedml:<name>`` event of the host plane, with
        the registry's duration and (relative to a sibling) its start."""
        import jax

        t = Telemetry(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with t.span("sink.outer", round=1):
                time.sleep(0.02)
                with t.timed("sink.inner"):
                    jnp.ones((64, 64)).sum().block_until_ready()
                    time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        t.record_span("sink.recorded", 1, 2)  # an interval has no block to annotate
        path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        events = {}
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("fedml:"):
                        events[e.name] = (e.start_ns, e.duration_ns)
        assert set(events) == {"fedml:sink.outer", "fedml:sink.inner"}
        spans = {s["name"]: s for s in t.snapshot()["spans"]}
        slack_ns = 2e6  # the annotation brackets the registry's two clock reads
        for name in ("sink.outer", "sink.inner"):
            dur = events["fedml:" + name][1]
            assert 0 <= dur - spans[name]["dur_ns"] < slack_ns, name
        offset_trace = events["fedml:sink.inner"][0] - events["fedml:sink.outer"][0]
        offset_registry = spans["sink.inner"]["t0_ns"] - spans["sink.outer"]["t0_ns"]
        assert abs(offset_trace - offset_registry) < slack_ns
        assert offset_registry >= 0.02e9

    def test_a_disabled_registry_writes_no_annotation(self, tmp_path):
        import jax

        t = Telemetry(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with t.span("sink.off"), t.timed("sink.off_timed"):
                pass
        finally:
            jax.profiler.stop_trace()
        path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        names = [e.name for plane in data.planes for line in plane.lines for e in line.events]
        assert not [n for n in names if n.startswith("fedml:")]

    def test_sink_adds_under_2us_when_no_trace_is_captured(self):
        assert tel.profiler_sink_overhead_ns() < 2000.0


class TestCounterThreads:
    def test_counter_correct_under_8_threads(self):
        t = Telemetry(enabled=True)
        c = t.counter("hits")
        n_threads, per_thread = 8, 5000

        def work():
            for _ in range(per_thread):
                c.add(1)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert c.value == n_threads * per_thread
        assert t.snapshot()["counters"]["hits"] == n_threads * per_thread
        # the timeline-event cap bounds memory; overflow is counted, not lost
        assert len(c.events) <= tel.core.MAX_COUNTER_EVENTS

    def test_counter_value_updates_when_disabled(self):
        t = Telemetry(enabled=False)
        t.counter("bytes").add(64)
        assert t.snapshot()["counters"]["bytes"] == 64
        assert t.counter("bytes").events == []  # timeline gated on enabled


class TestChromeTraceSchema:
    def test_export_schema(self, tmp_path):
        t = Telemetry(enabled=True)
        with t.span("round", round=1):
            with t.span("train", client=3):
                pass
        t.counter("comm.bytes").add(128)
        t.histogram("secs").observe(0.5)
        path = str(tmp_path / "trace.json")
        assert t.export_chrome_trace(path) == path
        doc = json.loads(open(path).read())
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        assert {e["ph"] for e in events} == {"M", "X", "C"}
        for e in events:
            assert isinstance(e["name"], str)
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        xs = [e for e in events if e["ph"] == "X"]
        assert [e["name"] for e in xs] == ["round", "train"]
        for e in xs:
            assert isinstance(e["ts"], float) and e["ts"] >= 0.0
            assert isinstance(e["dur"], float) and e["dur"] >= 0.0
        assert xs[0]["args"]["round"] == 1
        cs = [e for e in events if e["ph"] == "C"]
        assert cs and cs[0]["name"] == "comm.bytes"
        assert cs[0]["args"]["value"] == 128
        ms = {e["name"]: e for e in events if e["ph"] == "M"}
        assert ms["process_name"]["args"]["name"] == "fedml_tpu"
        assert "thread_name" in ms


class TestJaxHooks:
    def test_compile_counter_agrees_with_engine_trace_count(self):
        """jax.compiles.agg_accum moves in lockstep with the bucketed
        engine's own accum_traces contract — same trace-time side effect,
        one surfaced through telemetry, one through the engine attr."""
        from fedml_tpu.core.aggregation.bucketed import BucketedAggregator

        before = tel.compile_count("agg_accum")
        eng = BucketedAggregator(bucket_size=4)
        tree = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
        for k in (4, 8, 11):  # shared executables: 11 pads its ragged tail
            pairs = [(1.0, tree) for _ in range(k)]
            eng.aggregate(pairs)
        assert tel.compile_count("agg_accum") - before == eng.accum_traces
        assert eng.accum_traces == 2  # first-bucket + steady-state, once

    def test_tracked_function_is_named_after_its_label(self):
        """jax.jit names a program after the function it is handed: a
        tracked function lowers as jit_<label>, not as its own __name__."""
        import jax

        def run(x):
            return x + 1

        fn = jax.jit(tel.track_compiles(run, name="labelled_prog"))
        assert "@jit_labelled_prog" in fn.lower(jnp.zeros((2,))).as_text()
        unnamed = tel.track_compiles(run)
        assert unnamed.__name__ == "run"  # no label: the function's own name stays

    def test_record_transfer_books_both_directions(self):
        from fedml_tpu.utils.pytree import tree_from_numpy, tree_to_numpy

        t = tel.get_telemetry()
        h2d0 = t.counter(tel.H2D_BYTES).value
        d2h0 = t.counter(tel.D2H_BYTES).value
        host = {"w": np.ones((8, 4), np.float32)}
        dev = tree_from_numpy(host)
        back = tree_to_numpy(dev)
        np.testing.assert_allclose(back["w"], host["w"])
        assert t.counter(tel.H2D_BYTES).value - h2d0 == host["w"].nbytes
        assert t.counter(tel.D2H_BYTES).value - d2h0 == host["w"].nbytes

    def test_record_transfer_rejects_unknown_direction(self):
        with pytest.raises(ValueError):
            tel.record_transfer("sideways", 1)


class TestDisabledPath:
    def test_disabled_span_is_shared_noop(self):
        t = Telemetry(enabled=False)
        a, b = t.span("x"), t.span("y", k=1)
        assert a is b  # the shared handle: no per-call allocation
        with a:
            pass
        assert t.snapshot()["spans"] == []

    def test_disabled_span_under_1us(self):
        # the contract bench.py's --trace overhead guard also enforces
        assert tel.disabled_span_overhead_ns() < 1000.0


class TestRoundLifecycle:
    def test_sp_fedavg_round_emits_nested_span_lifecycle(self):
        """A full sp FedAvg round emits sample -> client_train xK ->
        aggregate -> eval, all nested under fedavg.round, in start order."""
        import fedml_tpu as fedml
        from fedml_tpu.arguments import default_config

        t = tel.get_telemetry()
        was = t.enabled
        t.set_enabled(True)
        t.reset()
        try:
            args = default_config(
                "simulation",
                backend="sp",
                model="lr",
                federated_optimizer="FedAvg",
                comm_round=2,
                client_num_in_total=4,
                client_num_per_round=2,
                epochs=1,
                batch_size=16,
                frequency_of_the_test=1,
            )
            args = fedml.init(args)
            device = fedml.device.get_device(args)
            dataset, output_dim = fedml.data.load(args)
            model_obj = fedml.model.create(args, output_dim)
            fedml.FedMLRunner(args, device, dataset, model_obj).run()
            spans = t.snapshot()["spans"]
        finally:
            t.reset()
            t.set_enabled(was)

        rounds = [s for s in spans if s["name"] == "fedavg.round"]
        assert len(rounds) == 2
        for rnd in rounds:
            r = rnd["attrs"]["round"]
            children = [s for s in spans if s["parent_seq"] == rnd["seq"]]
            # snapshot is start-ordered: the lifecycle reads off directly
            assert [c["name"] for c in children] == [
                "fedavg.sample",
                "fedavg.client_train",
                "fedavg.client_train",
                "fedavg.aggregate",
                "fedavg.eval",
            ]
            assert all(c["attrs"]["round"] == r for c in children)
            assert all(c["depth"] == rnd["depth"] + 1 for c in children)
            agg = children[3]
            assert agg["attrs"]["k"] == 2
            # the engine's per-bucket spans nest under fedavg.aggregate
            buckets = [s for s in spans if s["parent_seq"] == agg["seq"]
                       and s["name"] == "agg.aggregate"]
            assert buckets


class TestTimingLint:
    def test_no_unmarked_wall_clock_durations(self, capsys):
        """tools/check_timing.py: every time.time() under fedml_tpu/ carries
        a `# wall-clock ok: <reason>` marker (durations use telemetry)."""
        spec = importlib.util.spec_from_file_location(
            "check_timing", os.path.join(_REPO, "tools", "check_timing.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        rc = mod.main()
        assert rc == 0, capsys.readouterr().out

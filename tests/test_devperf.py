"""Devperf (ISSUE 17): compiled-program registry capture, MFU fold parity
with bench's published arithmetic, the HBM sampler's thread hygiene, the
perf_report attribution invariant, and the mfu_collapse alert drill.

The capture tests run on a REAL jitted function: the AOT
``lower().compile()`` the wrapper performs must BE the one trace the jit
dispatcher would have spent (``jax.compiles.*`` stays at 1 across repeated
instrumented calls) — the zero-recompile contract every hot loop relies on.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import pytest

import bench
from fedml_tpu.core import telemetry as tel
from fedml_tpu.core.distributed import device_specs
from fedml_tpu.core.telemetry import devperf, flight_recorder, slo, tsdb
from tools import perf_report


def _instrumented_matmul(label, size=64, **kw):
    body = jax.jit(tel.track_compiles(
        lambda x: (x @ x).sum(), name=label))
    return (devperf.instrument(body, label, **kw),
            jnp.ones((size, size), jnp.float32))


# ---------------------------------------------------------------------------
# registry capture + zero-recompile
# ---------------------------------------------------------------------------

class TestInstrument:
    def test_capture_on_real_jitted_fn_zero_recompile(self):
        t = tel.get_telemetry()
        was = t.enabled
        t.set_enabled(True)
        try:
            fn, x = _instrumented_matmul("t_capture")
            vals = [float(fn(x)) for _ in range(4)]
            assert all(v == vals[0] for v in vals)
            # the AOT capture consumed the ONE trace jit would have spent
            assert tel.compile_count("t_capture") == 1
        finally:
            t.set_enabled(was)
        rec = devperf.get_registry().snapshot()["programs"]["t_capture"]
        assert rec["captured"] and rec["aot"]
        assert rec["flops_xla"] and rec["flops_xla"] > 0
        assert rec["bytes_accessed"] and rec["bytes_accessed"] > 0
        assert rec["op_intensity"] == pytest.approx(
            rec["flops_xla"] / rec["bytes_accessed"])
        # the CPU is not in the peak table: no invented peak, so no MFU
        # denominator and no roofline verdict
        assert rec["peak_flops_per_sec"] is None
        assert rec["roofline_verdict"] is None
        assert rec["flops_source"] == devperf.FLOPS_SOURCE_XLA

    def test_known_device_kind_gets_peak_and_verdict(self, monkeypatch):
        monkeypatch.setattr(devperf, "_device_kind", lambda: "TPU v5 lite")
        fn, x = _instrumented_matmul("t_known")
        float(fn(x))
        rec = devperf.get_registry().snapshot()["programs"]["t_known"]
        assert rec["peak_flops_per_sec"] == pytest.approx(197e12)
        assert rec["roofline_verdict"] in (devperf.VERDICT_COMPUTE,
                                           devperf.VERDICT_BANDWIDTH)

    def test_signature_drift_raises_instead_of_rejitting(self):
        """One wrapper serves one signature: a call the captured executable
        cannot take surfaces, it is not re-dispatched through jit (a second,
        unrecorded compile that would also mask a sharding mismatch)."""
        fn, x = _instrumented_matmul("t_drift")
        float(fn(x))
        with pytest.raises((TypeError, ValueError)):
            fn(jnp.ones((3, 5), x.dtype))
        assert tel.compile_count("t_drift") <= 1

    def test_compile_error_surfaces(self):
        def bad(x):
            raise RuntimeError("boom at trace time")

        fn = devperf.instrument(jax.jit(bad), "t_bad")
        with pytest.raises(RuntimeError, match="boom at trace time"):
            fn(jnp.ones((2,)))

    def test_disabled_returns_fn_unchanged(self, monkeypatch):
        monkeypatch.setenv("FEDML_DEVPERF", "0")
        f = jax.jit(lambda x: x + 1)
        assert devperf.instrument(f, "t_disabled") is f
        assert devperf.observe_step("t_disabled", 1.0) is None
        assert devperf.start_hbm_sampler() is None

    def test_caller_hint_beats_cost_analysis(self, monkeypatch):
        monkeypatch.setattr(devperf, "_device_kind", lambda: "TPU v5 lite")
        fn, x = _instrumented_matmul("t_hint", flops_hint=123.0)
        float(fn(x))
        rec = devperf.get_registry().snapshot()["programs"]["t_hint"]
        assert rec["flops_source"] == devperf.FLOPS_SOURCE_ANALYTIC
        mfu = devperf.observe_step("t_hint", 0.5)
        assert mfu == pytest.approx(
            (123.0 / 0.5) / rec["peak_flops_per_sec"])


# ---------------------------------------------------------------------------
# MFU arithmetic parity with bench's published pipeline
# ---------------------------------------------------------------------------

class TestMfuParity:
    def test_fold_matches_bench_mfu_from_rate(self):
        """The registry fold and ``bench._mfu_from_rate`` are the SAME
        tokens/sec -> MFU arithmetic — the property the devperf_overhead
        bench stage guards end-to-end at 15%."""
        flops_per_token, tokens_per_step, steps, wall = 250.0, 512, 8, 0.4
        reg = devperf.get_registry()
        reg.register("t_parity", flops_per_token_hint=flops_per_token)
        reg.note_capture("t_parity", device_kind="TPU v5 lite",
                         flops_xla=None, bytes_accessed=None, memory=None,
                         aot=False)
        mfu = devperf.observe_step("t_parity", wall, steps=steps,
                                   tokens=steps * tokens_per_step)
        peak = device_specs.peak_flops_per_sec("TPU v5 lite")
        expected = bench._mfu_from_rate(
            tokens_per_sec=steps * tokens_per_step / wall,
            step_flops=flops_per_token * tokens_per_step,
            tokens_per_step=tokens_per_step,
            peak_flops_per_sec=peak)
        assert mfu == pytest.approx(expected, rel=1e-12)

    def test_peak_table_matches_bench_lookup(self):
        """bench's ``_chip_peak_tflops`` now IS device_specs (satellite 1):
        one table, no drift."""

        class _Dev:
            device_kind = "TPU v4"

        assert bench._chip_peak_tflops(_Dev(), 16) == pytest.approx(
            device_specs.peak_tflops("TPU v4", 16))
        assert device_specs.peak_tflops("v5p", 16) == pytest.approx(459.0)
        # a device outside the table has NO peak (callers then report no
        # MFU); an unlisted TPU is an error, not a default
        assert device_specs.peak_tflops("cpu", 16) is None
        assert device_specs.roofline_ridge_flops_per_byte("cpu") is None
        with pytest.raises(ValueError, match="TPU v9"):
            device_specs.peak_tflops("TPU v9", 16)
        assert bench._device_hbm_fallback("v5 lite") == 16 * 1024**3


# ---------------------------------------------------------------------------
# HBM sampler
# ---------------------------------------------------------------------------

class TestHbmSampler:
    def test_start_stop_without_thread_leak(self):
        stats = [("dev:0", {"bytes_in_use": 10.0, "peak_bytes_in_use": 12.0,
                            "bytes_limit": 100.0})]
        sampler = devperf.HbmSampler(interval_s=0.01, stats_fn=lambda: stats)
        sampler.start()
        sampler.start()  # idempotent
        assert sampler.running
        deadline = time.monotonic() + 5.0
        while sampler.samples < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sampler.samples >= 2
        sampler.stop()
        sampler.stop()  # idempotent
        assert not sampler.running
        assert all(t.name != "devperf-hbm" for t in threading.enumerate())
        hbm = devperf.get_registry().snapshot()["hbm"]
        assert hbm["dev:0"]["peak_bytes_in_use"] == pytest.approx(12.0)

    def test_sample_records_high_water_frac_gauge(self):
        store = tsdb.install()
        try:
            stats = [("dev:0", {"bytes_in_use": 10.0,
                                "peak_bytes_in_use": 30.0,
                                "bytes_limit": 100.0}),
                     ("dev:1", {"bytes_in_use": 50.0,
                                "peak_bytes_in_use": 80.0,
                                "bytes_limit": 100.0})]
            sampler = devperf.HbmSampler(interval_s=60.0,
                                         stats_fn=lambda: stats)
            assert sampler.sample_once() == 2
            # the gauge is the WORST device's high-water fraction
            assert store.last("devperf.hbm_high_water_frac") == \
                pytest.approx(0.8)
        finally:
            tsdb.reset()

    def test_prom_gauges_expose_hbm_and_programs(self):
        reg = devperf.get_registry()
        reg.register("t_prom", flops_hint=100.0)
        reg.note_capture("t_prom", device_kind="TPU v5 lite", flops_xla=None,
                         bytes_accessed=None, memory=None, aot=False)
        devperf.observe_step("t_prom", 0.5)
        reg.note_hbm("dev:0", {"bytes_in_use": 7.0, "peak_bytes_in_use": 9.0,
                               "bytes_limit": 10.0})
        gauges = {(name, tuple(sorted(labels.items())))
                  for name, labels, _v in devperf.prom_gauges()}
        assert ("device_mfu", (("program", "t_prom"),)) in gauges
        assert ("device_flops_per_sec", (("program", "t_prom"),)) in gauges
        assert ("device_hbm_bytes", (("device", "dev:0"),)) in gauges
        assert ("device_hbm_high_water_bytes", (("device", "dev:0"),)) in gauges


# ---------------------------------------------------------------------------
# round-time attribution (tools/perf_report.py)
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_buckets_sum_to_round_wall(self):
        spans = {
            "fedavg.round": 10.0,
            "client.train": 6.0,      # compute
            "client.compress": 2.0,   # comm
            "fedavg.sample": 0.5,     # host
            "fedavg.eval": 0.5,       # host
            "agg.bucket": 3.0,        # wrapper detail: NOT bucketed
        }
        report = perf_report.attribute(spans, None)
        b = report["buckets_s"]
        assert b["compute"] == pytest.approx(6.0)
        assert b["comm"] == pytest.approx(2.0)
        assert b["host"] == pytest.approx(1.0)
        assert b["idle"] == pytest.approx(1.0)
        assert sum(b.values()) == pytest.approx(report["round_wall_s"],
                                                rel=1e-9)
        assert "agg.bucket" in report["unattributed_spans"]
        # over-attribution clamps idle at zero instead of going negative
        spans["client.train"] = 12.0
        assert perf_report.attribute(spans, None)["buckets_s"]["idle"] == 0.0

    def test_parse_and_join_with_devperf_snapshot(self):
        prom_text = "\n".join([
            '# TYPE fedml_span_seconds_total counter',
            'fedml_span_seconds_total{span="fedavg.round"} 20.0',
            'fedml_span_seconds_total{span="client.train"} 14.0',
            'fedml_span_count_total{span="fedavg.round"} 4',
            'fedml_other_metric 7',
        ])
        spans = perf_report.parse_span_seconds(prom_text)
        assert spans == {"fedavg.round": 20.0, "client.train": 14.0}
        reg = devperf.get_registry()
        reg.register("llm_train", flops_hint=1e9)
        reg.note_capture("llm_train", device_kind="TPU v5 lite", flops_xla=None,
                         bytes_accessed=None, memory=None, aot=False)
        devperf.observe_step("llm_train", 14.0)
        report = perf_report.attribute(
            spans, devperf.snapshot(),
            span_counts=perf_report.parse_span_counts(prom_text))
        assert report["rounds"] == pytest.approx(4)
        (top,) = report["top_programs"]
        assert top["label"] == "llm_train"
        assert top["device_seconds"] == pytest.approx(14.0)
        text = perf_report.render_text(report)
        assert "llm_train" in text and "compute" in text


# ---------------------------------------------------------------------------
# mfu_collapse alert drill: chaos-throttled step -> pending -> firing
# ---------------------------------------------------------------------------

class TestMfuCollapseAlert:
    def test_throttled_step_fires_alert_with_one_snapshot(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("FEDML_FR_DIR", str(tmp_path / "fr"))
        store = tsdb.install()
        try:
            row = next(r for r in slo.DEFAULT_PACKS["engine"]
                       if r["name"] == "mfu_collapse")
            eng = slo.SLOEngine([slo.SLOSpec(**row)], store=store,
                                front="test")
            # a ~1e4-FLOP program against a >=50ms throttled wall sits far
            # under the pack's 1e-5 collapse floor vs a v5e's peak (the CPU
            # itself has no peak, hence no MFU to collapse)
            monkeypatch.setattr(devperf, "_device_kind", lambda: "TPU v5 lite")
            fn, x = _instrumented_matmul("t_chaos", size=16)
            with flight_recorder.installed(role="test"):
                for _ in range(4):
                    t0 = time.perf_counter()
                    float(fn(x))
                    time.sleep(0.05)  # the chaos throttle: device "stalled"
                    mfu = devperf.observe_step(
                        "t_chaos", time.perf_counter() - t0)
                    assert mfu is not None and mfu < 1e-6
                    eng.tick()
                st = eng.statusz()["slos"]["mfu_collapse"]
                assert st["state"] == slo.STATE_FIRING
                trans = [(t["from"], t["to"]) for t in eng.history]
                assert ("ok", "pending") in trans
                assert ("pending", "firing") in trans
                dumps = sorted((tmp_path / "fr").glob("fr_*.jsonl"))
                assert len(dumps) == 1, "exactly one auto-snapshot per firing"
            # instrumented chaos steps still never re-traced
            assert tel.compile_count("t_chaos") == 1
        finally:
            tsdb.reset()

    def test_hbm_high_water_breach_trips_pack_row(self):
        store = tsdb.install()
        try:
            row = next(r for r in slo.DEFAULT_PACKS["serving"]
                       if r["name"] == "hbm_high_water")
            eng = slo.SLOEngine([slo.SLOSpec(**row)], store=store,
                                front="test")
            stats = [("dev:0", {"bytes_in_use": 97.0,
                                "peak_bytes_in_use": 99.0,
                                "bytes_limit": 100.0})]
            sampler = devperf.HbmSampler(interval_s=60.0,
                                         stats_fn=lambda: stats)
            for _ in range(2):
                sampler.sample_once()
                eng.tick()
            assert eng.statusz()["slos"]["hbm_high_water"]["state"] == \
                slo.STATE_FIRING
        finally:
            tsdb.reset()

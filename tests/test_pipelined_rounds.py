"""Pipelined round execution (core/pipeline): executor semantics, the
link-cost micro-batch planner, engine parity, and the collapsed-pipeline
SLO alert.

The load-bearing property: ``PipelinedExecution`` in fold-at-arrival mode
must be BIT-EXACT with ``InProcessSequentialStrategy`` — same training
order (single train worker), same fold order (FIFO end to end), and the
async buffer's publish routing through the same bucketed ``engine
.aggregate`` the AlgFrameSink plain path uses.
"""

import threading
import time
import types

import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.core.pipeline import (
    MicroBatchPlan,
    PipelineError,
    PipelinedExecutor,
    StageSpec,
    even_micro_batches,
    plan_micro_batches,
)
from fedml_tpu.core.telemetry import netlink


@pytest.fixture(autouse=True)
def _clean_netlink():
    netlink.reset()
    yield
    netlink.reset()


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class TestPipelinedExecutor:
    def test_output_order_preserved(self):
        ex = PipelinedExecutor([
            StageSpec("a", lambda x: x + 1),
            StageSpec("b", lambda x: x * 10),
        ])
        report = ex.run(range(20))
        assert report.outputs == [(i + 1) * 10 for i in range(20)]
        assert [s.items for s in report.stages] == [20, 20]

    def test_stages_overlap(self):
        # two equal sleep stages: pipelined wall must beat the serial sum
        # and the measured overlap fraction must clear the bench floor
        dt = 0.02
        ex = PipelinedExecutor([
            StageSpec("sleep1", lambda x: (time.sleep(dt), x)[1]),
            StageSpec("sleep2", lambda x: (time.sleep(dt), x)[1]),
        ])
        report = ex.run(range(10))
        assert report.wall_s < report.serial_s
        assert report.overlap_frac >= 0.5

    def test_collapsed_pipeline_reports_zero_overlap(self):
        # one stage owns all the work: nothing to hide under anything, so
        # the achievable-overlap denominator vanishes and the report says 0
        ex = PipelinedExecutor([
            StageSpec("work", lambda x: (time.sleep(0.01), x)[1]),
            StageSpec("noop", lambda x: x),
        ])
        report = ex.run(range(6))
        assert report.overlap_frac < 0.2
        assert report.bottleneck == "work"

    def test_stage_error_propagates_without_hanging(self):
        def boom(x):
            if x == 3:
                raise ValueError("injected")
            return x

        ex = PipelinedExecutor([
            StageSpec("boom", boom),
            StageSpec("sink", lambda x: x),
        ])
        with pytest.raises(PipelineError) as ei:
            ex.run(range(50))
        assert ei.value.stage == "boom"
        assert isinstance(ei.value.cause, ValueError)

    def test_single_stage_and_empty_input(self):
        ex = PipelinedExecutor([StageSpec("only", lambda x: x * 2)])
        assert ex.run([1, 2, 3]).outputs == [2, 4, 6]
        report = ex.run([])
        assert report.outputs == []
        assert report.overlap_frac == 0.0

    def test_emits_pipeline_series(self):
        was = tel.get_telemetry().enabled
        tel.set_enabled(True)
        tel.reset()
        try:
            ex = PipelinedExecutor([StageSpec("a", lambda x: x)])
            ex.run(range(4))
            snap = tel.snapshot()
            counters = snap.get("counters", {})
            hists = snap.get("histograms", {})
            assert any("pipeline.items" in k for k in counters)
            for series in ("pipeline.stage_seconds", "pipeline.overlap_frac",
                           "pipeline.stage_stall_seconds", "pipeline.queue_depth"):
                assert any(series in k for k in hists), series
        finally:
            tel.reset()
            tel.set_enabled(was)  # not off: the next file of this worker gets the registry as it found it


# ---------------------------------------------------------------------------
# micro-batch planner
# ---------------------------------------------------------------------------

def _prime_link(src: int, dst: int, *, rtt_s: float, bw_bytes_s: float,
                n: int = 5) -> None:
    reg = netlink.get_registry()
    for _ in range(n):
        reg.observe_probe(src, dst, rtt_s, 0)  # rtt floor
    nbytes = int(bw_bytes_s * rtt_s)  # sized probes measure bandwidth
    for _ in range(n):
        reg.observe_probe(src, dst, rtt_s + 2.0 * nbytes / bw_bytes_s, nbytes)


class TestMicroBatchPlanner:
    def test_cold_model_falls_back(self):
        plan = plan_micro_batches(10_000, 1.0, src=1, dst=0, default_chunks=4)
        assert isinstance(plan, MicroBatchPlan)
        assert plan.reason == "low_confidence"
        assert plan.n_micro_batches == 4

    def test_balanced_link_sizes_from_measurements(self):
        # 10ms RTT, 1 MB/s: base ≈ 5ms per chunk, 100kB bulk ≈ 0.1s
        _prime_link(1, 0, rtt_s=0.010, bw_bytes_s=1e6)
        plan = plan_micro_batches(100_000, 1.0, src=1, dst=0, max_chunks=64)
        assert plan.reason == "balanced"
        assert plan.confidence >= 0.25
        # (compute 1.0 - bulk 0.1) / base 0.005 = 180 -> clamped to max
        assert plan.n_micro_batches == 64
        assert plan.chunk_nbytes * plan.n_micro_batches >= 100_000

    def test_bandwidth_bound_link_pins_small_m(self):
        _prime_link(1, 0, rtt_s=0.010, bw_bytes_s=1e4)  # 10 kB/s
        # 100kB upload = 10s of bulk against 0.5s compute: nothing can hide
        plan = plan_micro_batches(100_000, 0.5, src=1, dst=0)
        assert plan.reason == "bandwidth_bound"
        assert plan.n_micro_batches == 2

    def test_clamps_respected(self):
        _prime_link(1, 0, rtt_s=0.010, bw_bytes_s=1e6)
        plan = plan_micro_batches(100, 100.0, src=1, dst=0,
                                  min_chunks=2, max_chunks=6)
        assert 2 <= plan.n_micro_batches <= 6

    def test_even_micro_batches(self):
        assert even_micro_batches(12, 8) == 6
        assert even_micro_batches(8, 4) == 4
        assert even_micro_batches(7, 4) == 1  # prime batch: no even split
        assert even_micro_batches(1, 9) == 1


# ---------------------------------------------------------------------------
# engine parity: pipelined strategy vs the sequential reference
# ---------------------------------------------------------------------------

def _run_sp(optimizer: str, rounds: int = 2, **over):
    import fedml_tpu as fedml
    from fedml_tpu.arguments import default_config
    from fedml_tpu.simulation.sp.fedavg_api import FedAvgAPI

    args = default_config(
        "simulation", backend="sp", model="lr",
        federated_optimizer=optimizer, comm_round=rounds,
        client_num_in_total=4, client_num_per_round=2,
        epochs=1, batch_size=16, frequency_of_the_test=1, **over,
    )
    args = fedml.init(args)
    device = fedml.device.get_device(args)
    dataset, output_dim = fedml.data.load(args)
    model_obj = fedml.model.create(args, output_dim)
    api = FedAvgAPI(args, device, dataset, model_obj)
    api.train()
    return api


def _trees_equal(a, b) -> float:
    import jax

    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


class TestPipelinedStrategyParity:
    def test_fold_at_arrival_bit_exact_with_sequential(self):
        from fedml_tpu.core.pipeline import PipelinedBufferSink, PipelinedExecution

        seq = _run_sp("FedAvg")
        pipe = _run_sp("FedAvg", client_execution="pipelined")
        strategy, sink = pipe._build_execution()
        assert isinstance(strategy, PipelinedExecution)
        assert isinstance(sink, PipelinedBufferSink)  # plain FedAvg folds at arrival
        assert strategy.fold_at_arrival
        diff = _trees_equal(seq.model_trainer.get_model_params(),
                            pipe.model_trainer.get_model_params())
        assert diff == 0.0, f"pipelined fold-at-arrival drifted by {diff}"

    def test_structured_optimizer_routes_to_pairs_mode_bit_exact(self):
        from fedml_tpu.core.engine import AlgFrameSink
        from fedml_tpu.core.pipeline import PipelinedExecution

        seq = _run_sp("SCAFFOLD")
        pipe = _run_sp("SCAFFOLD", client_execution="pipelined")
        strategy, sink = pipe._build_execution()
        assert isinstance(strategy, PipelinedExecution)
        assert not strategy.fold_at_arrival  # structured payloads: pairs mode
        assert isinstance(sink, AlgFrameSink)
        diff = _trees_equal(seq.model_trainer.get_model_params(),
                            pipe.model_trainer.get_model_params())
        assert diff == 0.0, f"pipelined pairs mode drifted by {diff}"

    def test_strategy_records_plan_and_report(self):
        pipe = _run_sp("FedAvg", client_execution="pipelined")
        strategy, _ = pipe._build_execution()
        # a fresh strategy has no report; the one the engine ran does — dig
        # it out of the api's engine run via a 1-round re-run
        api_strategy = None

        orig = pipe._build_execution

        def capture():
            nonlocal api_strategy
            api_strategy, sink = orig()
            return api_strategy, sink

        pipe._build_execution = capture
        pipe.args.comm_round = 1
        pipe.train()
        assert api_strategy.last_report is not None
        assert api_strategy.last_plan is not None
        assert api_strategy.last_report.outputs is not None
        assert [s.name for s in api_strategy.last_report.stages] == [
            "train", "compress", "uplink", "fold"]


# ---------------------------------------------------------------------------
# collapsed pipeline fires the SLO alert
# ---------------------------------------------------------------------------

class TestCollapsedPipelineAlert:
    def test_zero_overlap_fires_pipeline_overlap_frac(self):
        from fedml_tpu.core.telemetry import slo

        was = tel.get_telemetry().enabled
        tel.set_enabled(True)
        tel.reset()
        slo.reset()
        args = types.SimpleNamespace()
        engine = slo.activate(args, front="engine")
        assert engine is not None
        try:
            ex = PipelinedExecutor([
                StageSpec("work", lambda x: (time.sleep(0.005), x)[1]),
                StageSpec("noop", lambda x: x),
            ])
            ex.run(range(6))  # overlap_frac ≈ 0 lands in the tsdb mirror
            transitions = []
            for _ in range(3):
                transitions += engine.tick()
            overlap = [t for t in transitions if t["slo"] == "pipeline_overlap_frac"]
            assert overlap, f"no pipeline_overlap_frac transition in {transitions}"
            assert overlap[-1]["to"] == "firing"
            # the rest of the pack saw no data and must hold its tongue
            assert not any(t["slo"] == "pipeline_stage_stall_p99_seconds"
                           for t in transitions)
        finally:
            slo.deactivate(engine)
            slo.reset()
            tel.reset()
            tel.set_enabled(was)  # not off: the next file of this worker gets the registry as it found it

    def test_healthy_overlap_does_not_alert(self):
        from fedml_tpu.core.telemetry import slo

        was = tel.get_telemetry().enabled
        tel.set_enabled(True)
        tel.reset()
        slo.reset()
        engine = slo.activate(types.SimpleNamespace(), front="engine")
        try:
            dt = 0.01
            ex = PipelinedExecutor([
                StageSpec("a", lambda x: (time.sleep(dt), x)[1]),
                StageSpec("b", lambda x: (time.sleep(dt), x)[1]),
            ])
            ex.run(range(8))
            transitions = []
            for _ in range(3):
                transitions += engine.tick()
            assert not any(t["slo"] == "pipeline_overlap_frac" for t in transitions)
        finally:
            slo.deactivate(engine)
            slo.reset()
            tel.reset()
            tel.set_enabled(was)  # not off: the next file of this worker gets the registry as it found it

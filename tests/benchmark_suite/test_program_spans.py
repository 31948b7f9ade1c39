"""The readers of the program's own spans (benchmark/program_spans.py and the
metrics built on it): on a traced run of each tiny cell the computing function
gives a finite number from spans inside the measured window, ``read`` gives
nothing without a chip, and a program that has no such spans gives nothing
rather than an error."""

import math
import os
import time

import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402
import program_spans  # noqa: E402
from fedml_tpu.core import telemetry as tel  # noqa: E402

SERVE_SPAN_METRICS = ["serve_queue_wait_p95_ms", "serve_admit_p95_ms", "serve_entry_self_p95_ms",
                      "serve_loop_admit_pct", "serve_loop_host_pct", "serve_decode_batch_mean"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixture_root.make_root(tmp_path_factory.mktemp("bench_spans"))


def _traced_run(root, workload):
    """What harness.run_cell hands the readers: the driver's run, with its ctx."""
    cell = harness.Cell(root, workload)
    harness.place_compile_cache(root)
    ctx = harness.Ctx(cell, seed=2**31 + 11, seconds=2.0, trace=True,
                      t_process_start=time.perf_counter(), allow_cpu=True)
    registry = tel.get_telemetry()
    was = registry.enabled
    registry.set_enabled(True)  # on by default; an earlier file of this worker may have left it off
    try:
        run = cell.driver().run(ctx)
    finally:
        registry.set_enabled(was)
    run["ctx"] = ctx
    assert run["verdict"].correct
    return cell, run


@pytest.fixture(scope="module")
def chat(root):
    return _traced_run(root, "tiny_chat")


@pytest.fixture(scope="module")
def lora(root):
    return _traced_run(root, "tiny_lora")


def _module(cell, name):
    return harness.load_module(os.path.join(cell.bench_dir, "metrics", name + ".py"))


@pytest.mark.parametrize("name", SERVE_SPAN_METRICS)
def test_serving_span_metric_computes_on_the_tiny_chat_run(chat, name):
    cell, run = chat
    assert name in [m["name"] for m in cell.per_layer()]
    mod = _module(cell, name)
    value = mod.value(run)
    assert value is not None and math.isfinite(value) and value >= 0
    if name.endswith("_pct"):
        assert value <= 100.0
    assert mod.read(run) is None  # no chip: a time here would not be a device-run number


def test_serving_spans_are_those_of_the_window_and_join_on_request_id(chat):
    _, run = chat
    w = run["window"]
    queued = program_spans.spans(run, "serving.request.queue")
    assert len(queued) == w["requests"]  # warm-up requests were enqueued before t_start
    assert len(program_spans.spans(run, "serving.request.queue", in_window=False)) > len(queued)
    assert all(w["t_start"] <= s["start_s"] <= w["t_close"] for s in queued)
    whole = program_spans.by_request(run, "serving.endpoint.predict")
    waited = program_spans.by_request(run, "serving.predict.wait", in_window=False)
    assert len(whole) == w["requests"] and set(whole) <= set(waited)
    assert all(whole[r] >= waited[r] for r in whole)
    # queue + admit of a request is the engine's TTFT: never more than the client saw
    admit = program_spans.by_request(run, "serving.request.admit", in_window=False)
    queue = program_spans.by_request(run, "serving.request.queue")
    assert set(queue) == set(whole)
    assert all(queue[r] + admit[r] <= whole[r] for r in queue)
    slots = [s["attrs"]["slots"] for s in program_spans.spans(run, "serving.cb.chunk")]
    assert slots and max(slots) <= w["slots_total"]


def test_train_dispatch_reads_the_steps_of_the_measured_call(lora):
    cell, run = lora
    mod = _module(cell, "train_dispatch_ms")
    value = mod.value(run)
    assert value is not None and math.isfinite(value) and value > 0
    assert mod.read(run) is None
    steps = program_spans.spans(run, "llm.train.step")
    assert len(steps) == run["window"]["steps"]  # not the check, timing or traced calls' steps
    assert [s["attrs"]["step"] for s in steps] == list(range(len(steps)))
    assert len(program_spans.spans(run, "llm.train.step", in_window=False)) > len(steps)
    assert len(program_spans.spans(run, "llm.train.sync")) == 1


def test_train_dispatch_reads_the_head_of_the_call_not_the_wait_for_the_device(lora):
    """Past the runtime's bound on steps in flight a dispatch waits one device
    step: those spans are the device's time and stay out of the host's metric."""
    cell, run = lora
    mod = _module(cell, "train_dispatch_ms")
    ms = 1_000_000
    durs = [2 * ms] * 32 + [400 * ms] * 64  # the chip's picture: 32 steps go out unhindered, then back-pressure
    records, t = [], 0
    for step, dur in enumerate(durs):
        records.append({"name": "llm.train.step", "t0_ns": t, "dur_ns": dur, "attrs": {"step": step}})
        t += dur
    fake = {"window": {"t0": 0.0, "t1": t / 1e9 + 1.0},
            program_spans.SNAPSHOT_KEY: {"epoch_perf_ns": 0, "spans": records}}
    assert mod.value(fake) == pytest.approx(2.0)
    assert program_spans.percentile_ms(program_spans.durations(fake, "llm.train.step"), 50.0) == pytest.approx(400.0)
    short = dict(fake, **{program_spans.SNAPSHOT_KEY: {"epoch_perf_ns": 0, "spans": records[:10]}})
    assert mod.value(short) == pytest.approx(2.0)  # a call shorter than the head: what there is


def test_admit_device_share_matches_program_names_by_prefix(chat):
    cell, _ = chat
    mod = _module(cell, "serve_admit_device_pct")
    mods = {"jit_paged_step(1234)": (9, 4.0), "jit_prefill(77)": (3, 0.25), "jit_paged_admit": (5, 0.5),
            "jit_paged_suffix_prefill(5)": (2, 0.125), "jit_paged_gather(6)": (2, 0.125)}
    assert mod.value(mods, busy_s=5.0) == pytest.approx(20.0)
    assert mod.value({"jit_run(1)": (9, 4.0), "jit_run(2)": (3, 0.5)}, busy_s=5.0) is None  # no stable names
    assert mod.value(mods, busy_s=0.0) is None


def test_a_program_without_these_spans_gives_nothing(chat, monkeypatch):
    """The readers are laid over the parent's checkout too: a registry whose
    snapshot has no epoch (or that is off) yields None, never an error."""
    cell, run = chat
    old = dict(run, window=dict(run["window"]))
    old.pop(program_spans.SNAPSHOT_KEY)
    snap = tel.snapshot()
    snap.pop("epoch_perf_ns")
    monkeypatch.setattr(tel, "snapshot", lambda: snap)
    for name in SERVE_SPAN_METRICS:
        assert _module(cell, name).value(old) is None
    empty = dict(run, window=dict(run["window"], t_start=0.0, t_close=1e-9))
    for name in SERVE_SPAN_METRICS:
        assert _module(cell, name).value(empty) is None

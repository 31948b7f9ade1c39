"""The starvation ledger's readers (benchmark/idle_by_span.py and the metrics
built on it): the helper on a hand-made trace and snapshot, the anchor's check
of itself, each metric on a tiny traced run of a CPU (a number from the
computing function, nothing from ``read`` without a chip), and nothing at all
on a program that lacks the records (the parent's)."""

import math
import os
import time
import types

import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402
import idle_by_span  # noqa: E402
import program_spans  # noqa: E402
from fedml_tpu.core import telemetry as tel  # noqa: E402
from test_jamba_cell import CELL as JAMBA_CELL, TINY_CHAT, TINY_JAMBA, TINY_SERVE  # noqa: E402

LEDGER_METRICS = ["serve_device_starved_pct", "serve_starved_pct.no_work", "serve_starved_pct.collect",
                  "serve_starved_pct.launch", "serve_starved_pct.land", "serve_worker_off_cpu_pct",
                  "serve_lock_wait_pct"]
TRACE_METRICS = ["serve_idle_in_starved_pct", "serve_idle_unnamed_pct"]
PHASES = [n for n in LEDGER_METRICS if n.startswith("serve_starved_pct.")]
S = 1e9  # ns


def _metric(name):
    return harness.load_module(os.path.join(fixture_root.BENCH, "metrics", name + ".py"))


# ---- a hand-made trace and snapshot ---------------------------------------------------------------

EPOCH = 5_000_000_000       # the registry's epoch on the perf_counter clock, ns
T0, T1 = 7.0, 12.0          # the driver's perf_counter readings around bench:trace_window
WORKER, OTHER = 11, 22


def _rec(name, t0_s, t1_s, depth=0, tid=WORKER, **attrs):
    """A registry record from profiler seconds (the trace starts at 1.0 = perf 7.0)."""
    perf_ns = (t0_s - 1.0 + T0) * S
    return {"name": name, "t0_ns": int(perf_ns - EPOCH), "dur_ns": int((t1_s - t0_s) * S), "depth": depth,
            "tid": tid, "attrs": attrs, "seq": 0, "parent_seq": None}


def _hand_made(window_end_s=6.0, keep_bounds=True):
    """Five traced seconds, busy but for two gaps: 2.0-2.3 s (the worker inside
    a prefill's launch, the ledger starved in ``launch`` from 2.05 with an
    unseen head of 0.05) and 4.0-4.1 s (the worker in its iteration, no child
    span open, no piece)."""
    red = harness.load_module(os.path.join(fixture_root.BENCH, "trace", "reduce.py"))
    ev = red.Event
    ops = [ev("fusion", 1.0 * S, 1.0 * S), ev("fusion", 2.3 * S, 1.7 * S), ev("fusion", 4.1 * S, 1.9 * S)]
    raw = red.Trace({"/device:TPU:0": ops}, {}, [ev("trace_window", 1.0 * S, (window_end_s - 1.0) * S),
                                                 ev("client_wait", 1.9 * S, 0.5 * S)])
    spans = [
        _rec("serving.engine.iteration", 0.5, 6.5, starved_ns=250_000_000, cpu_ns=int(1.5 * S),
             blocked_ns=int(3.0 * S), lock_wait_ns=int(0.06 * S)),
        _rec("serving.engine.collect_wave", 0.6, 1.8, depth=1, n=1, deferred=0),
        _rec("serving.paged.admit_wave", 1.9, 2.6, depth=1, n=1),
        _rec("serving.cb.prefill", 1.95, 2.5, depth=2, request_id="r"),
        _rec("serving.cb.chunk", 2.6, 3.9, depth=1, slots=3),
        _rec("serving.cb.chunk.sync", 2.7, 3.9, depth=2),
        _rec("serving.paged.first_token_wait", 4.2, 6.4, depth=1, request_id="r"),
        _rec("serving.device.starved", 2.05, 2.3, phase="launch", first=True, unseen_ns=50_000_000),
        _rec("serving.request.queue", 0.0, 6.4, request_id="r"),            # the worker records it: not its activity
        _rec("serving.http.request", 3.95, 4.15, tid=OTHER, request_id="r"),  # another thread's: not the worker's
    ]
    logged = []
    ctx = types.SimpleNamespace(tracer=types.SimpleNamespace(reduce=red), peaks={"any": 1}, log=logged.append)
    window = {"t_start": 0.0, "t_close": 100.0}
    if keep_bounds:
        window.update(trace_t0=T0, trace_t1=T1)
    run = {"window": window, "trace": {"raw": raw, "lo": 1.0 * S, "hi": 6.0 * S}, "ctx": ctx,
           program_spans.SNAPSHOT_KEY: {"epoch_perf_ns": EPOCH, "spans": spans}}
    return run, logged


def test_each_idle_gap_is_intersected_with_the_workers_deepest_span_and_the_starved_pieces():
    run, logged = _hand_made()
    out = idle_by_span.report(run)
    assert out["idle_s"] == pytest.approx(0.4) and out["window_s"] == pytest.approx(5.0)
    assert out["offset_ns"] == pytest.approx(1.0 * S - T0 * S)
    assert {k: round(v, 6) for k, v in out["by_name"].items()} == {"serving.cb.prefill": 0.3,
                                                                  "serving.engine.iteration": 0.1}
    assert {k: round(v, 6) for k, v in out["in_starved"].items()} == {"launch": 0.3}  # 0.25 seen + the 0.05 head
    assert [round(g["seconds"], 6) for g in out["longest"]] == [0.3, 0.1]
    assert list(out["longest"][0]["worker"]) == ["serving.cb.prefill"] and out["longest"][1]["starved"] == {}
    assert idle_by_span.idle_in_starved_pct(run) == pytest.approx(75.0)
    assert idle_by_span.idle_unnamed_pct(run) == pytest.approx(25.0)
    assert _metric("serve_idle_in_starved_pct").read(run) == pytest.approx(75.0)
    # the ledger's own share, over the traced five seconds: the piece, not its head
    assert idle_by_span.starved_pct(run) == pytest.approx(5.0) == idle_by_span.starved_pct(run, "launch")
    assert [idle_by_span.starved_pct(run, p) for p in ("no_work", "collect", "land")] == [0.0, 0.0, 0.0]
    # logged once however many readers ask, the gaps' labels the WORKER's (the dispatcher's client_wait is not one)
    assert len(logged) == 4 and "serving.cb.prefill 0.3000" in logged[1] and "gap 300.0 ms at 1.000 s" in logged[2]
    assert not any("client_wait" in line for line in logged)


def test_a_gap_half_under_a_child_span_is_split_not_looked_up_at_its_middle():
    segs = idle_by_span.flatten([(0, 100, 0, "iteration"), (10, 40, 1, "wave"), (20, 30, 2, "prefill"), (40, 60, 1, "chunk")])
    assert segs == [(0, 10, "iteration"), (10, 20, "wave"), (20, 30, "prefill"), (30, 40, "wave"), (40, 60, "chunk"),
                    (60, 100, "iteration")]
    got = idle_by_span.overlap_by_name((25, 45), segs, [s[0] for s in segs])
    assert got == {"prefill": 5, "wave": 10, "chunk": 5}
    assert idle_by_span.overlap_by_name((90, 120), segs, [s[0] for s in segs]) == {"iteration": 10, idle_by_span.NOTHING: 20}
    assert idle_by_span.overlap_by_name((5, 8), [], []) == {idle_by_span.NOTHING: 3}


def test_the_anchor_checks_itself_by_the_other_end_and_refuses_five_milliseconds():
    assert idle_by_span.anchor_ns((1.0 * S, 6.0 * S), T0, T1) == pytest.approx(-6.0 * S)
    assert idle_by_span.anchor_ns((1.0 * S, 6.0 * S + 1.5e6), T0, T1) == pytest.approx(-6.0 * S)  # 1.5 ms: a late exit
    assert idle_by_span.anchor_ns((1.0 * S, 6.0 * S + 5e6), T0, T1) is None
    assert idle_by_span.anchor_ns(None, T0, T1) is None and idle_by_span.anchor_ns((1.0 * S, 6.0 * S), None, None) is None
    run, logged = _hand_made(window_end_s=6.005)
    assert idle_by_span.report(run) is None and logged == []
    assert idle_by_span.idle_in_starved_pct(run) is None and idle_by_span.idle_unnamed_pct(run) is None


def test_a_run_whose_driver_keeps_no_trace_t0_gives_none_and_reads_the_window():
    run, logged = _hand_made(keep_bounds=False)
    assert idle_by_span.report(run) is None and logged == []
    assert [_metric(n).value(run) for n in TRACE_METRICS] == [None, None]
    assert idle_by_span.bounds(run) == (0.0, 100.0)
    assert idle_by_span.starved_pct(run) == pytest.approx(0.25)  # the same piece over the window's 100 s


def test_idle_under_one_percent_of_the_traced_part_says_nothing():
    run, _ = _hand_made()
    red = run["ctx"].tracer.reduce
    run["trace"]["raw"] = red.Trace({"/device:TPU:0": [red.Event("fusion", 1.0 * S, 4.96 * S)]}, {},
                                    run["trace"]["raw"].host_spans)
    assert idle_by_span.report(run)["idle_s"] == pytest.approx(0.04)
    assert [_metric(n).value(run) for n in TRACE_METRICS] == [None, None]


def test_the_workers_account_is_a_share_of_its_passes():
    run, _ = _hand_made()
    assert idle_by_span.off_cpu_pct(run) == pytest.approx(100.0 * (6.0 - 1.5 - 3.0) / 6.0)
    assert idle_by_span.lock_wait_pct(run) == pytest.approx(1.0)


# ---- tiny traced runs on the CPU ------------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = fixture_root.make_root(tmp_path_factory.mktemp("bench_ledger"))
    fixture_root.add_cell(root, JAMBA_CELL, "tiny_jamba", TINY_JAMBA, "tiny_chat_ragged", TINY_CHAT, TINY_SERVE,
                          {"serve_latency_p95_ms", "serve_out_tokens_per_s"})
    return root


def _traced_run(root, workload):
    cell = harness.Cell(root, workload)
    harness.place_compile_cache(root)
    ctx = harness.Ctx(cell, seed=2**31 + 37, seconds=2.0, trace=True,
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
    return _traced_run(root, "tiny_chat")      # llm_serve's own measure: no trace_t0 on the window


@pytest.fixture(scope="module")
def jamba(root):
    return _traced_run(root, JAMBA_CELL)       # llm_serve_jamba's measure keeps trace_t0 / trace_t1


@pytest.mark.parametrize("name", LEDGER_METRICS)
def test_ledger_metric_computes_on_the_tiny_chat_run(chat, name):
    cell, run = chat
    assert name in [m["name"] for m in cell.per_layer()]
    mod = _metric(name)
    value = mod.value(run)
    assert value is not None and math.isfinite(value) and 0.0 <= value <= 100.0
    assert mod.read(run) is None  # no chip: a share of time here would not be a device-run number


def test_the_four_phases_add_up_to_the_starved_share_over_the_same_seconds(chat, jamba):
    for _, run in (chat, jamba):
        total = _metric("serve_device_starved_pct").value(run)
        assert sum(_metric(n).value(run) for n in PHASES) == pytest.approx(total, abs=1e-9)
        lo, hi = idle_by_span.bounds(run)
        assert all(lo <= p["lo_s"] <= p["hi_s"] <= hi and p["phase"] in idle_by_span.PHASES for p in idle_by_span.pieces(run))
    assert "trace_t0" not in chat[1]["window"] and idle_by_span.bounds(chat[1]) == program_spans.window_bounds(chat[1])
    w = jamba[1]["window"]
    assert idle_by_span.bounds(jamba[1]) == (w["trace_t0"], w["trace_t1"])


@pytest.mark.parametrize("name", TRACE_METRICS)
def test_trace_metric_lays_the_ledger_over_the_trace_where_the_driver_keeps_the_anchor(chat, jamba, name):
    mod = _metric(name)
    assert mod.value(chat[1]) is None          # no anchor: nothing, not an error
    _, run = jamba
    out = idle_by_span.report(run)
    assert out is not None and out["has_ledger"]
    assert sum(out["by_name"].values()) == pytest.approx(out["idle_s"], rel=1e-6)
    assert sum(out["in_starved"].values()) <= out["idle_s"] * (1 + 1e-9)
    assert set(out["in_starved"]) <= set(idle_by_span.PHASES)
    # the CPU's executor threads stand in for a device: mostly idle, so the shares exist
    value = mod.value(run)
    assert value is not None and 0.0 <= value <= 100.0
    assert mod.read(run) is None
    assert abs(out["offset_ns"] - (run["trace"]["lo"] - run["window"]["trace_t0"] * 1e9)) < 5e6


@pytest.mark.parametrize("name", LEDGER_METRICS + TRACE_METRICS)
def test_a_program_without_the_ledger_gives_nothing(jamba, monkeypatch, name):
    """The readers are laid over the parent's checkout too. Its registry has
    the loop's spans and no starved piece, no account on the iteration: every
    reader gives None, never an error; likewise a registry with no epoch."""
    _, run = jamba
    mod = _metric(name)
    snap = run[program_spans.SNAPSHOT_KEY]
    account = {"cpu_ns", "blocked_ns", "lock_wait_ns", "starved_ns"}
    parents = [dict(r, attrs={k: v for k, v in (r.get("attrs") or {}).items() if k not in account})
               for r in snap["spans"] if r["name"] not in ("serving.device.starved", "serving.engine.collect_wave")]
    old = {k: v for k, v in run.items() if k != idle_by_span.REPORT_KEY}
    old[program_spans.SNAPSHOT_KEY] = dict(snap, spans=parents)
    assert mod.value(old) is None
    bare = {k: v for k, v in old.items() if k != program_spans.SNAPSHOT_KEY}
    no_epoch = {k: v for k, v in tel.snapshot().items() if k != "epoch_perf_ns"}
    monkeypatch.setattr(tel, "snapshot", lambda: no_epoch)
    assert mod.value(bare) is None

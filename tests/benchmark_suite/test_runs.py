"""A whole run of each driver at a tiny size on the CPU (the harness's look
for a chip skipped): the last stdout line carries exactly the contract's keys,
the window counts no compile, the timed path agrees with the plain reference."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

import fixture_root

fixture_root.bench_imports()

import run as bench_run  # noqa: E402

E2E = {"tiny_lora": {"train_tokens_per_s", "setup_s"},
       "tiny_chat": {"serve_latency_p95_ms", "serve_out_tokens_per_s", "setup_s"}}
LAYER = {"tiny_lora": {"compiles_in_window.train", "train_step_device_ms", "device_idle_pct.train"},
         "tiny_chat": {"serve_ttft_p95_ms", "serve_prefix_hit_pct", "serve_tpot_p50_ms", "gen_lateness_p95_ms",
                       "serve_slot_occupancy_pct", "compiles_in_window.serve", "device_idle_pct.serve"}}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixture_root.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, trace, seed=2**31 + 77):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
                            root=root, allow_cpu=True)
    lines = buf.getvalue().strip().splitlines()
    assert rc == 0 and len(lines) == 1, "exactly one stdout line: the result"
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", ["tiny_lora", "tiny_chat"])
def test_untraced_run_prints_the_contract_line(root, cell):
    out = _run(root, cell, 0)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == E2E[cell]
    assert all(set(m) == {"value", "unit"} and m["value"] > 0 for m in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(r) == {"value", "limit"} and r["limit"] is not None for r in out["compared"].values())


@pytest.mark.parametrize("cell", ["tiny_lora", "tiny_chat"])
def test_traced_run_reports_the_per_layer_metrics(root, cell):
    out = _run(root, cell, 1)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "breakdown", "compared"]
    assert out["correct"] is True
    # rooflines and MFUs need the chip's peaks: on the CPU their readers return nothing
    assert set(out["metrics"]) == LAYER[cell]
    assert out["metrics"]["compiles_in_window." + ("train" if cell == "tiny_lora" else "serve")]["value"] == 0
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10 and len(out["breakdown"]["idle_gaps"]) <= 10
    assert not os.path.exists(os.path.join(root, ".bench_out", "trace"))   # the trace is not kept


def test_chat_run_serves_every_request_and_shares_prefix_pages(root):
    out = _run(root, "tiny_chat", 1, seed=5)
    assert out["attempted"] == 8 and out["failed"] == 0
    assert out["metrics"]["serve_prefix_hit_pct"]["value"] == 75.0
    assert out["compared"]["page_leaks"] == {"value": 0.0, "limit": 0}
    assert out["compared"]["failed_requests"] == {"value": 0.0, "limit": 0}

"""The harness is driven by data: a cell, a configuration, a traffic mix, a
driver and a per-layer metric added as NEW files (and BENCHMARK.json entries)
are found and run; nothing that exists is edited. A run with no TPU exits
non-zero and prints no result line."""

import json
import os
import subprocess
import sys

import pytest

import fixture_root

fixture_root.bench_imports()

import harness  # noqa: E402

DUMMY_DRIVER = '''
import compare

def run(ctx):
    v = compare.Verdict()
    v.add("answer_gap", abs(ctx.traffic["answer"] - 42), 0)
    return {"attempted": 1, "failed": 0, "verdict": v, "memory_peak_bytes": 123,
            "end_to_end": {"dummy_rate": 7.0 * ctx.config["width"], "setup_s": 0.5},
            "window": {"things": ctx.seed}, "trace": {"busy_s": 0.25, "window_s": 1.0,
                                                      "device_ops": [["op", 0.25]], "idle_gaps": [["no_span", 0.75]]}}
'''
DUMMY_METRIC = '''
def read(run):
    return float(run["window"]["things"]) if run["window"]["things"] else None
'''


@pytest.fixture()
def root(tmp_path):
    root = fixture_root.make_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "drivers", "dummy.py"), "w") as f:
        f.write(DUMMY_DRIVER)
    with open(os.path.join(bench, "metrics", "dummy_things.v2.py"), "w") as f:
        f.write(DUMMY_METRIC)
    fixture_root.add_cell(root, "dummy_cell", "dummy_cfg", {"name": "dummy_cfg", "width": 3},
                          "dummy_mix", {"kind": "none", "answer": 42}, {"driver": "dummy"}, set())
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["end_to_end"].append({"name": "dummy_rate", "unit": "things/s", "better": "higher", "bound": 0.01,
                            "source": "host_clock", "workloads": ["dummy_cell"]})
    b["per_layer"].append({"name": "dummy_things.v2", "unit": "count", "better": "higher",
                           "source": "program_counter", "layer": "Dummy", "moves": "dummy_rate"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return root


def test_a_cell_added_as_files_is_found_and_run(root):
    out = harness.run_cell(root, "dummy_cell", seed=9, seconds=1, trace=False,
                           t_process_start=0.0, allow_cpu=True)
    assert out["correct"] is True
    assert out["metrics"] == {"dummy_rate": {"value": 21.0, "unit": "things/s"},
                              "setup_s": {"value": 0.5, "unit": "s"}}
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "compared"]


def test_a_metric_added_as_a_file_is_read_in_the_cells_that_report_what_it_moves(root):
    out = harness.run_cell(root, "dummy_cell", seed=9, seconds=1, trace=True,
                           t_process_start=0.0, allow_cpu=True)
    assert out["metrics"] == {"dummy_things.v2": {"value": 9.0, "unit": "count"}}
    assert out["device"]["busy_s"] == 0.25 and out["device"]["window_s"] == 1.0
    assert list(out)[-2:] == ["breakdown", "compared"]
    cell = harness.Cell(root, "tiny_lora")
    assert "dummy_things.v2" not in [m["name"] for m in cell.per_layer()]


def test_a_reader_that_finds_nothing_leaves_its_metric_out(root):
    out = harness.run_cell(root, "dummy_cell", seed=0, seconds=1, trace=True,
                           t_process_start=0.0, allow_cpu=True)
    assert out["metrics"] == {}


def test_a_wrong_answer_is_not_correct(root):
    with open(os.path.join(root, "benchmark", "traffic", "dummy_mix.json"), "w") as f:
        json.dump({"kind": "none", "answer": 41}, f)
    out = harness.run_cell(root, "dummy_cell", seed=9, seconds=1, trace=False,
                           t_process_start=0.0, allow_cpu=True)
    assert out["correct"] is False and out["compared"]["answer_gap"] == {"value": 1.0, "limit": 0}


def test_unknown_cell_and_missing_files_are_errors(root):
    with pytest.raises(harness.HarnessError):
        harness.Cell(root, "no_such_cell")
    cell = harness.Cell(root, "dummy_cell")
    with pytest.raises(harness.HarnessError):
        cell.metric_reader("no_such_metric")


def test_cells_report_only_their_own_metrics():
    train = harness.Cell(fixture_root.REPO, "mistral7b_lora_pack2k")
    serve = harness.Cell(fixture_root.REPO, "internlm2_7b_chat_open")
    assert [m["name"] for m in train.end_to_end()] == ["train_tokens_per_s", "setup_s"]
    assert [m["name"] for m in serve.end_to_end()] == ["serve_latency_p95_ms", "serve_out_tokens_per_s", "setup_s"]
    t = {m["name"] for m in train.per_layer()}
    s = {m["name"] for m in serve.per_layer()}
    assert not (t & s) and "train_step_mfu" in t and "serve_step_mfu" in s
    for cell in (train, serve):
        for m in cell.per_layer():
            assert callable(cell.metric_reader(m["name"]))


def _run_py(cwd, *args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    r = _run_py(fixture_root.REPO, "benchmark/run.py", "--workload", "mistral7b_lora_pack2k",
                "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0", env_extra={"BENCH_RUN": "7"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_without_the_program_it_exits_nonzero_and_prints_no_result(root):
    # a directory that holds only BENCHMARK.json and the files under paths
    code = ("import sys; sys.path.insert(0, 'benchmark'); import run; "
            "sys.exit(run.main(['--workload', 'tiny_lora', '--seconds', '1'], root='.', allow_cpu=True))")
    r = _run_py(root, "-c", code)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "fedml_tpu" in r.stderr

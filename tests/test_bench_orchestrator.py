"""The bench orchestrator's isolation contract (VERDICT r3 item 1).

The r03 bench died because replica grandchildren kept HBM across stages.
The round-4 rearchitecture guarantees: a stage that exceeds its budget is
SIGKILLed as a whole process GROUP (grandchildren included), its partial
stderr survives into the failure record, and a healthy stage's one JSON
line is parsed. These tests drive bench._spawn_stage through its test seam
on CPU — the only way to verify the contract without a chip.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import sys
import textwrap
import time

import bench
import pytest


@pytest.fixture
def _restore_signals():
    """bench.main() installs SIGTERM/SIGINT handlers; a pytest process must
    get its own back or Ctrl-C/outer timeouts bypass normal teardown."""
    saved = {sig: signal.getsignal(sig) for sig in (signal.SIGINT, signal.SIGTERM)}
    yield
    for sig, handler in saved.items():
        signal.signal(sig, handler)


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:  # pragma: no cover
        return True


def test_stage_timeout_kills_grandchildren(tmp_path):
    """A stage spawning its own child (the serving stage's replica shape):
    on budget exhaustion BOTH processes must die — the child holds the
    chip's memory in the real topology."""
    pid_file = tmp_path / "child.pid"
    script = textwrap.dedent(f"""
        import subprocess, sys, time
        child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(300)"])
        import os as _os
        open({str(pid_file)!r} + ".tmp", "w").write(str(child.pid))
        _os.replace({str(pid_file)!r} + ".tmp", {str(pid_file)!r})
        print("stage spawned child", child.pid, file=sys.stderr, flush=True)
        time.sleep(300)
    """)
    result, err = bench._spawn_stage(
        "fake", budget_s=3, argv=[sys.executable, "-c", script]
    )
    assert result is None
    assert err is not None and "timeout after 3s" in err
    # partial stderr made it into the failure record
    assert "stage spawned child" in err
    child_pid = int(pid_file.read_text())
    deadline = time.time() + 5
    while time.time() < deadline and _alive(child_pid):
        time.sleep(0.1)
    assert not _alive(child_pid), "grandchild survived the stage killpg"


def test_stage_failure_summarizes_error_tail():
    script = "import sys; print('boom', file=sys.stderr); raise RuntimeError('RESOURCE_EXHAUSTED: fake')"
    result, err = bench._spawn_stage(
        "fake", budget_s=30, argv=[sys.executable, "-c", script]
    )
    assert result is None
    assert "RESOURCE_EXHAUSTED" in err


def test_stage_success_parses_last_json_line():
    script = "print('noise'); print('{\"metric\": 1.5}')"
    result, err = bench._spawn_stage(
        "fake", budget_s=30, argv=[sys.executable, "-c", script]
    )
    assert err is None
    assert result == {"metric": 1.5}


def test_sigterm_forwarding_kills_inflight_stage(tmp_path):
    """bench_watch's outer timeout signals only the orchestrator; the
    handler must forward death to the stage's process group."""
    pid_file = tmp_path / "stage.pid"
    script = textwrap.dedent(f"""
        import os, time
        open({str(pid_file)!r} + ".tmp", "w").write(str(os.getpid()))
        os.replace({str(pid_file)!r} + ".tmp", {str(pid_file)!r})
        time.sleep(300)
    """)
    import threading

    # run _spawn_stage in a thread, then deliver the handler by hand the way
    # the signal would (raising SystemExit in the main thread is the
    # handler's job; here we only verify the group kill side effect)
    done = threading.Event()

    def run():
        bench._spawn_stage("fake", budget_s=30, argv=[sys.executable, "-c", script])
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    deadline = time.time() + 10
    while time.time() < deadline and not pid_file.exists():
        time.sleep(0.05)
    stage_pid = int(pid_file.read_text())
    assert bench._CURRENT_STAGE_PROC is not None
    bench._kill_stage_group(bench._CURRENT_STAGE_PROC)
    assert done.wait(timeout=10)
    assert not _alive(stage_pid)


# --- main() merge/artifact/rc contract (runs exactly once per capture) -------


def _canned_stages(monkeypatch, tmp_path, results):
    """Patch the orchestrator's seams: canned stage results, artifacts under
    tmp_path."""
    monkeypatch.setattr(bench, "_REPO", str(tmp_path))

    def fake_spawn(name, budget_s, argv=None, env=None):
        return results.get(name, (None, f"{name}: canned failure"))

    monkeypatch.setattr(bench, "_spawn_stage", fake_spawn)


_LLM_OK = ({
    "tokens_per_sec": 50000.0, "mfu": 0.41, "attention_impl": "pallas",
    "step_flops": 1e12, "n_params": 268000000, "device": "TPU v5 lite",
    "shape": {"d_model": 1024, "n_layers": 16, "n_heads": 16, "d_ff": 2752,
              "vocab": 32000, "seq": 1024, "bs": 8},
    "remat": False,
    "flash_blocks": "128x128",
}, None)


def test_main_happy_path_merges_and_exits_zero(monkeypatch, tmp_path, capsys, _restore_signals):
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": _LLM_OK,
        "llm_xla": ({"tokens_per_sec": 30000.0, "mfu": 0.23, "remat": False,
                     "attention_impl": "xla", "n_params": 268000000,
                     "shape": _LLM_OK[0]["shape"], "device": "TPU v5 lite",
                     "step_flops": 1e12}, None),
        "decode": ({"decode_tokens_per_sec": 900.0, "bs": 4, "new": 128}, None),
        "decode_int8": ({"decode_tokens_per_sec": 1500.0, "bs": 4, "new": 128,
                         "weight_quant": "int8"}, None),
        "resnet": ({"steps_per_sec": 20.0, "mfu": 0.2, "bs": 128}, None),
        "attn_micro": ({"fwd_bwd_ms": {"flash_128x128": 9.0,
                                       "flash_256x256": 7.5,
                                       "xla_einsum": 8.0},
                        "best_flash": "flash_256x256",
                        "best_vs_128x128": 1.2,
                        "best_vs_einsum": 1.067}, None),
        "memplan": ({"plan_bytes_per_device": 7_500_000_000,
                     "device_bytes_limit": 16 * 2**30,
                     "device_bytes_in_use": 0, "device_kind": "TPU v5 lite",
                     "memory_plan_validated": True}, None),
        "cpu_llm": ({"cpu_llm_tokens_per_sec": 100.0}, None),
        "cpu_resnet": ({"cpu_resnet_images_per_sec": 80.0}, None),
        "agg": ({"agg_clients_per_sec": {"resnet56": {"8": 120.0, "64": 240.0},
                                         "llm268m": {"8": 3.0}},
                 "agg_hbm_gbps": {"resnet56": {"8": 1.5, "64": 2.8},
                                  "llm268m": {"8": 40.0}},
                 "agg_bucket_size": 16,
                 "agg_cohorts": [8, 64, 257, 512],
                 "agg_pytrees": {"resnet56": {"n_params": 861620,
                                              "client_dtype": "float32",
                                              "geometry": "flagship"}},
                 "agg_accum_traces": 4,
                 "device": "TPU v5 lite"}, None),
        "agg_sharded": ({"agg_sharded_hbm_ratio": 0.125,
                         "agg_sharded_clients_per_sec": 12.0,
                         "agg_sharded_overlap_efficiency": 1.4,
                         "agg_sharded_traces": 2,
                         "agg_round_traces": 1,
                         "device": "TPU v5 lite"}, None),
        "async_rounds": ({"async_rounds_per_hr": {"1000": 350000.0,
                                                  "10000": 340000.0,
                                                  "100000": 330000.0},
                          "async_flatness_ratio": 1.06,
                          "async_publish_k": 32,
                          "async_parity_bit_exact": True,
                          "device": "TPU v5 lite"}, None),
        "placement_search": ({"placement_plan": {
                                  "async_fedbuff": {"fingerprint": "abc123",
                                                    "strategy": "vmapped_megabatch",
                                                    "publish_k": 8}},
                              "placement_speedup": {"async_fedbuff": 4.07,
                                                    "sync_agg": 3.14},
                              "placement_plan_files": [
                                  "PLACEMENT_PLAN_async_fedbuff.json"],
                              "device": "TPU v5 lite"}, None),
        "wan_profile": ({"wan_profile": {
                             "3": {"injected_bytes_per_sec": 262144,
                                   "measured_bytes_per_sec": 263750.6,
                                   "bw_error_pct": 0.61}},
                         "link_bw_error_pct": 0.97,
                         "probe_overhead_pct": 0.36,
                         "wan_probes_sent": 72,
                         "wan_probes_answered": 72}, None),
        "slo_overhead": ({"slo_overhead_pct": 0.38,
                          "slo_ticks": 6,
                          "slo_ingest_ms": 1.2,
                          "slo_tick_ms": 1.9,
                          "slo_samples": 1200,
                          "alerts_fired": 1,
                          "slo_rounds": 600,
                          "slo_window_s": 1.21}, None),
        "pipeline_overlap": ({"pipeline_overlap_frac": 0.88,
                              "pipeline_overlap_frac_min": 0.86,
                              "pipeline_speedup": 1.44,
                              "pipeline_serial_wall_s": 0.87,
                              "pipeline_wall_s": 0.6,
                              "pipeline_micro_batches": 8,
                              "pipeline_chunk_nbytes": 32768,
                              "pipeline_plan_reason": "balanced",
                              "pipeline_clients": 3,
                              "pipeline_bottleneck": "train"}, None),
        "modelwatch_overhead": ({"modelwatch_overhead_pct": 0.46,
                                 "modelwatch_plain_round_ms": 1501.2,
                                 "modelwatch_watched_round_ms": 1508.1,
                                 "modelwatch_fold_ms": 12.4,
                                 "modelwatch_rounds": 16,
                                 "modelwatch_clients": 16,
                                 "modelwatch_work_reps": 160,
                                 "modelwatch_detection_caught": 2}, None),
        "fleet_scale": ({"fleet_scale_clients": 1_000_000,
                         "fleet_scale_nodes": 73,
                         "fleet_scale_quantile_err_pct": 0.86,
                         "fleet_telemetry_bytes_per_client": 6.2,
                         "fleet_scale_total_sketch_bytes": 6_190_000,
                         "fleet_scale_mem_ratio_vs_ref": 1.08,
                         "fleet_scale_ingest_overhead_pct": 0.44,
                         "fleet_scale_edge_eq_flat": True,
                         "fleet_scale_offenders_recovered": "12/12",
                         "fleet_scale_hll_err_pct": 1.49}, None),
        "secagg_overhead": ({"secagg_overhead_pct": 0.81,
                             "secagg_plain_round_ms": 42.0,
                             "secagg_masked_round_ms": 42.3,
                             "secagg_fold_ms": 3.1,
                             "secagg_rounds": 12,
                             "secagg_clients": 10,
                             "secagg_model_dim": 192,
                             "dp_epsilon_spent": 21.35,
                             "dp_noise_multiplier": 0.8}, None),
        "devperf_overhead": ({"llm_mfu": 0.018,
                              "llm_mfu_analytic": 0.018,
                              "llm_mfu_rel_err": 0.0,
                              "devperf_overhead_pct": 0.19,
                              "devperf_flops_source": "caller_analytic",
                              "devperf_xla_vs_analytic_flops_ratio": 1.16,
                              "devperf_roofline_verdict": "bandwidth-bound",
                              "devperf_steps": 83,
                              "devperf_window_s": 1.5,
                              "devperf_hbm_samples": 43}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "llm_train_tokens_per_sec"
    assert out["value"] == 50000.0
    assert out["mfu"] == 0.41
    assert out["mfu_xla_attention"] == 0.23
    assert out["remat_xla_attention"] is False
    assert out["vs_baseline"] == 500.0  # 50000 / 100
    assert out["resnet56_vs_torch_cpu"] == 32.0  # 20*128 / 80
    assert out["attn_best_flash"] == "flash_256x256"
    assert out["attn_best_vs_einsum"] == 1.067
    assert out["agg_clients_per_sec"]["resnet56"]["64"] == 240.0
    assert out["agg_hbm_gbps"]["llm268m"]["8"] == 40.0
    assert out["agg_bucket_size"] == 16
    assert out["agg_accum_traces"] == 4
    assert out["agg_sharded_hbm_ratio"] == 0.125
    assert out["agg_sharded_clients_per_sec"] == 12.0
    assert out["agg_sharded_overlap_efficiency"] == 1.4
    assert out["agg_sharded_traces"] == 2
    assert out["async_rounds_per_hr"]["100000"] == 330000.0
    assert out["async_flatness_ratio"] == 1.06
    assert out["async_parity_bit_exact"] is True
    assert out["placement_speedup"]["async_fedbuff"] == 4.07
    assert out["placement_plan"]["async_fedbuff"]["publish_k"] == 8
    assert out["link_bw_error_pct"] == 0.97
    assert out["probe_overhead_pct"] == 0.36
    assert out["slo_overhead_pct"] == 0.38
    assert out["alerts_fired"] == 1
    assert out["pipeline_overlap_frac"] == 0.88
    assert out["pipeline_speedup"] == 1.44
    assert out["llm_mfu"] == 0.018
    assert out["modelwatch_overhead_pct"] == 0.46
    assert out["modelwatch_detection_caught"] == 2
    assert out["devperf_overhead_pct"] == 0.19
    assert out["devperf_roofline_verdict"] == "bandwidth-bound"
    assert out["fleet_scale_quantile_err_pct"] == 0.86
    assert out["fleet_telemetry_bytes_per_client"] == 6.2
    assert out["fleet_scale_edge_eq_flat"] is True
    assert out["secagg_overhead_pct"] == 0.81
    assert out["dp_epsilon_spent"] == 21.35
    assert out["stages_failed"] == []
    # incremental artifacts landed (one per stage + final, same stamp file)
    arts = glob.glob(str(tmp_path / "BENCH_MEASURED_*.json"))
    assert len(arts) == 1
    with open(arts[0]) as f:
        doc = json.loads(f.read())
    assert "_stages" in doc and doc["value"] == 50000.0


def test_main_headline_failure_records_and_exits_nonzero(monkeypatch, tmp_path, capsys, _restore_signals):
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": (None, "llm_pallas: rc=1 RESOURCE_EXHAUSTED: fake"),
        "resnet": ({"steps_per_sec": 20.0, "mfu": 0.2, "bs": 128}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    # rc contract: nonzero only because the HEADLINE is missing
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] is None
    assert any("RESOURCE_EXHAUSTED" in f for f in out["stages_failed"])
    # the resnet number still shipped despite the headline failure
    assert out["resnet56_steps_per_sec"] == 20.0


def test_main_merges_memplan_validation(monkeypatch, tmp_path, capsys, _restore_signals):
    """VERDICT r4 next #6: the real-HBM 7B plan validation lands in the
    one-line JSON and the measured artifact."""
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": _LLM_OK,
        "memplan": ({"plan_bytes_per_device": 7_500_000_000,
                     "device_bytes_limit": 16 * 2**30,
                     "device_bytes_in_use": 0, "device_kind": "TPU v5 lite",
                     "memory_plan_validated": True}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["memory_plan_validated"] is True
    assert out["device_bytes_limit"] == 16 * 2**30
    assert out["memplan_bytes_per_device"] == 7_500_000_000


def test_main_reuses_banked_cpu_baselines(monkeypatch, tmp_path, capsys, _restore_signals):
    """With BENCH_CPU_BASELINES.json banked, a chip run never re-runs
    the cpu stages: the banked denominators feed vs_baseline directly and
    the output says so (VERDICT r4 weak #1/#2)."""
    (tmp_path / "BENCH_CPU_BASELINES.json").write_text(json.dumps({
        "cpu_llm_tokens_per_sec": 200.0, "cpu_resnet_images_per_sec": 80.0,
        "measured_at_utc": "20260731T000000Z"}))
    spawned = []

    def recording_canned(results):
        def fake_spawn(name, budget_s, argv=None, env=None):
            spawned.append(name)
            return results.get(name, (None, f"{name}: canned failure"))
        return fake_spawn

    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    monkeypatch.setattr(bench, "_spawn_stage", recording_canned({
        "llm_pallas": _LLM_OK,
        "resnet": ({"steps_per_sec": 20.0, "mfu": 0.2, "bs": 128}, None),
    }))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    assert "cpu_llm" not in spawned and "cpu_resnet" not in spawned
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["vs_baseline"] == 250.0  # 50000 / banked 200
    assert out["resnet56_vs_torch_cpu"] == 32.0  # 20*128 / banked 80
    assert out["cpu_baseline_source"] == "banked 20260731T000000Z (cpu_llm, cpu_resnet)"


def test_partial_bank_remeasures_only_missing_stage(monkeypatch, tmp_path):
    """A bank holding only one denominator is COMPLETED by the next
    banking run (only the missing stage re-measures), and main() keeps
    live-measuring the stage whose banked value is absent."""
    (tmp_path / "BENCH_CPU_BASELINES.json").write_text(json.dumps({
        "cpu_llm_tokens_per_sec": 200.0, "measured_at_utc": "20260731T000000Z"}))
    spawned = []

    def fake_spawn(name, budget_s, argv=None, env=None):
        spawned.append(name)
        return {"cpu_resnet_images_per_sec": 80.0}, None

    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    monkeypatch.setattr(bench, "_spawn_stage", fake_spawn)
    banked = bench._ensure_cpu_baselines()
    assert spawned == ["cpu_resnet"]  # cpu_llm reused, not re-measured
    assert banked["cpu_llm_tokens_per_sec"] == 200.0
    assert banked["cpu_resnet_images_per_sec"] == 80.0
    # the completed bank was persisted
    on_disk = json.loads((tmp_path / "BENCH_CPU_BASELINES.json").read_text())
    assert on_disk["cpu_resnet_images_per_sec"] == 80.0


def test_tiny_dryrun_writes_no_artifact_and_no_ratio(monkeypatch, tmp_path, capsys, _restore_signals):
    """FEDML_BENCH_TINY=1 exercises the real orchestrator path end-to-end
    on CPU, but must never persist a measured artifact (a CPU 'value' could
    be committed as chip evidence) nor compare tiny throughput against the
    flagship denominator."""
    (tmp_path / "BENCH_CPU_BASELINES.json").write_text(json.dumps({
        "cpu_llm_tokens_per_sec": 100.0, "measured_at_utc": "20260731T000000Z"}))
    monkeypatch.setenv("FEDML_BENCH_TINY", "1")
    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    monkeypatch.setattr(
        bench, "_spawn_stage",
        lambda name, *a, **k: _LLM_OK if name == "llm_pallas" else (None, f"{name}: canned failure"))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["tiny_dryrun"] is True
    assert out["vs_baseline"] is None
    assert not glob.glob(str(tmp_path / "BENCH_MEASURED_*.json"))


def test_main_int8_decode_comparison_surfaces(monkeypatch, tmp_path, capsys, _restore_signals):
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": _LLM_OK,
        "cpu_llm": ({"cpu_llm_tokens_per_sec": 100.0}, None),
        "decode": ({"decode_tokens_per_sec": 800.0, "bs": 4, "new": 128,
                    "weight_quant": "none"}, None),
        "decode_int8": ({"decode_tokens_per_sec": 1400.0, "bs": 4, "new": 128,
                         "weight_quant": "int8"}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["decode_tokens_per_sec"] == 800.0
    assert out["decode_tokens_per_sec_int8"] == 1400.0
    assert out["int8_decode_speedup"] == 1.75


def test_long_decode_speedup_merge(monkeypatch, tmp_path, capsys, _restore_signals):
    """int8_decode_speedup_long is published only when BOTH stages measured
    the long bucket; the short-bucket ratio stays independent."""
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": _LLM_OK,
        "decode": ({"decode_tokens_per_sec": 800.0, "bs": 4, "new": 128,
                    "new_long": 512, "decode_tokens_per_sec_long": 1500.0,
                    "weight_quant": "none"}, None),
        "decode_int8": ({"decode_tokens_per_sec": 900.0, "bs": 4, "new": 128,
                         "new_long": 512, "decode_tokens_per_sec_long": 2400.0,
                         "weight_quant": "int8"}, None),
        "cpu_llm": ({"cpu_llm_tokens_per_sec": 100.0}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["int8_decode_speedup"] == 1.12
    assert out["decode_tokens_per_sec_long"] == 1500.0
    assert out["decode_new_long"] == 512
    assert out["int8_decode_speedup_long"] == 1.6


def test_attn_micro_rejection_merge(monkeypatch, tmp_path, capsys, _restore_signals):
    """A sweep where every flash config was rejected merges its rejections
    and einsum time without best_flash keys; a partial sweep merges both."""
    _canned_stages(monkeypatch, tmp_path, {
        "llm_pallas": _LLM_OK,
        "attn_micro": ({"fwd_bwd_ms": {"xla_einsum": 8.0},
                        "rejected_configs": {"flash_128x128": "Mosaic: no"}},
                       None),
        "cpu_llm": ({"cpu_llm_tokens_per_sec": 100.0}, None),
    })
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["attn_rejected_configs"] == {"flash_128x128": "Mosaic: no"}
    assert "attn_best_flash" not in out
    assert "attn_best_vs_einsum" not in out
    assert out["attn_fwd_bwd_ms"] == {"xla_einsum": 8.0}


def _patch_orchestrator(monkeypatch, tmp_path, fake_spawn):
    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    monkeypatch.setattr(bench, "_spawn_stage", fake_spawn)


def test_llm_xla_oom_sharded_respawn_recovers(monkeypatch, tmp_path, capsys,
                                              _restore_signals):
    """ISSUE 7: the llm_xla OOM ladder tries the fsdp-sharded train state
    FIRST (FEDML_LLM_XLA_SHARDED=1 in a fresh subprocess, full geometry);
    when that fits, there is no half-batch respawn and the headline
    geometry ships undegraded with sharded_attempted=True."""
    xla_envs = []

    def fake_spawn(name, budget_s, argv=None, env=None):
        if name == "llm_xla":
            xla_envs.append(env)
            if len(xla_envs) == 1:
                return None, "llm_xla: rc=1 RESOURCE_EXHAUSTED: out of memory"
            return ({"tokens_per_sec": 22000.0, "mfu": 0.18, "remat": True,
                     "attention_impl": "xla", "n_params": 268000000,
                     "shape": _LLM_OK[0]["shape"],
                     "device": "TPU v5 lite", "step_flops": 1e12,
                     "server_sharded": True, "mesh_devices": 8}, None)
        return {"llm_pallas": _LLM_OK}.get(name, (None, f"{name}: canned failure"))

    _patch_orchestrator(monkeypatch, tmp_path, fake_spawn)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    assert len(xla_envs) == 2  # one OOM, ONE sharded respawn — it fit
    assert xla_envs[1] is not None
    assert xla_envs[1]["FEDML_LLM_XLA_SHARDED"] == "1"
    assert "FEDML_LLM_XLA_BS" not in xla_envs[1]  # geometry NOT degraded
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["tokens_per_sec_xla_attention"] == 22000.0
    assert out["llm_xla_sharded_attempted"] is True
    assert out["llm_xla_mesh_devices"] == 8
    assert "llm_xla_degraded_bs" not in out
    assert not any("llm_xla" in f for f in out.get("stages_failed", []))


def test_llm_xla_oom_half_bs_is_the_fallback_after_sharded(
        monkeypatch, tmp_path, capsys, _restore_signals):
    """When the sharded respawn ALSO OOMs, the r5 half-batch respawn runs
    as the fallback (keeping the sharded state for its extra headroom),
    and the shrunken geometry is surfaced via degraded_bs rather than
    silently passing as the headline shape."""
    xla_envs = []

    def fake_spawn(name, budget_s, argv=None, env=None):
        if name == "llm_xla":
            xla_envs.append(env)
            if len(xla_envs) <= 2:
                return None, "llm_xla: rc=1 RESOURCE_EXHAUSTED: out of memory"
            return ({"tokens_per_sec": 15000.0, "mfu": 0.12, "remat": True,
                     "attention_impl": "xla", "n_params": 268000000,
                     "shape": dict(_LLM_OK[0]["shape"], bs=4),
                     "device": "TPU v5 lite", "step_flops": 1e12,
                     "degraded_bs": 4}, None)
        return {"llm_pallas": _LLM_OK}.get(name, (None, f"{name}: canned failure"))

    _patch_orchestrator(monkeypatch, tmp_path, fake_spawn)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    assert len(xla_envs) == 3  # OOM -> sharded OOM -> half-bs, no loop
    half = str(max(1, bench._llm_shape()["bs"] // 2))
    assert xla_envs[1]["FEDML_LLM_XLA_SHARDED"] == "1"
    assert "FEDML_LLM_XLA_BS" not in xla_envs[1]
    assert xla_envs[2]["FEDML_LLM_XLA_BS"] == half
    assert xla_envs[2]["FEDML_LLM_XLA_SHARDED"] == "1"  # kept: more headroom
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["tokens_per_sec_xla_attention"] == 15000.0
    assert out["llm_xla_degraded_bs"] == 4
    assert out["llm_xla_sharded_attempted"] is True
    # the recovered stage is a success: no llm_xla entry in stages_failed
    assert not any("llm_xla" in f for f in out.get("stages_failed", []))


def test_llm_xla_oom_single_device_skips_sharding_honestly(
        monkeypatch, tmp_path, capsys, _restore_signals):
    """On a single-device host the sharded respawn reports
    SHARDED_UNAVAILABLE without measuring; the half-bs fallback then runs
    WITHOUT the sharded env and the artifact records
    sharded_attempted="unavailable" — a degraded single-chip number must
    never claim a sharded attempt backed it."""
    xla_envs = []

    def fake_spawn(name, budget_s, argv=None, env=None):
        if name == "llm_xla":
            xla_envs.append(env)
            if len(xla_envs) == 1:
                return None, "llm_xla: rc=1 RESOURCE_EXHAUSTED: out of memory"
            if len(xla_envs) == 2:
                return None, ("llm_xla: rc=1 SHARDED_UNAVAILABLE: 1 device — "
                              "the fsdp-sharded train state needs a "
                              "multi-device mesh")
            return ({"tokens_per_sec": 15000.0, "mfu": 0.12, "remat": True,
                     "attention_impl": "xla", "n_params": 268000000,
                     "shape": dict(_LLM_OK[0]["shape"], bs=4),
                     "device": "TPU v5 lite", "step_flops": 1e12,
                     "degraded_bs": 4}, None)
        return {"llm_pallas": _LLM_OK}.get(name, (None, f"{name}: canned failure"))

    _patch_orchestrator(monkeypatch, tmp_path, fake_spawn)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 0
    assert len(xla_envs) == 3
    assert "FEDML_LLM_XLA_SHARDED" not in xla_envs[2]  # sharding can't run
    assert xla_envs[2]["FEDML_LLM_XLA_BS"] == str(
        max(1, bench._llm_shape()["bs"] // 2))
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["llm_xla_sharded_attempted"] == "unavailable"
    assert out["llm_xla_degraded_bs"] == 4


def test_llm_xla_non_oom_failure_does_not_respawn(monkeypatch, tmp_path,
                                                  capsys, _restore_signals):
    calls = []

    def fake_spawn(name, budget_s, argv=None, env=None):
        if name == "llm_xla":
            calls.append(env)
            return None, "llm_xla: rc=1 RuntimeError: compile failed"
        return {"llm_pallas": _LLM_OK}.get(name, (None, f"{name}: canned failure"))

    monkeypatch.setattr(bench, "_REPO", str(tmp_path))
    monkeypatch.setattr(bench, "_spawn_stage", fake_spawn)
    with pytest.raises(SystemExit):
        bench.main()
    assert len(calls) == 1  # the half-bs respawn is OOM-specific
    capsys.readouterr()


def test_memplan_device_kind_hbm_fallback_table():
    """Satellite: when the runtime exposes no memory_stats bytes_limit, the
    per-device-kind datasheet table supplies the HBM ceiling (v5e = 16 GiB
    per device) so memory_plan_validated is a real verdict, not null."""
    assert bench._device_hbm_fallback("TPU v5 lite") == 16 * 2**30
    assert bench._device_hbm_fallback("TPU v5p") == 95 * 2**30
    assert bench._device_hbm_fallback("TPU v4") == 32 * 2**30
    assert bench._device_hbm_fallback("TPU v6e") == 32 * 2**30
    assert bench._device_hbm_fallback("TPU v3") == 16 * 2**30
    assert bench._device_hbm_fallback("some-future-chip") is None

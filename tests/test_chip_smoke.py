"""chip_smoke.py's contract off the chip, and the no-fallback rules it rests on.

On the CPU the smoke must REFUSE to run (non-zero, before compiling anything,
naming the platform it found) — the chip check is worthless if a machine
without a chip can pass it. ``--dry-run-cpu`` is the explicit rehearsal. The
unit tests pin the rules that keep a missing or misbehaving device visible:
``get_device``, the kernel platform check, ``device_specs`` on an unknown
kind, ``server_mesh`` on an unsatisfiable spec, ``enable_compile_cache``
with and without ``JAX_COMPILATION_CACHE_DIR``, and ``ReplicaSet`` start-up.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SMOKE, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_the_cpu_before_compiling():
    t0 = time.monotonic()
    r = _run_smoke(timeout=120)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60, "must fail at start-up, not after a phase"
    assert "platform=cpu" in r.stdout  # the first thing it prints
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert "phase" not in r.stdout and '"ok"' not in r.stdout  # no result of any kind


@pytest.mark.slow
def test_smoke_dry_run_cpu_passes():
    r = _run_smoke("--dry-run-cpu", timeout=1500)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRY RUN" in r.stdout
    *_, summary_line, last = r.stdout.strip().splitlines()
    # the driver accepts exactly these keys on the last line, no others
    result = json.loads(last)
    assert list(result) == ["ok", "device"] and result["ok"] is True
    assert list(result["device"]) == ["platform", "kind", "count"]
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["kind"] == jax.devices()[0].device_kind
    assert type(result["device"]["count"]) is int and result["device"]["count"] >= 4
    prefix = "chip_smoke: summary "
    assert summary_line.startswith(prefix)
    summary = json.loads(summary_line[len(prefix):])
    assert summary["dry_run_cpu"] is True
    assert set(summary["phases"]) >= {"kernel", "fedavg", "llm", "serving", "multichip"}
    assert list(summary)[-1] == "claim" and summary["claim"] is None


# --- get_device ---------------------------------------------------------------

def test_get_device_raises_when_no_accelerator_and_cpu_not_selected(monkeypatch):
    from fedml_tpu import device

    args = types.SimpleNamespace(using_gpu=True, gpu_id=0)
    monkeypatch.setattr(device, "cpu_selected", lambda: False)
    with pytest.raises(RuntimeError, match="accelerator was requested.*'cpu'"):
        device.get_device(args)
    # asking for the CPU is always honoured
    assert device.get_device(types.SimpleNamespace(using_gpu=False)).platform == "cpu"


def test_get_device_hands_out_the_cpu_only_when_selected_explicitly():
    from fedml_tpu import device

    assert device.cpu_selected()  # conftest pins JAX_PLATFORMS=cpu
    assert device.get_device(types.SimpleNamespace(using_gpu=True)).platform == "cpu"


# --- kernel platform check ------------------------------------------------------

def test_flash_kernel_interprets_on_cpu_compiles_on_tpu_refuses_the_rest(monkeypatch):
    from fedml_tpu.ops import flash_attention as fa

    assert fa._interpret() is True  # the tests' CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fa._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not 'gpu'"):
        fa._interpret()


def test_auto_attention_rule_is_by_platform_and_tiling():
    from fedml_tpu.models.transformer import _auto_attention_impl

    assert _auto_attention_impl("tpu", 1024) == "pallas"
    assert _auto_attention_impl("tpu", 1000) == "xla"   # 128-blocks do not tile
    assert _auto_attention_impl("cpu", 1024) == "xla"


# --- device_specs -----------------------------------------------------------------

def test_unknown_device_kind_has_no_peak_and_unknown_tpu_is_an_error():
    from fedml_tpu.core.distributed import device_specs as ds

    assert ds.peak_tflops("TPU v5 lite") == 197.0
    for fn in (ds.peak_tflops, ds.peak_flops_per_sec, ds.hbm_bandwidth_bytes_per_sec,
               ds.roofline_ridge_flops_per_byte):
        assert fn("cpu") is None
        with pytest.raises(ValueError, match="TPU v99"):
            fn("TPU v99")


def test_local_chip_count_reads_device_nodes_not_jax(monkeypatch):
    from fedml_tpu.core.distributed import device_specs as ds

    fake = {"/dev/accel[0-9]*": [], "/dev/vfio/*": ["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/vfio"]}
    monkeypatch.setattr(ds.glob, "glob", lambda pat: fake[pat])
    assert ds.local_chip_count() == 2
    fake["/dev/accel[0-9]*"] = ["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"]
    assert ds.local_chip_count() == 4


# --- server mesh ------------------------------------------------------------------

def test_server_mesh_raises_on_a_spec_it_cannot_satisfy():
    from fedml_tpu.core.distributed import mesh as dmesh

    with pytest.raises(ValueError, match="needs 64 devices but only 8"):
        dmesh.server_mesh("fsdp:64")
    assert dmesh.server_mesh("fsdp:1") is None  # one device IS the unsharded path


def test_sharded_fedopt_rounds_start_on_the_sharded_layout():
    """With a server mesh the sp round loop starts from the server's sharded
    view of the params: the local step traces ONCE, not once for round 0's
    single-device tree and again for the sharded trees every later round
    gets back (seen on 4 chips, PR 21)."""
    import fedml_tpu as fedml
    from fedml_tpu.core import telemetry as tel

    args = fedml.default_config(
        "simulation", backend="sp", model="lr", dataset="mnist", data_cache_dir="",
        partition_method="homo", client_num_in_total=4, client_num_per_round=2,
        comm_round=2, batch_size=64, frequency_of_the_test=1,
        federated_optimizer="FedOpt", server_optimizer="adam", server_lr=0.01,
        server_mesh="fsdp:8")
    before = tel.compile_count("local_train")
    fedml.run_simulation(args=args)
    assert tel.compile_count("local_train") - before == 1


# --- compile cache ----------------------------------------------------------------

def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    from fedml_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert compile_cache.enable_compile_cache() == "/somewhere/outside"
    assert calls == []  # JAX reads the variable itself


def test_compile_cache_defaults_to_a_fixed_dir_in_the_checkout(monkeypatch):
    from fedml_tpu.utils import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]


# --- replica start-up -------------------------------------------------------------

def test_replica_that_cannot_start_fails_the_set_with_its_stderr():
    from fedml_tpu.serving.replica_controller import ReplicaSet

    with pytest.raises(RuntimeError) as exc:
        ReplicaSet("no_such_module_for_replica:create_predictor", desired=1,
                   startup_timeout_s=120)
    msg = str(exc.value)
    assert "died during startup" in msg and "fedml_replica_" in msg
    assert "no_such_module_for_replica" in msg  # the child's traceback, from its log


def test_more_subprocess_replicas_than_chips_is_an_error(monkeypatch):
    from fedml_tpu.serving import replica_controller as rc

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # replicas would take chips
    monkeypatch.setattr(rc, "local_chip_count", lambda: 1)
    spawned = []
    monkeypatch.setattr(rc, "SubprocessReplica", lambda *a, **k: spawned.append(k) or None)
    with pytest.raises(ValueError, match="2 subprocess replicas requested but only 1"):
        rc.ReplicaSet("unused:spec", desired=2)
    assert spawned == []  # refused before anything started

"""Test harness config: the CPU, as an 8-device virtual mesh.

The tests run on the CPU (``JAX_PLATFORMS=cpu``): multi-chip sharding logic
is validated on a virtual CPU mesh
(``xla_force_host_platform_device_count=8``). What the chip itself says is
``chip_smoke.py``'s job, run through the chip tool.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# must be set before ANY protobuf import (grpc pulls in the C upb runtime,
# after which the reference's older generated pb2 modules refuse to load —
# this was the suite's one perpetual, order-dependent skip)
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
# Persistent compile cache, placed from outside the program through JAX's
# own variables (utils/compile_cache.py then touches nothing): compile-heavy
# tests share executables across runs, which is most of the fast tier's wall
# time. A fixed /tmp directory keeps CPU test entries out of the checkout
# the chip tool copies; child processes (examples, scheduler jobs, serving
# replicas) inherit all of it.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_compile_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_singletons():
    """Middleware singletons are process-wide; reset between tests."""
    yield
    from fedml_tpu.core.alg_frame.context import Context
    from fedml_tpu.core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
    from fedml_tpu.core.fhe.fhe_agg import FedMLFHE
    from fedml_tpu.core.security.fedml_attacker import FedMLAttacker
    from fedml_tpu.core.security.fedml_defender import FedMLDefender

    FedMLAttacker._instance = None
    FedMLDefender._instance = None
    FedMLDifferentialPrivacy._instance = None
    FedMLFHE._instance = None
    Context._instance = None
    # server-mesh config + engine registry are process-wide too: a test that
    # configures a mesh must not leak sharded engines into the next test
    from fedml_tpu.core.aggregation.bucketed import reset_engines
    from fedml_tpu.core.distributed.mesh import reset_mesh_state

    reset_engines()
    reset_mesh_state()
    # SLO engine + tsdb hook are process-wide ride-alongs on /statusz and
    # /metrics: a leaked engine would surface in unrelated tests' expositions
    from fedml_tpu.core.telemetry import slo as _slo

    _slo.reset()
    # devperf registry + HBM sampler are process-wide ride-alongs too: a
    # leaked program row or running sampler thread would surface in later
    # tests' expositions
    from fedml_tpu.core.telemetry import devperf as _devperf

    _devperf.reset()
    # fleet sketches hold a process-wide active provider + cardinality
    # budget; a leaked provider would surface in later tests' expositions
    from fedml_tpu.core.telemetry import sketches as _sketches

    _sketches.reset()


def spawn_to_logs(cmds, tmp_path, env=None, timeout=600, names=None):
    """Run N subprocesses with FILE-backed stdout/stderr and wait for all.

    Multi-process federation tests must never use stdout=PIPE with
    sequential communicate(): a party whose pipe fills before its turn
    blocks in write() and deadlocks the whole federation (the persistent
    compile cache's AOT-load warnings alone exceed the 64KB pipe buffer).
    Returns (procs, outs). On timeout, every survivor is killed first so one
    hung party cannot cascade into N sequential timeouts.
    """
    import subprocess

    names = names or [f"proc{i}" for i in range(len(cmds))]
    logs = [tmp_path / f"{n}.log" for n in names]
    procs = []
    for cmd, log_path in zip(cmds, logs):
        with open(log_path, "w") as log_f:
            procs.append(subprocess.Popen(
                cmd, env=env, stdout=log_f, stderr=subprocess.STDOUT, text=True))
    try:
        for p in procs:
            p.communicate(timeout=timeout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs, [log.read_text() for log in logs]

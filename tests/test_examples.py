"""Every example config parses and its platform entry runs (VERDICT item 10).

Mirrors the reference's CI model (SURVEY §4: smoke tests run the quick-start
examples). Config-parse coverage is exhaustive over examples/**/ *.yaml;
runnable coverage executes the cheap entries end to end."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _configs():
    return sorted(
        p for p in glob.glob(os.path.join(EXAMPLES, "**", "*.yaml"), recursive=True)
        if "job.yaml" not in p
    )


def test_found_all_platform_examples():
    expected = [
        "quick_start/parrot/fedml_config.yaml",
        "quick_start/octopus/fedml_config.yaml",
        "simulation/vmap_fedavg/fedml_config.yaml",
        "train/llm_finetune/fedml_config.yaml",
        "train/llm_moe/fedml_config.yaml",
        "fednlp/text_classification/fedml_config.yaml",
        "federated_analytics/heavy_hitter/fedml_config.yaml",
        "deploy/quick_start/main.py",
        "deploy/llm_endpoint/main.py",
        "cross_device/main.py",
        "launch/hello_job/job.yaml",
        "workflow/train_deploy_infer/main.py",
        "security/attack_defense/main.py",
        "privacy/dp_fedavg/main.py",
        "interop/run_mixed_demo.py",
        "flow/main.py",
    ]
    missing = [p for p in expected if not os.path.exists(os.path.join(EXAMPLES, p))]
    assert not missing, missing


@pytest.mark.parametrize("cfg", _configs(), ids=lambda p: os.path.relpath(p, EXAMPLES))
def test_example_config_parses(cfg):
    import argparse

    import fedml_tpu as fedml

    ns = argparse.Namespace(yaml_config_file=cfg)
    args = fedml.load_arguments(args=ns)
    assert getattr(args, "training_type", None) in ("simulation", "cross_silo", "cross_device")


def _run(script, *argv, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.basename(script), *argv],
        cwd=os.path.dirname(script), env=env, capture_output=True, text=True, timeout=timeout,
    )


@pytest.mark.slow
def test_fa_example_runs():
    s = os.path.join(EXAMPLES, "federated_analytics", "heavy_hitter", "main.py")
    r = _run(s, "--cf", "fedml_config.yaml")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "heavy hitters:" in r.stdout


@pytest.mark.slow
def test_launch_example_runs():
    s = os.path.join(EXAMPLES, "launch", "hello_job", "job.yaml")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "fedml_tpu.cli", "launch", "job.yaml", "--backend", "mqtt"],
        cwd=os.path.dirname(s), env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FINISHED" in r.stdout


@pytest.mark.slow
def test_cluster_job_example_runs():
    """Capacity-matched launch demo: 2-slot job lands on the 2 registered
    agents; over-ask refused with a clear error."""
    s = os.path.join(EXAMPLES, "launch", "cluster_job", "main.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, s], cwd=os.path.dirname(s), env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "over-ask correctly refused" in r.stdout


@pytest.mark.slow
def test_cross_cloud_region_wan_example_runs():
    """Region config + resumable WAN transfer demo: a dropped link resumes
    instead of restarting; download verifies chunk shas."""
    s = os.path.join(EXAMPLES, "cross_cloud", "region_wan", "main.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, s], cwd=os.path.dirname(s), env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "resume shipped only" in r.stdout
    assert "download verified" in r.stdout


@pytest.mark.slow
def test_llm_finetune_example_runs():
    s = os.path.join(EXAMPLES, "train", "llm_finetune", "main.py")
    r = _run(s, "--cf", "fedml_config.yaml", timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "federated LoRA fine-tune complete" in r.stdout


@pytest.mark.slow
def test_llm_moe_example_runs():
    s = os.path.join(EXAMPLES, "train", "llm_moe", "main.py")
    r = _run(s, "--cf", "fedml_config.yaml", timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "moe train done" in r.stdout


@pytest.mark.slow
def test_llm_endpoint_example_runs():
    s = os.path.join(EXAMPLES, "deploy", "llm_endpoint", "main.py")
    r = _run(s, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "llm endpoint example done" in r.stdout


@pytest.mark.slow
def test_workflow_example_runs():
    s = os.path.join(EXAMPLES, "workflow", "train_deploy_infer", "main.py")
    r = _run(s, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "workflow example done" in r.stdout


@pytest.mark.slow
def test_deploy_example_runs():
    s = os.path.join(EXAMPLES, "deploy", "quick_start", "main.py")
    r = _run(s, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "undeployed" in r.stdout


@pytest.mark.slow
def test_native_edge_federation_example_runs():
    s = os.path.join(EXAMPLES, "cross_device", "native_edge", "main.py")
    r = _run(s, "2", "2")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "native edge federation example done" in r.stdout
    assert "rc=[0, 0]" in r.stdout


@pytest.mark.slow
def test_security_example_runs():
    s = os.path.join(EXAMPLES, "security", "attack_defense", "main.py")
    r = _run(s, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "defense margin" in r.stdout


@pytest.mark.slow
def test_privacy_example_runs():
    s = os.path.join(EXAMPLES, "privacy", "dp_fedavg", "main.py")
    r = _run(s, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "privacy cost" in r.stdout


@pytest.mark.slow
def test_flow_example_runs():
    s = os.path.join(EXAMPLES, "flow", "main.py")
    r = _run(s, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "flow example done: 3 rounds" in r.stdout

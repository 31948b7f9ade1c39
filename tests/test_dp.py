"""DP frames + facade tests (reference test model: smoke_test_cross_silo_cdp/ldp
workflows run FL jobs with DP flags; we additionally unit-test the math the
reference never does)."""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.dp.fedml_differential_privacy import FedMLDifferentialPrivacy
from fedml_tpu.core.dp.frames import DPClip, GlobalDP, LocalDP, NbAFLDP, create_dp_frame
from fedml_tpu.utils.pytree import tree_global_norm


def _args(**kw):
    base = dict(
        enable_dp=True, dp_solution_type="cdp", mechanism_type="gaussian",
        epsilon=1.0, delta=1e-5, sensitivity=1.0, random_seed=0,
        comm_round=10, client_num_per_round=2, client_num_in_total=4,
    )
    base.update(kw)
    return SimpleNamespace(**base)


def _tree():
    return {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}


def test_frame_factory_dispatch():
    assert isinstance(create_dp_frame(_args(dp_solution_type="cdp")), GlobalDP)
    assert isinstance(create_dp_frame(_args(dp_solution_type="ldp")), LocalDP)
    assert isinstance(create_dp_frame(_args(dp_solution_type="nbafl")), NbAFLDP)
    assert isinstance(create_dp_frame(_args(dp_solution_type="dp_clip", clipping_norm=1.0)), DPClip)
    with pytest.raises(ValueError):
        create_dp_frame(_args(dp_solution_type="bogus"))


def test_ldp_noise_changes_params_deterministically():
    frame = create_dp_frame(_args(dp_solution_type="ldp"))
    key = jax.random.PRNGKey(1)
    out1 = frame.add_local_noise(_tree(), key)
    out2 = frame.add_local_noise(_tree(), key)
    assert not np.allclose(out1["w"], _tree()["w"])  # noise applied
    np.testing.assert_allclose(out1["w"], out2["w"])  # PRNG-key pure


def test_cdp_global_noise_and_accounting():
    dp = FedMLDifferentialPrivacy.get_instance()
    dp.init(_args(dp_solution_type="cdp"))
    out = dp.add_global_noise(_tree())
    assert not np.allclose(out["w"], 1.0)
    # accountant auto-stepped by add_global_noise
    assert float(np.sum(dp.accountant._rdp)) > 0.0
    assert math.isfinite(dp.get_epsilon(1e-5))


def test_nbafl_coordinate_clip_and_downlink_gate():
    # T=10 > sqrt(N)*L = 2*2 → downlink noise ON
    # epsilon=1e3 → ldp sigma ~5e-3, so the coordinate clip dominates
    frame = NbAFLDP(_args(dp_solution_type="nbafl", nbafl_C=0.5, comm_round=10, epsilon=1e3))
    frame.set_params_for_dp([(20, _tree()), (5, _tree())])
    assert frame.m == 5
    noised = frame.add_local_noise({"w": jnp.full((3,), 4.0)}, jax.random.PRNGKey(0))
    # coordinate clip bounds |w| by C before noising: 4.0 → 0.5 ± tiny noise
    assert float(jnp.max(jnp.abs(noised["w"]))) < 0.6
    g = frame.add_global_noise(_tree(), jax.random.PRNGKey(1))
    assert not np.allclose(g["w"], 1.0)
    # T small → no downlink noise
    frame2 = NbAFLDP(_args(dp_solution_type="nbafl", comm_round=2))
    g2 = frame2.add_global_noise(_tree(), jax.random.PRNGKey(1))
    np.testing.assert_allclose(g2["w"], 1.0)


def test_dp_clip_delta_clipping():
    frame = DPClip(_args(dp_solution_type="dp_clip", clipping_norm=1.0,
                         noise_multiplier=1.0, train_data_num_in_total=100))
    w_local = {"w": jnp.full((4,), 3.0)}
    w_global = {"w": jnp.ones((4,))}
    out = frame.add_local_noise(w_local, jax.random.PRNGKey(0), {"global_model_params": w_global})
    # returns a *model* = global + clipped delta, so averaging stays valid
    from fedml_tpu.utils.pytree import tree_sub
    assert float(tree_global_norm(tree_sub(out, w_global))) <= 1.0 + 1e-5
    # no anchor → passthrough, never clips raw weights to near-zero
    np.testing.assert_allclose(
        frame.add_local_noise(w_local, jax.random.PRNGKey(0), None)["w"], 3.0
    )
    noised = frame.add_global_noise(w_global, jax.random.PRNGKey(1))
    assert not np.allclose(noised["w"], 1.0)
    assert frame.get_rdp_scale() == 1.0


@pytest.mark.parametrize("solution", ["dp_clip", "nbafl"])
def test_dp_end_to_end_training_survives(solution):
    """The full hook path (client anchor stash → delta clip → aggregate →
    central noise) must still train; guards against clipping raw weights."""
    import fedml_tpu as fedml
    from fedml_tpu.arguments import default_config

    args = default_config(
        "simulation", model="lr", dataset="mnist", comm_round=2, epochs=1,
        client_num_in_total=2, client_num_per_round=2,
        enable_dp=True, dp_solution_type=solution, epsilon=100.0,
        clipping_norm=5.0, noise_multiplier=0.05, train_data_num_in_total=1000,
    )
    out = fedml.run_simulation(args=args)
    assert out["test_acc"] > 0.8, out


def test_facade_routes_to_frame():
    dp = FedMLDifferentialPrivacy.get_instance()
    dp.init(_args(dp_solution_type="nbafl"))
    assert dp.is_local_dp_enabled() and dp.is_global_dp_enabled()
    assert isinstance(dp.frame, NbAFLDP)
    out = dp.add_local_noise(_tree())
    assert out["w"].shape == (4, 3)
    # global_clip feeds round stats to the frame
    dp.global_clip([(3, _tree()), (9, _tree())])
    assert dp.frame.m == 3
    dp.account(sample_rate=0.5)
    assert math.isfinite(dp.get_epsilon(1e-5))


def test_get_instance_builds_one_instance_under_racing_threads(monkeypatch):
    """Parties of one process call fedml.init from threads: all of them get
    the one instance, built once (unlocked, each racing thread built its own
    and all but the last were dropped while another thread configured them)."""
    import threading
    import time

    built = []
    init = FedMLDifferentialPrivacy.__init__

    def slow_init(self):
        built.append(self)
        time.sleep(0.05)  # hold the window open: every thread arrives while the first still builds
        init(self)

    monkeypatch.setattr(FedMLDifferentialPrivacy, "__init__", slow_init)
    monkeypatch.setattr(FedMLDifferentialPrivacy, "_instance", None)
    start = threading.Barrier(8)
    got = []

    def party():
        start.wait()
        got.append(FedMLDifferentialPrivacy.get_instance())

    threads = [threading.Thread(target=party) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1 and len(got) == 8
    assert all(g is built[0] for g in got)

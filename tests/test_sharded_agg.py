"""Mesh-sharded server aggregation: mesh-spec plumbing, sharded-vs-unsharded
parity (same pairs, bit-tolerance), the fused sharded FedOpt round step,
engine-registry keying, telemetry surfaces, and the sharding-hygiene lint.

Everything runs on the conftest-forced 8-device virtual CPU mesh
(``xla_force_host_platform_device_count=8``) — the same validation path the
build instructions prescribe for all sharding logic.
"""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.aggregation.bucketed import (
    BucketedAggregator,
    get_engine,
    reset_engines,
)
from fedml_tpu.core.aggregation.server_optimizer import (
    FedOptServer,
    create_fedopt_server,
)
from fedml_tpu.core.aggregation.sharded import (
    ShardedBucketedAggregator,
    ShardedDelta,
    ShardedFedOptServer,
)
from fedml_tpu.core.distributed import mesh as dmesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh8():
    dmesh.configure_server_mesh(spec="fsdp:8")
    mesh = dmesh.server_mesh()
    assert mesh is not None, "conftest forces 8 virtual CPU devices"
    return mesh


def _client_tree(rng, i):
    """Mixed-dtype tree: a dim-0-divisible f32 matrix (shards evenly), a
    ragged bf16 vector and an int32 vector (padded groups), and a scalar."""
    return {
        "w": jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32)),
        "bf": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)).astype(jnp.bfloat16),
        "i": jnp.asarray(rng.integers(-40, 40, size=(3,)), jnp.int32),
        "s": jnp.float32(float(i)),
    }


def _assert_tree_close(a_tree, b_tree, rtol, int_atol=1):
    for name in a_tree:
        a = np.asarray(jax.tree.leaves(a_tree[name])[0] if False else a_tree[name])
        b = np.asarray(b_tree[name])
        if np.issubdtype(np.asarray(a).dtype, np.integer):
            np.testing.assert_allclose(a, b, atol=int_atol)
        else:
            np.testing.assert_allclose(
                np.asarray(jnp.asarray(a, jnp.float32)),
                np.asarray(jnp.asarray(b, jnp.float32)), rtol=rtol, atol=1e-5)


class TestMeshSpec:
    def test_parse_variants(self):
        assert dmesh.parse_mesh_spec("auto") == [("fsdp", -1)]
        assert dmesh.parse_mesh_spec("fsdp:8") == [("fsdp", 8)]
        assert dmesh.parse_mesh_spec("dp:2,fsdp:4") == [("dp", 2), ("fsdp", 4)]
        for auto in ("fsdp:auto", "fsdp:-1", "fsdp:*"):
            assert dmesh.parse_mesh_spec(auto) == [("fsdp", -1)]

    @pytest.mark.parametrize("bad", ["", "fsdp", "fsdp:0", ":4",
                                     "dp:auto,fsdp:auto", "fsdp:-2"])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            dmesh.parse_mesh_spec(bad)

    def test_server_mesh_resolves_auto_axes(self):
        dmesh.configure_server_mesh(spec="dp:2,fsdp:auto")
        mesh = dmesh.server_mesh()
        assert mesh is not None
        topo = dmesh.mesh_topology(mesh)
        assert topo["axis_names"] == ["dp", "fsdp"]
        assert topo["axis_sizes"] == [2, 4]
        assert topo["n_devices"] == 8

    def test_oversized_spec_raises(self):
        dmesh.configure_server_mesh(spec="fsdp:64")
        with pytest.raises(ValueError, match="needs 64 devices but only 8"):
            dmesh.server_mesh()

    def test_unconfigured_is_none(self):
        assert dmesh.configured_spec() is None
        assert dmesh.server_mesh() is None

    def test_args_and_env_precedence(self, monkeypatch):
        monkeypatch.setenv(dmesh.SERVER_MESH_ENV, "fsdp:2")
        assert dmesh.configured_spec() == "fsdp:2"
        dmesh.configure_server_mesh(types.SimpleNamespace(server_mesh="fsdp:4"))
        assert dmesh.configured_spec() == "fsdp:4"  # programmatic wins


class TestEngineRegistry:
    def test_keyed_by_mesh_spec(self):
        plain = get_engine(16)
        assert type(plain) is BucketedAggregator
        dmesh.configure_server_mesh(spec="fsdp:8")
        sharded = get_engine(16)
        assert isinstance(sharded, ShardedBucketedAggregator)
        assert sharded is not plain
        # spec drift -> fresh engine; same spec -> cached
        assert get_engine(16) is sharded
        dmesh.configure_server_mesh(spec=None)
        assert get_engine(16) is plain

    def test_configured_spec_on_oversized_mesh_raises(self):
        # a spec that cannot be satisfied is an error, never a quiet
        # single-device engine
        dmesh.configure_server_mesh(spec="fsdp:64")
        with pytest.raises(ValueError, match="needs 64 devices"):
            get_engine(16)

    def test_reset_engines_drops_cache(self):
        eng = get_engine(16)
        reset_engines()
        assert get_engine(16) is not eng

    def test_lru_eviction_bounds_registry(self):
        from fedml_tpu.core.aggregation import bucketed

        first = get_engine(101)
        for b in range(102, 102 + bucketed._MAX_ENGINES):
            get_engine(b)
        assert len(bucketed._ENGINES) == bucketed._MAX_ENGINES
        assert get_engine(101) is not first  # evicted, rebuilt


class TestShardedParity:
    @pytest.mark.parametrize("k", [1, 5, 8, 17])
    def test_matches_unsharded_same_pairs(self, k):
        """ISSUE acceptance: sharded-vs-unsharded parity over the SAME
        (weight, tree) pairs, non-uniform weights, mixed dtypes."""
        mesh = _mesh8()
        rng = np.random.default_rng(k)
        pairs = [(float(rng.uniform(0.1, 5.0)), _client_tree(rng, i))
                 for i in range(k)]
        if k > 2:
            pairs[1] = (0.0, pairs[1][1])  # a zero-weight client rides along
        ref = BucketedAggregator(8).aggregate(pairs)
        out = ShardedBucketedAggregator(8, mesh).aggregate(pairs)
        assert out["bf"].dtype == jnp.bfloat16 and out["i"].dtype == jnp.int32
        _assert_tree_close(ref, out, rtol=2e-5)

    def test_sharded_delta_ingestion_parity(self):
        """Host deltas pre-ingested as ShardedDelta (the cross-silo arrival
        path) aggregate identically to raw trees — including mixed cohorts."""
        mesh = _mesh8()
        eng = ShardedBucketedAggregator(4, mesh)
        rng = np.random.default_rng(0)
        trees = [_client_tree(rng, i) for i in range(9)]
        w = [float(rng.uniform(0.5, 2.0)) for _ in trees]
        ref = BucketedAggregator(4).aggregate(list(zip(w, trees)))
        host = [jax.tree.map(np.asarray, t) for t in trees]
        deltas = [eng.ingest(h) for h in host]
        assert all(isinstance(d, ShardedDelta) for d in deltas)
        out = eng.aggregate(list(zip(w, deltas)))
        _assert_tree_close(ref, out, rtol=2e-5)
        mixed = [(wi, d if i % 2 else t)
                 for i, (wi, d, t) in enumerate(zip(w, deltas, trees))]
        out2 = eng.aggregate(mixed)
        _assert_tree_close(ref, out2, rtol=2e-5)

    def test_layout_mismatch_rejected(self):
        mesh = _mesh8()
        eng = ShardedBucketedAggregator(4, mesh)
        rng = np.random.default_rng(1)
        delta = eng.ingest({"x": np.ones((8,), np.float32)})
        other = _client_tree(rng, 0)
        with pytest.raises(ValueError, match="layout"):
            eng.aggregate([(1.0, eng.ingest(other)), (1.0, delta)])

    def test_object_leaves_fall_back_to_host_fold(self):
        class Cipher:
            def __init__(self, v):
                self.v = v

            def __add__(self, other):
                return Cipher(self.v + other.v)

            def __mul__(self, s):
                return Cipher(self.v * s)

        mesh = _mesh8()
        eng = ShardedBucketedAggregator(4, mesh)
        pairs = [(1.0, {"c": Cipher(2.0), "x": np.ones((2,), np.float32)}),
                 (3.0, {"c": Cipher(6.0), "x": 3 * np.ones((2,), np.float32)})]
        out = eng.aggregate(pairs)
        np.testing.assert_allclose(out["c"].v, 0.25 * 2.0 + 0.75 * 6.0)
        np.testing.assert_allclose(np.asarray(out["x"]), 2.5)
        srv = object()  # any server: object cohorts cannot ride the fused step
        with pytest.raises(ValueError, match="fused"):
            eng.aggregate_round(pairs, server=srv)  # type: ignore[arg-type]

    def test_zero_recompiles_across_cohort_sizes_and_rounds(self):
        mesh = _mesh8()
        eng = ShardedBucketedAggregator(8, mesh)
        rng = np.random.default_rng(2)
        trees = [_client_tree(rng, i) for i in range(24)]
        eng.aggregate([(1.0, t) for t in trees[:17]])
        assert eng.sharded_traces == 2  # first-bucket + donated steady-state
        eng.aggregate([(2.0, t) for t in trees])
        eng.aggregate([(0.5, t) for t in trees[:9]])
        assert eng.sharded_traces == 2  # zero retraces on new cohort sizes


class TestShardedFedOptServer:
    def _run_rounds(self, rounds=3, opt="adam"):
        mesh = _mesh8()
        rng = np.random.default_rng(7)
        params = {
            "w": jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)),
        }
        args = types.SimpleNamespace(server_optimizer=opt, server_lr=0.1)
        clients = [jax.tree.map(lambda x, i=i: x + (i + 1) * 1e-3, params)
                   for i in range(5)]
        w = [float(rng.uniform(0.5, 2.0)) for _ in clients]

        srv_u = FedOptServer(args, params)
        g_u = params
        eng = ShardedBucketedAggregator(4, mesh)
        srv_s = ShardedFedOptServer(args, params, eng)
        g_s = None
        for _ in range(rounds):
            pairs = list(zip(w, clients))
            g_u = srv_u.apply(g_u, BucketedAggregator(4).aggregate(pairs))
            g_s = eng.aggregate_round(pairs, srv_s)
        return g_u, g_s, srv_s, eng

    def test_fused_round_matches_fedopt_server(self):
        for opt in ("sgd", "adam", "yogi"):
            g_u, g_s, srv_s, _ = self._run_rounds(opt=opt)
            host_s = srv_s.materialize_broadcast()
            for name in g_u:
                a = np.asarray(g_u[name])
                b = np.asarray(host_s[name])
                scale = np.max(np.abs(a)) + 1e-12
                assert np.max(np.abs(a - b)) / scale < 1e-4, (opt, name)

    def test_optimizer_state_sharded_before_first_round(self):
        """Adam's moments must carry the layout's vec_sharding from
        construction: on device 0 they would pin the whole optimizer state
        to one chip AND cost a retrace when round 1's outputs come back
        sharded (the seed's ``round_traces == 2``)."""
        eng = ShardedBucketedAggregator(4, _mesh8())
        params = {"w": jnp.ones((16, 3), jnp.float32), "b": jnp.ones((5,), jnp.float32)}
        srv = ShardedFedOptServer(
            types.SimpleNamespace(server_optimizer="adam", server_lr=0.1), params, eng)
        moments = [l for l in jax.tree.leaves(srv._state) if l.ndim == 1]
        assert moments, "adam state should hold mu/nu group vectors"
        for leaf in moments:
            assert leaf.sharding == srv.layout.vec_sharding
        for leaf in jax.tree.leaves(srv._state):
            if leaf.ndim == 0:
                assert leaf.sharding == srv.layout.repl_sharding

    def test_one_round_trace_and_sharded_outputs(self):
        _g_u, g_s, srv_s, _eng = self._run_rounds()
        assert srv_s.round_traces == 1  # the fused step compiled ONCE
        # eval contract: the returned global params are a SHARDED tree view —
        # the dim-0-divisible leaf is actually split, so the eval step that
        # consumes it runs sharded under GSPMD
        assert len(g_s["w"].sharding.device_set) == 8
        assert not g_s["w"].sharding.is_fully_replicated

    def test_materialize_broadcast_is_host_numpy(self):
        _g_u, _g_s, srv_s, _eng = self._run_rounds(rounds=1)
        host = srv_s.materialize_broadcast()
        assert all(isinstance(l, np.ndarray) for l in jax.tree.leaves(host))

    def test_state_setter_reshards_host_state_without_retrace(self):
        """Crash-resume restores optimizer state as numpy; re-entering it
        through the setter must re-shard, not force a recompile."""
        _g_u, _g_s, srv_s, eng = self._run_rounds(rounds=2)
        assert srv_s.round_traces == 1
        srv_s.state = jax.tree.map(np.asarray, srv_s.state)  # host round-trip
        rng = np.random.default_rng(3)
        params_t = srv_s.materialize_broadcast()
        clients = [jax.tree.map(lambda x: x + 1e-3, params_t) for _ in range(3)]
        eng.aggregate_round([(1.0, c) for c in clients], srv_s)
        assert srv_s.round_traces == 1  # resharded state hit the same jit

    def test_apply_contract_matches_fedopt_server(self):
        mesh = _mesh8()
        rng = np.random.default_rng(9)
        params = {"w": jnp.asarray(rng.normal(size=(8, 2)).astype(np.float32))}
        args = types.SimpleNamespace(server_optimizer="sgd", server_lr=1.0,
                                     server_momentum=0.0)
        avg = jax.tree.map(lambda x: x * 0.9, params)
        ref = FedOptServer(args, params).apply(params, avg)
        eng = ShardedBucketedAggregator(4, mesh)
        out = ShardedFedOptServer(args, params, eng).apply(params, avg)
        np.testing.assert_allclose(
            np.asarray(ref["w"]), np.asarray(out["w"]), rtol=1e-6)

    def test_factory_picks_sharded_iff_mesh_configured(self):
        params = {"w": jnp.ones((8, 2), jnp.float32)}
        args = types.SimpleNamespace(server_optimizer="adam", server_lr=0.1,
                                     server_mesh=None)
        assert type(create_fedopt_server(args, params)) is FedOptServer
        args.server_mesh = "fsdp:8"
        assert isinstance(create_fedopt_server(args, params), ShardedFedOptServer)


class TestTelemetrySurfaces:
    def test_statusz_sharding_section(self):
        from fedml_tpu.core.telemetry import statusz

        mesh = _mesh8()
        ShardedBucketedAggregator(4, mesh).layout_for(
            {"w": jnp.ones((16, 2), jnp.float32)})
        sec = statusz.render()["sections"]["sharding"]
        assert sec["configured_spec"] == "fsdp:8"
        assert sec["meshes"]["server"]["axis_sizes"] == [8]
        assert sec["meshes"]["server_agg"]["n_devices"] == 8
        assert len(sec["shard_bytes_by_device"]) == 8
        assert all(v > 0 for v in sec["shard_bytes_by_device"].values())

    def test_prom_shard_bytes_gauges(self):
        from fedml_tpu.core.telemetry import core as tel_core
        from fedml_tpu.core.telemetry import prom

        mesh = _mesh8()
        eng = ShardedBucketedAggregator(4, mesh)
        ShardedFedOptServer(
            types.SimpleNamespace(server_optimizer="adam", server_lr=0.1),
            {"w": jnp.ones((16, 2), jnp.float32)}, eng)
        text = prom.render(telemetry=tel_core.Telemetry(enabled=True))
        assert "fedml_server_shard_bytes{device=" in text
        # both owners are booked: accumulator + fedopt params/opt state
        booked = dmesh.shard_bytes_by_device()
        assert len(booked) == 8 and all(v > 0 for v in booked.values())

    def test_flight_recorder_dump_carries_mesh_topology(self, tmp_path):
        from fedml_tpu.core.telemetry import flight_recorder as fr

        _mesh8()
        rec = fr.FlightRecorder(capacity=4, enabled=True)
        path = rec.dump(path=str(tmp_path / "d.jsonl"), reason="test")
        lines = [json.loads(l) for l in open(path)]
        mesh_lines = [l for l in lines if l.get("type") == "mesh"]
        assert len(mesh_lines) == 1
        assert mesh_lines[0]["configured_spec"] == "fsdp:8"
        assert mesh_lines[0]["meshes"]["server"]["axis_sizes"] == [8]

    def test_dump_omits_mesh_line_when_never_sharded(self, tmp_path):
        from fedml_tpu.core.telemetry import flight_recorder as fr

        rec = fr.FlightRecorder(capacity=4, enabled=True)
        path = rec.dump(path=str(tmp_path / "d.jsonl"), reason="test")
        lines = [json.loads(l) for l in open(path)]
        assert not [l for l in lines if l.get("type") == "mesh"]


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "check_sharding", os.path.join(_REPO, "tools", "check_sharding.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestShardingLint:
    def test_repo_is_clean(self):
        assert _load_lint().main([]) == 0

    def test_detects_scattered_sharding_and_device_get(self, tmp_path):
        mod = _load_lint()
        root = tmp_path / "fedml_tpu"
        (root / "core" / "distributed").mkdir(parents=True)
        (root / "core" / "aggregation").mkdir(parents=True)
        (root / "cross_silo").mkdir()
        (root / "simulation" / "collective").mkdir(parents=True)
        (root / "core" / "distributed" / "mesh.py").write_text(
            "from jax.sharding import Mesh\n")
        (root / "simulation" / "collective" / "collective_sim.py").write_text(
            "import jax.sharding\n")
        # violation 1: device_get inside a privileged sharding module
        (root / "core" / "aggregation" / "sharded.py").write_text(
            "import jax\nx = jax.device_get(1)\n")
        # violation 2: jax.sharding escaping into the wider server scope
        (root / "cross_silo" / "bad.py").write_text(
            "from jax.sharding import NamedSharding\n")
        violations = mod.find_violations(str(root))
        msgs = [m for _, _, m in violations]
        assert any("device_get" in m for m in msgs)
        assert any("outside the mesh/sharded modules" in m for m in msgs)
        assert mod.main([str(root)]) == 1
        # clean the two violations -> rc 0
        (root / "core" / "aggregation" / "sharded.py").write_text("import jax\n")
        (root / "cross_silo" / "bad.py").write_text("import numpy\n")
        assert mod.main([str(root)]) == 0

    def test_missing_allowlisted_file_is_a_violation(self, tmp_path):
        mod = _load_lint()
        root = tmp_path / "fedml_tpu"
        (root / "core").mkdir(parents=True)
        violations = mod.find_violations(str(root))
        assert any("allowlist names missing file" in m for _, _, m in violations)

"""Bucketed aggregation engine: numerical equivalence, compile-count
regression, batched comm-boundary transfer, and the agg bench stage."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.aggregation.bucketed import (
    DEFAULT_BUCKET_SIZE,
    BucketedAggregator,
    bucketed_weighted_average,
    get_engine,
)
from fedml_tpu.utils.pytree import (
    stacked_weighted_average,
    tree_from_numpy,
    tree_stack,
    tree_to_numpy,
    weighted_average,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _client_tree(rng, dtype=np.float32):
    return {
        "w": jnp.asarray(rng.normal(size=(5, 3)).astype(np.float32)).astype(dtype),
        "b": jnp.asarray(rng.normal(size=(7,)).astype(np.float32)).astype(dtype),
    }


def _reference_avg(pairs):
    """f64 numpy ground truth, same normalize-then-sum contract."""
    ws = np.asarray([w for w, _ in pairs], dtype=np.float64)
    ws = ws / ws.sum()
    out = {}
    for k in pairs[0][1]:
        out[k] = sum(
            w * np.asarray(t[k]).astype(np.float64) for w, (_, t) in zip(ws, pairs)
        )
    return out


class TestNumericalEquivalence:
    @pytest.mark.parametrize("k", [1, 3, 64, 65, 257])
    def test_matches_f64_reference(self, k):
        rng = np.random.default_rng(k)
        pairs = [(float(rng.uniform(0.5, 5.0)), _client_tree(rng)) for _ in range(k)]
        out = weighted_average(pairs)
        ref = _reference_avg(pairs)
        for name in ref:
            np.testing.assert_allclose(np.asarray(out[name]), ref[name], rtol=2e-5, atol=1e-6)

    def test_non_f32_dtypes_roundtrip_through_f32_accumulator(self):
        rng = np.random.default_rng(0)
        k = 21  # one full bucket + ragged tail at the default size
        pairs = [
            (1.0, {
                "bf": jnp.full((4,), float(i), jnp.bfloat16),
                "i":  jnp.full((3,), i, jnp.int32),
                "f":  jnp.asarray(rng.normal(size=(2,)).astype(np.float32)),
            })
            for i in range(k)
        ]
        out = weighted_average(pairs)
        # leaves come back in their ORIGINAL dtypes (accumulation was f32)
        assert out["bf"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32
        assert out["f"].dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(out["bf"].astype(jnp.float32)), (k - 1) / 2.0, rtol=1e-2
        )
        np.testing.assert_allclose(np.asarray(out["i"]), (k - 1) // 2, atol=1)

    def test_bucket_boundary_sizes(self):
        # K exactly on, one under, and one over a bucket boundary must agree
        rng = np.random.default_rng(3)
        trees = [_client_tree(rng) for _ in range(17)]
        for k in (15, 16, 17):
            pairs = [(float(i + 1), t) for i, t in enumerate(trees[:k])]
            out = bucketed_weighted_average(pairs)
            ref = _reference_avg(pairs)
            np.testing.assert_allclose(np.asarray(out["w"]), ref["w"], rtol=2e-5)

    def test_aggregate_stacked_nonuniform_weights_matches_reference(self):
        """Strongly skewed weights (4 orders of magnitude apart, plus an
        exact zero) through the stacked tensordot path vs the f64 ground
        truth — the contraction must not lose the small contributors."""
        eng = BucketedAggregator(bucket_size=8)
        rng = np.random.default_rng(13)
        k = 19  # ragged tail: two full buckets + 3
        trees = [_client_tree(rng) for _ in range(k)]
        w = np.asarray([10.0 ** (i % 5 - 2) for i in range(k)], np.float64)
        w[4] = 0.0  # a zero-weight client must contribute exactly nothing
        wn = (w / w.sum()).astype(np.float32)
        stacked = tree_stack(trees)
        out = eng.aggregate_stacked(stacked, jnp.asarray(wn))
        ref = _reference_avg(list(zip(w, trees)))
        for name in ref:
            np.testing.assert_allclose(
                np.asarray(out[name]), ref[name], rtol=5e-5, atol=1e-6)
        # the zeroed client really is absent: perturbing it changes nothing
        trees[4] = jax.tree.map(lambda x: x + 100.0, trees[4])
        out2 = eng.aggregate_stacked(tree_stack(trees), jnp.asarray(wn))
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(out2["w"]))

    def test_object_leaf_fold_uses_leaf_algebra(self):
        class Cipher:
            """FHE-ciphertext stand-in: only + and scalar * are defined."""

            def __init__(self, v):
                self.v = v

            def __add__(self, other):
                return Cipher(self.v + other.v)

            def __mul__(self, s):
                return Cipher(self.v * s)

        pairs = [(1.0, {"c": Cipher(2.0)}), (3.0, {"c": Cipher(6.0)})]
        out = weighted_average(pairs)
        assert isinstance(out["c"], Cipher)
        np.testing.assert_allclose(out["c"].v, 0.25 * 2.0 + 0.75 * 6.0)

    def test_object_leaf_mixture_folds_both_kinds(self):
        """A tree MIXING object leaves with array leaves (the FHE-partial
        case: some layers encrypted, some plain) must fold the objects via
        their algebra and the arrays numerically, in one pass."""
        class Cipher:
            def __init__(self, v):
                self.v = v

            def __add__(self, other):
                return Cipher(self.v + other.v)

            def __mul__(self, s):
                return Cipher(self.v * s)

        rng = np.random.default_rng(21)
        pairs = [
            (float(i + 1), {
                "enc": Cipher(float(i) * 2.0),
                "plain": jnp.asarray(rng.normal(size=(3,)).astype(np.float32)),
            })
            for i in range(5)
        ]
        out = weighted_average(pairs)
        ws = np.asarray([w for w, _ in pairs], np.float64)
        ws = ws / ws.sum()
        assert isinstance(out["enc"], Cipher)
        np.testing.assert_allclose(
            out["enc"].v, sum(w * float(i) * 2.0 for i, w in enumerate(ws)),
            rtol=1e-6)
        ref = sum(w * np.asarray(t["plain"], np.float64)
                  for w, (_, t) in zip(ws, pairs))
        np.testing.assert_allclose(np.asarray(out["plain"]), ref, rtol=2e-5)


class TestCompileReuse:
    def test_one_accumulator_compile_across_cohort_sizes(self):
        """The ISSUE's core claim: K=57 and K=64 (and 257) share the same
        two executables (first-bucket + donated steady-state step)."""
        eng = BucketedAggregator(bucket_size=16)
        rng = np.random.default_rng(7)
        trees = [_client_tree(rng) for _ in range(257)]

        eng.aggregate([(1.0, t) for t in trees[:57]])
        assert eng.accum_traces == 2  # first bucket + steady-state, no more
        eng.aggregate([(2.0, t) for t in trees[:64]])
        eng.aggregate([(1.5, t) for t in trees[:257]])
        assert eng.accum_traces == 2  # zero retraces on new cohort sizes

    def test_single_bucket_cohort_only_traces_first_step(self):
        eng = BucketedAggregator(bucket_size=16)
        rng = np.random.default_rng(8)
        eng.aggregate([(1.0, _client_tree(rng)) for _ in range(9)])
        assert eng.accum_traces == 1  # never needed the donating step

    def test_stacked_path_shares_compile_across_padded_cohorts(self):
        eng = BucketedAggregator(bucket_size=16)
        rng = np.random.default_rng(9)
        stacked = {"a": jnp.asarray(rng.normal(size=(64, 6)).astype(np.float32))}
        for k in (57, 64):  # both pad to nb=4 buckets -> one executable
            sub = {"a": stacked["a"][:k]}
            w = np.abs(rng.normal(size=(k,)).astype(np.float32)) + 0.1
            w = w / w.sum()
            out = eng.aggregate_stacked(sub, jnp.asarray(w))
            ref = stacked_weighted_average(sub, jnp.asarray(w))
            np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(ref["a"]), rtol=1e-5)
        assert eng.stacked_traces == 1

    def test_get_engine_is_process_wide_per_bucket_size(self):
        assert get_engine(16) is get_engine(16)
        assert get_engine(16) is not get_engine(8)


class TestBatchedCommBoundary:
    def test_roundtrip_preserves_values_and_dtypes(self):
        rng = np.random.default_rng(11)
        tree = {
            "f32": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "bf16": jnp.asarray(rng.normal(size=(5,)).astype(np.float32)).astype(jnp.bfloat16),
            "i32": jnp.arange(6, dtype=jnp.int32),
        }
        host = tree_to_numpy(tree)
        assert isinstance(host["f32"], np.ndarray)
        assert host["f32"].dtype == np.float32 and host["i32"].dtype == np.int32
        back = tree_from_numpy(host)
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(
                np.asarray(back[k].astype(jnp.float32)), np.asarray(tree[k].astype(jnp.float32))
            )

    def test_int64_canonicalizes_like_plain_asarray(self):
        # without x64, jnp.asarray(int64) -> int32; the batched upload must
        # keep that contract (MPC masks that need exact int64 never take
        # this path - the cross-silo gate holds them host-side)
        host = {"n": np.arange(4, dtype=np.int64)}
        up = tree_from_numpy(host)
        assert up["n"].dtype == jnp.asarray(host["n"]).dtype
        np.testing.assert_array_equal(np.asarray(up["n"]), host["n"])

    def test_object_leaves_pass_through(self):
        class Cipher:
            pass

        c = Cipher()
        tree = {"c": c, "x": jnp.ones((2,), jnp.float32)}
        host = tree_to_numpy(tree)
        assert host["c"] is c
        assert isinstance(host["x"], np.ndarray)

    def test_cross_silo_eager_upload_gate(self):
        from fedml_tpu.cross_silo.server.fedml_aggregator import _float_array_leaves_only

        assert _float_array_leaves_only({"a": np.ones((2,), np.float32)})
        assert not _float_array_leaves_only({"a": np.ones((2,), np.int64)})
        assert not _float_array_leaves_only({"a": object()})
        assert not _float_array_leaves_only({})


@pytest.mark.slow
def test_bench_agg_stage_emits_valid_json(tmp_path):
    """`bench.py --stage agg --trace OUT.json` prints exactly one JSON line
    with per-cohort clients/sec for both pytrees (tiny CPU geometry) AND
    writes a Chrome-trace with per-bucket agg spans + comm byte counters."""
    trace_path = tmp_path / "agg_trace.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", FEDML_BENCH_TINY="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), "--stage", "agg",
         "--trace", str(trace_path)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["agg_bucket_size"] >= 1
    assert out["agg_cohorts"] == [8, 64, 257, 512]
    for label in ("resnet56", "llm268m"):
        rates = out["agg_clients_per_sec"][label]
        assert set(rates) == {"8", "64", "257", "512"}
        assert all(r > 0 for r in rates.values())
        gbps = out["agg_hbm_gbps"][label]
        assert all(g > 0 for g in gbps.values())
    # one compile pair PER PYTREE for the whole cohort sweep (2 pytrees x
    # first-bucket + steady-state): the engine's single-compile claim
    assert out["agg_accum_traces"] == 4
    # the artifact roll-up of the engine's own spans rides the stage JSON
    assert out["agg_span_summary"]["agg.bucket"]["count"] > 0

    # --trace acceptance: the stage's Perfetto trace holds the per-bucket
    # engine spans and the comm-boundary byte counters (the per-bucket host
    # weight upload), wrapped in the stage span, under the overhead budget
    assert out["trace_file"] == str(trace_path)
    assert out["telemetry_disabled_span_ns"] < 1000.0
    doc = json.loads(trace_path.read_text())
    events = doc["traceEvents"]
    span_names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"bench.agg", "agg.bucket", "agg.finalize"} <= span_names
    counter_names = {e["name"] for e in events if e["ph"] == "C"}
    assert "comm.host_to_device_bytes" in counter_names
    assert "jax.compiles.agg_accum" in counter_names

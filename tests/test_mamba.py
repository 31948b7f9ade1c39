"""The Mamba mixer, the hybrid model and the selective-scan kernel, on the CPU
at small sizes with seeded weights: the cache-free forward against the
benchmark's plain reference (``benchmark/reference_jamba.py``, nothing of the
program in it), the kernel (interpreted) against its plain formulation, and a
dense configuration untouched by all of it."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import mamba
from fedml_tpu.models.transformer import TransformerConfig, TransformerLM, hybrid_pattern
from fedml_tpu.ops import selective_scan as ss
from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_jamba  # noqa: E402
import weights_jamba  # noqa: E402

HF = {"attn_layer_offset": 1, "attn_layer_period": 4, "hidden_size": 64, "intermediate_size": 128,
      "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2, "num_attention_heads": 4,
      "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 4, "num_key_value_heads": 1,
      "rms_norm_eps": 1e-06, "tie_word_embeddings": True, "vocab_size": 97}
CFG = config_from_hf_keys(HF, max_seq_len=128, dtype=jnp.float32, remat=False)


@pytest.fixture(scope="module")
def params():
    shapes = jax.eval_shape(lambda k: TransformerLM(CFG).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    return weights_jamba.make_params(weights_jamba.shapes_of(shapes), 7, jnp.float32)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, HF["vocab_size"], n).astype(np.int32)


def _reference_logits(params, tokens):
    cfg = reference_jamba.norm_cfg(HF)
    return reference_jamba.logits_at(params, jnp.asarray(tokens), jnp.arange(len(tokens)), cfg)


# float32 program against the float32 reference at Precision.HIGHEST: what is left is the order of
# float32 sums (the CPU's matmuls are exact float32; the kernel sums the 16 states in another order
# than lax.scan). Logits are O(1-10) here and 40 tokens deep in 3 recurrent layers; measured 2e-5.
LOGIT_TOL = 2e-4


def test_cache_free_forward_matches_the_plain_reference(params):
    toks = _tokens(40)  # a multiple of 8: the interpreted kernel runs, not the plain formulation
    got = TransformerLM(CFG).apply({"params": params}, jnp.asarray(toks)[None])[0]
    ref = _reference_logits(params, toks)
    assert got.shape == ref.shape == (40, HF["vocab_size"])
    assert float(jnp.max(jnp.abs(got - ref))) < LOGIT_TOL


def test_a_bfloat16_recurrent_state_fails_that_tolerance(params, monkeypatch):
    """The tolerance is tight enough to tell the stated float32 state from the
    precision below it: the same scan with h rounded to bfloat16 after every
    token is outside it by an order of magnitude."""
    def rounded_scan(u, dt, a_t, b, c, d_skip, h0, length, snap):
        def step(h, x):
            u_t, dt_t, b_t, c_t = x
            h = jnp.exp(dt_t[:, None, :] * a_t) * h + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
            return h, jnp.sum(h * c_t[:, :, None], axis=1) + d_skip * u_t
        h, y = jax.lax.scan(step, h0, tuple(x.swapaxes(0, 1) for x in (u, dt, b, c)))
        return y.swapaxes(0, 1), h, h

    monkeypatch.setattr(mamba, "_selective_scan_impl", lambda *a: rounded_scan)
    toks = _tokens(40)
    got = TransformerLM(CFG).apply({"params": params}, jnp.asarray(toks)[None])[0]
    assert float(jnp.max(jnp.abs(got - _reference_logits(params, toks)))) > 10 * LOGIT_TOL


def test_hybrid_config_from_the_catalog_rows_keys():
    row = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
           "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
           "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
           "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
           "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
           "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
           "vocab_size": 65536}
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        held = json.load(f)
    assert {k: held[k] for k in row} == row  # the benchmark's file holds every published key unchanged
    cfg = config_from_hf_keys(row)
    assert [i for i, k in enumerate(cfg.layer_pattern) if k == "attention"] == [7, 21]
    assert cfg.layer_pattern.count("mamba") == 26 and cfg.has_recurrent_state
    assert (cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2560, 8192, 20, 1, 128)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank, cfg.mamba_expand) == (16, 4, 160, 2)
    assert cfg.tie_embeddings and not cfg.use_rope and cfg.norm_eps == 1e-6
    assert mamba.state_bytes(cfg) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2)  # 9.3 MB a request
    with pytest.raises(ValueError, match="num_experts=16"):
        config_from_hf_keys(dict(row, num_experts=16, num_experts_per_tok=2))


# ---- the kernel (interpreted) against its plain formulation --------------------------------------

def _scan_inputs(B, T, D, N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (B, T, D)), jax.nn.softplus(jax.random.normal(ks[1], (B, T, D)) - 3),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (N, D))), jax.random.normal(ks[3], (B, T, N)),
            jax.random.normal(ks[4], (B, T, N)), jax.random.normal(ks[5], (D,)), jax.random.normal(ks[6], (B, N, D)))


@pytest.mark.parametrize("case,T,D,length,snap,zero_h0", [
    ("initial state carried over two chunks", 512, 128, [512], [0], False),
    ("true length inside the last chunk", 512, 128, [300], [0], False),
    ("padded tail leaves the state at the true length", 48, 256, [37, 48], [16, 0], False),
    ("snapshot position inside a chunk, zero start", 64, 128, [50], [27], True),
    ("snapshot at the true length", 24, 128, [24], [24], False),
])
def test_kernel_matches_its_plain_formulation(case, T, D, length, snap, zero_h0):
    B = len(length)
    args = list(_scan_inputs(B, T, D, 16))
    if zero_h0:
        args[6] = jnp.zeros_like(args[6])
    L, S = jnp.asarray(length, jnp.int32), jnp.asarray(snap, jnp.int32)
    got = ss.selective_scan(*args, L, S)
    ref = ss.selective_scan_reference(*args, L, S)
    for g, r, name in zip(got, ref, ("y", "state at length", "state at snapshot")):
        # float32 both; the kernel sums the states of y in a tree, lax.scan's XLA in a row: 1e-5 of |y| ~ 20
        assert float(jnp.max(jnp.abs(g - r))) < 2e-5 * max(1.0, float(jnp.max(jnp.abs(r)))), (case, name)
    # the state at the true length is the state of a scan that stops there
    for b in range(B):
        cut = [a[b:b + 1, :length[b]] if a.ndim == 3 and a.shape[1] == T else a for a in args]
        cut[6] = args[6][b:b + 1]
        n = jnp.asarray([length[b]], jnp.int32)
        _, h_stop, _ = ss.selective_scan_reference(*cut, n, n)
        assert float(jnp.max(jnp.abs(got[1][b] - h_stop[0]))) < 2e-5 * max(1.0, float(jnp.max(jnp.abs(h_stop))))


def test_the_plain_formulation_is_chosen_from_shapes_and_said(caplog):
    mamba._selective_scan_impl.cache_clear()
    with caplog.at_level("INFO", logger="fedml_tpu.models.mamba"):
        assert mamba._selective_scan_impl("cpu", 42, 128, 16) is ss.selective_scan_reference  # no 8-token tile
        assert mamba._selective_scan_impl("cpu", 48, 64, 16) is ss.selective_scan            # interpreted: any width
        assert mamba._selective_scan_impl("tpu", 48, 64, 16) is ss.selective_scan_reference   # lanes not filled
        assert mamba._selective_scan_impl("tpu", 1280, 5120, 16) is ss.selective_scan
    said = [r.getMessage() for r in caplog.records]
    assert sum("plain lax.scan formulation" in m for m in said) == 2 and sum("pallas kernel" in m for m in said) == 2
    assert ss.block_t(1280) == 256 and ss.block_t(320) == 160 and ss.block_t(336) == 168 and ss.block_d(5120) == 512
    mamba._selective_scan_impl.cache_clear()


# ---- a dense configuration is what it was --------------------------------------------------------

DENSE = TransformerConfig(vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=64,
                          dtype=jnp.float32, remat=False)


def test_dense_parameter_paths_are_unchanged():
    shapes = jax.eval_shape(lambda k: TransformerLM(DENSE).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                            jax.random.PRNGKey(0))
    layer = {"attn/q_proj/kernel", "attn/k_proj/kernel", "attn/v_proj/kernel", "attn/o_proj/kernel",
             "attn_norm/scale", "mlp/gate_proj/kernel", "mlp/up_proj/kernel", "mlp/down_proj/kernel", "mlp_norm/scale"}
    want = {"embed/embedding", "final_norm/scale", "lm_head/kernel"} | {f"layer_{i}/{p}" for i in range(2) for p in layer}
    assert set(weights_jamba.shapes_of(shapes)) == want
    assert DENSE.layer_pattern == () and not DENSE.has_recurrent_state and DENSE.layer_kind(1) == "attention"


def test_dense_engine_compiles_what_it_compiled():
    """One program a label (two prefill buckets), as before the cache pytree
    learned a second kind of state; and its pool has no state leaf."""
    from fedml_tpu.core import telemetry as tel
    from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine

    cfg = dataclasses.replace(DENSE, vocab_size=83)  # no other test file builds this config: fresh program caches whatever ran before in the worker
    params = TransformerLM(cfg).init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"]
    labels = ("prefill", "paged_step", "paged_admit", "paged_gather", "paged_suffix_prefill")
    before = {k: tel.compile_count(k) for k in labels}
    eng = PagedContinuousBatchingEngine(params, cfg, num_slots=2, chunk=4, page_size=16, num_pages=12)
    try:
        system = (_tokens(16, 5) % 80 + 1).tolist()  # inside this config's vocabulary
        for tail in (3, 7, 20, 5):
            eng.generate(system + (_tokens(tail, tail) % 80 + 1).tolist(), 5)
        leaves = {str(p[-1].key) for p, _ in jax.tree_util.tree_flatten_with_path(eng._cache)[0]}
        assert leaves == {"k", "v", "idx"}
        st = eng.stats()
        assert st["state_snapshots"] == 0 and st["state_prefix_hits"] == st["state_prefix_misses"] == 0
        assert eng._alloc.check_leaks()["state_leaked"] == []
    finally:
        eng.shutdown()
    grew = {k: tel.compile_count(k) - before[k] for k in labels}
    assert grew == {"prefill": 1, "paged_step": 1, "paged_admit": 1, "paged_gather": 1, "paged_suffix_prefill": 2}, grew


def test_a_prefills_row_cache_crosses_the_program_boundary_packed(params):
    """The recurrent leaves of all Mamba layers ride stacked, one array a leaf
    name: a program's launch costs the chip's host by its OUTPUT buffers (PR
    29), so a prefill returns 4 of them whatever the depth. ``unpack_state``
    gives the model's own tree back; a dense cache is left as it is."""
    from fedml_tpu.train.llm.generation import _prefill_fn, decode_model

    cfg = dataclasses.replace(CFG, decode=True)
    toks = jnp.asarray(_tokens(16)[None])
    row, _ = _prefill_fn(cfg, 1, 16)(params, toks, jnp.int32(11), jnp.int32(5))
    n_mamba = sum(1 for k in CFG.layer_pattern if k == "mamba")
    assert set(row[mamba.PACKED]) == {"conv", "ssm", "snap_conv", "snap_ssm"}
    assert {v.shape[0] for v in row[mamba.PACKED].values()} == {n_mamba}
    assert not any(layer in row for layer in mamba.mamba_layers(CFG))
    _, plain = decode_model(cfg).apply({"params": params}, toks, mutable=["cache"],
                                       seq_lens=jnp.asarray([11]), snap_lens=jnp.asarray([5]))
    back = mamba.unpack_state(cfg, row)
    for layer in mamba.mamba_layers(CFG):
        for name, leaf in plain["cache"][layer]["mamba"].items():
            # the jitted program against an eager apply: another order of float32 sums, 1e-7 here
            np.testing.assert_allclose(np.asarray(back[layer]["mamba"][name]), np.asarray(leaf), rtol=0, atol=1e-5)
    dense_cache = {"layer_0": {"attn": {"k": jnp.zeros((1, 4, 2, 8))}}}
    assert mamba.pack_state(DENSE, dense_cache) is dense_cache and mamba.unpack_state(DENSE, dense_cache) is dense_cache

"""A latent layer's prefill and suffix passes through the rows kernel, on the CPU
with the kernel interpreted: ``flash_attention_rows`` with values narrower
than its keys against its plain formulation, the latent layer's row-cache pass
through the kernel against the loop over every key block
(``mla.expanded_latent_attention``) at a Kimi-Linear-shaped and an
openPangu-shaped width, the shape rule that picks between the two, and a tiny
Kimi-shaped engine that serves the same tokens either way."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core import telemetry as tel
from fedml_tpu.models import mla
from fedml_tpu.ops.flash_attention import flash_attention_rows, flash_attention_rows_reference
from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
from fedml_tpu.train.llm import generation
from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys
from tests import test_kimi_serving as kimi
from tests import test_latent_moe_serving as pangu


@pytest.mark.parametrize("T,off,S,Hq,Hkv,window", [
    (100, 37, 256, 4, 4, 0),      # one q block, T off the block grid
    (160, 64, 512, 4, 2, 0),      # two q blocks of 128 (the second padded), GQA
    (48, 200, 256, 4, 4, 0),      # the pass ends at the row's end
    (200, 0, 384, 2, 2, 100),     # a window, a row of three 128-key blocks
])
def test_the_rows_kernel_takes_values_of_a_width_of_their_own(T, off, S, Hq, Hkv, window):
    rng = np.random.default_rng(T + off)
    D, Dv = 48, 32
    q = jnp.asarray(rng.normal(size=(1, T, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, Hkv, Dv)), jnp.float32)
    got = flash_attention_rows(q, k, v, jnp.int32(off), window=window)
    want = flash_attention_rows_reference(q, k, v, jnp.int32(off), window=window)
    assert got.shape == want.shape == (1, T, Hq, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    # an explicit scale is the default's where they agree, and moves the result where they do not
    same = flash_attention_rows(q, k, v, jnp.int32(off), window=window, scale=D ** -0.5)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(got))
    hotter = flash_attention_rows(q, k, v, jnp.int32(off), window=window, scale=2 * D ** -0.5)
    assert float(jnp.max(jnp.abs(hotter - got))) > 1e-2


def _layer_cfg(hf):
    cfg = config_from_hf_keys(hf, max_seq_len=256, dtype=jnp.float32, remat=False)
    return dataclasses.replace(cfg, decode=True, attention_impl="xla")


SHAPES = {"kimi": kimi.HF, "pangu": pangu.HF}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("T,off", [(40, 70), (150, 30), (64, 0)])
def test_a_row_cache_pass_through_the_kernel_equals_the_loop(shape, T, off, monkeypatch):
    """The layer's pass over a row cache at a suffix offset, ``kv_len = off + T`` off the 256-key block,
    with NaN planted in the row past ``kv_len``: the kernel's path reads none of it, and says what the loop
    says over the same row with zeros there (the loop's last block would carry the NaN into ``p @ v``)."""
    cfg = _layer_cfg(SHAPES[shape])
    assert (cfg.q_lora_rank == 0 and not cfg.use_rope) if shape == "kimi" else (cfg.q_lora_rank and cfg.use_rope)
    layer = mla.LatentAttention(cfg)
    rng = np.random.default_rng(T)
    x = jnp.asarray(rng.normal(size=(1, T, cfg.d_model)), jnp.float32)
    pos = off + jnp.arange(T)[None]
    params = layer.init(jax.random.PRNGKey(0), x, pos)["params"]
    W, kv_len = cfg.latent_row_width, off + T
    row = np.zeros((1, cfg.max_seq_len, W), np.float32)
    row[0, :off, :cfg.kv_lora_rank + cfg.qk_rope_head_dim] = rng.normal(size=(off, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
    poisoned = row.copy()
    poisoned[0, kv_len:] = np.nan

    def run(impl, latents):
        monkeypatch.setattr(mla, "latent_row_attention", lambda *a: impl)
        out, state = layer.apply({"params": params, "cache": {"latent": jnp.asarray(latents), "idx": jnp.int32(off)}},
                                 x, pos, mutable=["cache"])
        return np.asarray(out), state["cache"]

    got, cache = run("kernel", poisoned)
    want, _ = run("loop", row)
    assert np.isfinite(got).all() and int(cache["idx"]) == kv_len
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5)


def test_the_shape_rule_picks_by_the_pass_against_its_row():
    rule = mla.latent_row_attention
    # Kimi-Linear's row of 16,896: the long passes take the kernel, a 512- or 1,024-token turn keeps the loop
    assert rule("tpu", 16384, 16896, 32) == rule("tpu", 8192, 16896, 32) == rule("tpu", 2048, 16896, 32) == "kernel"
    assert rule("tpu", 1024, 16896, 32) == rule("tpu", 512, 16896, 32) == "loop"
    # openPangu's row of 2,048: a 1,024-token turn takes it, a 512-token one does not
    assert rule("tpu", 1024, 2048, 128) == rule("tpu", 768, 2048, 128) == "kernel"
    assert rule("tpu", 512, 2048, 128) == "loop"
    assert rule("tpu", 1, 16896, 32) == "loop"                   # generate()'s token steps
    assert rule("tpu", 4096, 1000, 8) == "loop"                  # a row no block of the kernel tiles
    assert rule("cpu", 16384, 16896, 32) == "loop"               # interpreted: never by itself


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_cache_free_forward_keeps_the_loop_and_its_gradient(shape, monkeypatch):
    """A training forward has no row cache: the rule is not asked, and the loop's gradient flows."""
    def refuse(*a):
        raise AssertionError("the cache-free forward asked for the rows kernel")

    monkeypatch.setattr(mla, "latent_row_attention", refuse)
    cfg = dataclasses.replace(_layer_cfg(SHAPES[shape]), decode=False)
    layer = mla.LatentAttention(cfg)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 24, cfg.d_model)), jnp.float32)
    pos = jnp.arange(24)[None]
    params = layer.init(jax.random.PRNGKey(2), x, pos)["params"]
    grads = jax.grad(lambda p: jnp.mean(layer.apply({"params": p}, x, pos) ** 2))(params)
    g = np.asarray(grads["kv_b_proj"]["kernel"])
    assert np.isfinite(g).all() and np.abs(g).max() > 0


def test_a_kimi_shaped_engine_serves_the_same_tokens_through_the_kernel(monkeypatch):
    """Three prompts behind one system prompt, served with the rule as the CPU answers it (the loop) and
    with the kernel forced for every pass: the same tokens, and each prefill span names its formulation."""
    params = kimi._params(kimi.CFG)
    system = kimi._toks(40, 5)
    prompts = [system + kimi._toks(n, 60 + n) for n in (9, 30)] + [kimi._toks(50, 70)]

    def serve(rule):
        monkeypatch.setattr(mla, "latent_row_attention", rule)
        monkeypatch.setattr(generation, "_COMPILED", {})
        tel.reset()
        eng = PagedContinuousBatchingEngine(params, kimi.CFG, num_slots=2, chunk=4, page_size=kimi.PS,
                                            num_pages=48, state_snapshots=2)
        try:
            served = [eng.generate(p, 8) for p in prompts]
            hits = eng.stats()["kv_prefix_hits"]
        finally:
            eng.shutdown()
        said = [s["attrs"]["latent_attn"] for s in tel.snapshot()["spans"] if s["name"] == "serving.cb.prefill"]
        return served, hits, said

    loop, hits, said_loop = serve(mla.latent_row_attention)
    kernel, _, said_kernel = serve(lambda platform, T, S, h: "kernel" if T > 1 else "loop")
    assert hits >= 1 and kernel == loop
    assert said_loop == ["loop"] * 3 and said_kernel == ["kernel"] * 3
    assert kimi._gap(params, prompts[1], kernel[1]) < kimi.GAP_TOL

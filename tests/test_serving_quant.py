"""Weight-only int8 serving quantization (serving/quant.py).

The decode path re-reads every dense kernel per generated token; int8
weights halve that HBM traffic. These tests pin the layout transform, the
numerics (per-channel symmetric), and the end-to-end decode path under
``weight_quant="int8"``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
from fedml_tpu.serving.quant import dequantize_params_int8, quantize_params_int8


def _small_cfg(**kw):
    return TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=32, dtype=jnp.float32, remat=False, **kw,
    )


@pytest.fixture(scope="module")
def fp_model():
    cfg = _small_cfg()
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def test_quantize_layout_and_roundtrip(fp_model):
    _cfg, _model, params = fp_model
    q = quantize_params_int8(params)
    leaves = jax.tree_util.tree_leaves_with_path(q)
    kq = [v for p, v in leaves if "kernel_q" in jax.tree_util.keystr(p)]
    assert kq and all(v.dtype == jnp.int8 for v in kq)
    assert not any("'kernel'" in jax.tree_util.keystr(p) for p, _ in leaves)
    # non-kernel leaves (embed, norms) untouched
    emb_q = q["embed"]["embedding"]
    np.testing.assert_array_equal(np.asarray(emb_q), np.asarray(params["embed"]["embedding"]))
    # per-channel symmetric round-trip error is bounded by scale/2 per entry
    deq = dequantize_params_int8(q)
    for path, orig in jax.tree_util.tree_leaves_with_path(params):
        key = jax.tree_util.keystr(path)
        if "kernel" in key and getattr(orig, "ndim", 0) == 2:
            rebuilt = deq
            for part in [p.key for p in path]:
                rebuilt = rebuilt[part]
            absmax = np.abs(np.asarray(orig)).max(axis=0)
            tol = (absmax / 127.0) * 0.51 + 1e-8
            assert (np.abs(np.asarray(rebuilt) - np.asarray(orig)) <= tol[None, :]).all()


def test_quantize_handles_frozendict_and_refuses_kernel_free_tree(fp_model):
    """A flax FrozenDict tree used to pass through UNQUANTIZED while the cfg
    still flipped to int8 (ADVICE r4) — Mapping-based matching quantizes it,
    and a tree with no 2D kernel at all is rejected outright."""
    import flax.core

    _cfg, _model, params = fp_model
    q = quantize_params_int8(flax.core.freeze(params))
    kq = [v for p, v in jax.tree_util.tree_leaves_with_path(q)
          if "kernel_q" in jax.tree_util.keystr(p)]
    assert kq and all(v.dtype == jnp.int8 for v in kq)
    with pytest.raises(ValueError, match="no 2D 'kernel' leaf"):
        quantize_params_int8({"embed": {"embedding": jnp.zeros((4, 4, 1))}})


def test_int8_logits_close_to_fp(fp_model):
    cfg, model, params = fp_model
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qparams = quantize_params_int8(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    fp = model.apply({"params": params}, tokens)
    q = TransformerLM(qcfg).apply({"params": qparams}, tokens)
    assert fp.shape == q.shape
    # per-channel int8 keeps logits tightly aligned: top-1 agreement high
    agree = float((fp.argmax(-1) == q.argmax(-1)).mean())
    assert agree > 0.9, agree
    rel = float(jnp.linalg.norm(fp - q) / jnp.linalg.norm(fp))
    assert rel < 0.1, rel


def test_int8_decode_end_to_end(fp_model):
    from fedml_tpu.train.llm.generation import generate

    cfg, _model, params = fp_model
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qparams = quantize_params_int8(params)
    prompt = jnp.asarray([[3, 5, 7, 11]], jnp.int32)
    out = generate(qparams, qcfg, prompt, max_new_tokens=8, temperature=0.0)
    toks = np.asarray(out)
    assert toks.shape == (1, 8)  # generate returns the NEW tokens
    assert (toks >= 0).all() and (toks < cfg.vocab_size).all()
    # NOTE: no fp-vs-int8 sequence match here — on a random-init model the
    # near-uniform logits make greedy decoding diverge permanently after one
    # argmax flip; single-step top-1 agreement (the meaningful quality
    # metric) is pinned in test_int8_logits_close_to_fp. Decode must at
    # least be deterministic:
    out2 = generate(qparams, qcfg, prompt, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(toks, np.asarray(out2))


def test_int8_weights_through_the_engine_match_generate(fp_model):
    """Weight-only int8 served through the continuous-batching engine: the
    paged step reads the same quantised tree ``generate()`` does, token for
    token, for requests of several lengths interleaved across two slots."""
    from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine
    from fedml_tpu.serving.quant import quantize_model_int8
    from fedml_tpu.train.llm.generation import generate

    cfg, _model, params = fp_model
    qcfg, qparams = quantize_model_int8(cfg, params)
    assert qcfg.weight_quant == "int8"
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (3, 8, 13, 5)]
    eng = PagedContinuousBatchingEngine(qparams, qcfg, num_slots=2, chunk=4, page_size=8)
    try:
        handles = [eng.submit(p, 9) for p in prompts]
        for p, h in zip(prompts, handles):
            want = generate(qparams, qcfg, jnp.asarray([p], jnp.int32), 9)
            assert h.result(timeout=120) == np.asarray(want)[0].tolist()
        assert eng._alloc.check_leaks()["accounted"]
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_from_checkpoint_int8_serves(tmp_path):
    """The user-facing serving entry (LLMPredictor.from_checkpoint) exposes
    the int8 mode end-to-end: HF llama checkpoint -> quantized predictor ->
    text out."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    from fedml_tpu.serving.fedml_predictor import LLMPredictor
    from fedml_tpu.train.llm.tokenizer import train_bpe

    hf_cfg = transformers.LlamaConfig(
        vocab_size=300, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=64,
        max_position_embeddings=64, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    ckpt = str(tmp_path / "tiny_llama")
    transformers.LlamaForCausalLM(hf_cfg).eval().save_pretrained(
        ckpt, safe_serialization=True)
    tok = train_bpe(["serving quantization test corpus " * 8] * 4, vocab_size=280)
    tok.save(f"{ckpt}/tokenizer.json")

    predictor = LLMPredictor.from_checkpoint(ckpt, quantize="int8",
                                             default_max_new_tokens=4)
    assert predictor._cfg.weight_quant == "int8"
    out = predictor.predict({"prompt": "quantized", "max_new_tokens": 4})
    assert isinstance(out.get("text"), str)

    with pytest.raises(ValueError, match="unknown quantize mode"):
        LLMPredictor.from_checkpoint(ckpt, quantize="fp4")


def test_int8_decode_logits_close_to_fp(fp_model):
    """The DECODE path's int8 numerics (distinct from the forward-pass test
    above: decode runs the cache_idx/KV-cache kernels the serving engine
    uses): stepped int8 logits track stepped fp logits closely enough that
    top-1 agreement stays high at every position."""
    from fedml_tpu.train.llm.generation import decode_model

    cfg, _model, params = fp_model
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qparams = quantize_params_int8(params)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0, cfg.vocab_size)

    def stepped_logits(model, p):
        positions = jnp.broadcast_to(jnp.arange(4), (2, 4))
        logits, state = model.apply(
            {"params": p}, toks[:, :4], positions=positions, mutable=["cache"])
        outs = [logits]
        cache = state["cache"]
        for t in range(4, 10):
            pos = jnp.full((2, 1), t, jnp.int32)
            step, state = model.apply(
                {"params": p, "cache": cache}, toks[:, t:t + 1],
                positions=pos, mutable=["cache"])
            cache = state["cache"]
            outs.append(step)
        return jnp.concatenate(outs, axis=1)  # [2, 10, V]

    fp = stepped_logits(decode_model(cfg), params)
    q = stepped_logits(decode_model(qcfg), qparams)
    agree = float((fp.argmax(-1) == q.argmax(-1)).mean())
    assert agree > 0.9, agree
    rel = float(jnp.linalg.norm(fp - q) / jnp.linalg.norm(fp))
    assert rel < 0.1, rel


def test_int8_generate_no_retrace(fp_model):
    """The r05 regression class bench.py now guards with compile counters:
    int8 decode retracing per call (or per step) is what turned 370k tok/s
    into 985. After one warm call, repeated int8 generate calls — including
    different runtime temperatures — must add ZERO compiles of the decode
    scan or prefill."""
    from fedml_tpu.core import telemetry as tel
    from fedml_tpu.train.llm.generation import generate

    cfg, _model, params = fp_model
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qparams = quantize_params_int8(params)
    prompt = jnp.asarray([[3, 5, 7, 11]], jnp.int32)
    generate(qparams, qcfg, prompt, max_new_tokens=8)  # warm
    d0 = tel.compile_count("decode_scan")
    p0 = tel.compile_count("prefill")
    for temp in (0.0, 0.0, 0.7):
        generate(qparams, qcfg, prompt, max_new_tokens=8, temperature=temp)
    # temperature>0 selects the SAMPLED decode executable (a static branch,
    # one extra legitimate compile the first time it is ever used); the
    # greedy repeats must be exactly zero new compiles
    assert tel.compile_count("prefill") == p0
    assert tel.compile_count("decode_scan") <= d0 + 1
    d1 = tel.compile_count("decode_scan")
    generate(qparams, qcfg, prompt, max_new_tokens=8, temperature=0.9)
    assert tel.compile_count("decode_scan") == d1  # sampled path now warm too

"""``correct`` has to be able to fail. The control (the plain reference put in
the program's place one precision down: int8 matmul operands) comes out as not
correct, and so does each fault the cells can have, planted under a run that
skips only the harness's look for a chip: a step that returns its state
unchanged, half of the batch left out (the mean over the rest), a token altered
where it is produced. Sizes are what a test can hold; the chip readings at the
cells' own sizes are in PERF.md."""

import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

import fixture_root

fixture_root.bench_imports()

import compare  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import run as bench_run  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return fixture_root.make_root(tmp_path_factory.mktemp("bench"))


def _result(root, cell, seed=31):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1", "--trace", "0"],
                            root=root, allow_cpu=True)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _train_driver(root):
    return harness.load_module(os.path.join(root, "benchmark", "drivers", "llm_train.py"))


def _break_step(monkeypatch, drv, breaker):
    """Wrap the trainer's compiled step, the object the window drives."""
    real_build = drv.build_trainer

    def build(ctx):
        trainer = real_build(ctx)
        inner_build = trainer._build

        def _build(params):
            inner_build(params)
            trainer._step_fn = breaker(trainer._step_fn)

        trainer._build = _build
        return trainer

    monkeypatch.setattr(drv, "build_trainer", build)


def test_sound_train_run_is_correct(root):
    out = _result(root, "tiny_lora")
    assert out["correct"] is True
    assert out["compared"]["frozen_moved"] == {"value": 0.0, "limit": 0.0}


def test_state_returned_unchanged_is_not_correct(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def breaker(step):
        def broken(p, o, toks, mask):
            keep = jax.tree.map(jnp.copy, (p, o))     # the step donates its state
            return keep[0], keep[1], step(p, o, toks, mask)[2]
        return broken

    _break_step(monkeypatch, _train_driver(root), breaker)
    out = _result(root, "tiny_lora")
    assert out["correct"] is False
    # nothing moved: the gap of norms reads 1 on the change and on the gradient
    assert out["compared"]["delta_gap"]["value"] == pytest.approx(1.0)
    assert out["compared"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    def breaker(step):
        def broken(p, o, toks, mask):
            keep = np.zeros((mask.shape[0], 1), np.float32)
            keep[: mask.shape[0] // 2] = 1.0
            return step(p, o, toks, mask * keep)      # the mean is taken over the rest
        return broken

    _break_step(monkeypatch, _train_driver(root), breaker)
    out = _result(root, "tiny_lora")
    assert out["correct"] is False
    over = [k for k, r in out["compared"].items() if r["value"] > r["limit"]]
    assert "grad_gap" in over


def test_frozen_weights_that_move_are_not_correct(root, monkeypatch):
    import jax

    def breaker(step):
        def broken(p, o, toks, mask):
            p2, o2, loss = step(p, o, toks, mask)
            p2 = jax.tree.map(lambda x: x, p2)
            p2["final_norm"]["scale"] = p2["final_norm"]["scale"] * 1.001
            return p2, o2, loss
        return broken

    _break_step(monkeypatch, _train_driver(root), breaker)
    out = _result(root, "tiny_lora")
    assert out["correct"] is False and out["compared"]["frozen_moved"]["value"] > 0


def _control_ctx(root, cell, seed):
    c = harness.Cell(root, cell)

    class Ctx:
        config, workload, traffic = c.config, c.workload, c.traffic

    Ctx.seed = seed
    return Ctx


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_int8_reference_is_not_correct(root, seed):
    import traffic

    drv = _train_driver(root)
    ctx = _control_ctx(root, "tiny_lora", seed)
    p, tr = ctx.workload["program"], ctx.traffic
    batches = [traffic.packed_batch(tr, seed, i, p["batch_sequences"], ctx.config["vocab_size"])
               for i in range(drv.CHECK_STEPS)]
    shapes = {k: tuple(v) for k, v in _tiny_train_shapes(ctx).items()}
    ref = drv.follow_reference(ctx, shapes, batches)
    control = drv.follow_reference(ctx, shapes, batches, quant=reference.int8_quant)
    sound = drv.follow_reference(ctx, shapes, batches, quant=reference.bf16_quant)
    v_control, v_sound = compare.Verdict(), compare.Verdict()
    drv.judge(v_control, control, ref, ctx.workload["limits"])
    drv.judge(v_sound, sound, ref, ctx.workload["limits"])
    assert v_sound.correct is True          # the stated precision (bf16) passes
    assert v_control.correct is False       # one precision down does not


def _tiny_train_shapes(ctx):
    c, r = ctx.config, ctx.workload["program"].get("lora_rank", 0)
    d, hd, h, kv, ff, v = (c["hidden_size"], c["head_dim"], c["num_attention_heads"],
                           c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"])
    out = {"embed/embedding": (v, d), "final_norm/scale": (d,), "lm_head/kernel": (d, v)}
    for i in range(c["num_hidden_layers"]):
        L = f"layer_{i}"
        out[f"{L}/attn_norm/scale"] = (d,)
        out[f"{L}/mlp_norm/scale"] = (d,)
        for name, (a, b) in {"q_proj": (d, h * hd), "k_proj": (d, kv * hd), "v_proj": (d, kv * hd),
                             "o_proj": (h * hd, d)}.items():
            out[f"{L}/attn/{name}/kernel"] = (a, b)
            out[f"{L}/attn/{name}/lora_a"] = (a, r)
            out[f"{L}/attn/{name}/lora_b"] = (r, b)
        for name, (a, b) in {"gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}.items():
            out[f"{L}/mlp/{name}/kernel"] = (a, b)
    return out


def test_the_program_has_the_leaves_the_reference_expects(root):
    drv = _train_driver(root)
    c = harness.Cell(root, "tiny_lora")

    class Ctx:
        config, workload, traffic, seed, out_dir = c.config, c.workload, c.traffic, 1, os.path.join(root, ".o")

    trainer = drv.build_trainer(Ctx)
    assert drv.param_shapes(trainer, c.traffic["seq_len"]) == _tiny_train_shapes(Ctx)


def test_sound_chat_run_is_correct(root):
    assert _result(root, "tiny_chat")["correct"] is True


def test_token_altered_where_it_is_produced_is_not_correct(root, monkeypatch):
    import jax.numpy as jnp
    from fedml_tpu.serving import continuous_batching, paged_kv
    from fedml_tpu.train.llm import generation

    def second_best(logits, key, temperature):      # the runner-up instead of the best
        return jnp.argsort(logits, axis=-1)[..., -2]

    monkeypatch.setattr(generation, "_COMPILED", {})
    for mod in (generation, paged_kv, continuous_batching):
        monkeypatch.setattr(mod, "_sample", second_best)
    out = _result(root, "tiny_chat")
    monkeypatch.setattr(generation, "_COMPILED", {})
    assert out["correct"] is False
    assert out["failed"] == 0                         # every reply came, and said the wrong thing
    assert out["compared"]["widest_logit_gap"]["value"] > out["compared"]["widest_logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_int8_reference_is_not_correct(root, seed):
    """Tokens decoded greedily by the reference at the stated precision (bf16
    operands) stay inside the limit; the tokens int8 operands put first do not."""
    import jax
    import jax.numpy as jnp
    import weights

    drv = harness.load_module(os.path.join(root, "benchmark", "drivers", "llm_serve.py"))
    ctx = _control_ctx(root, "tiny_chat", seed)
    ctx.traffic = dict(ctx.traffic, max_new_tokens={"values": [40], "weights": [1]})
    limit = ctx.workload["limits"]["widest_logit_gap"]
    rng = np.random.default_rng(seed)
    shapes = {k: v for k, v in _tiny_train_shapes(ctx).items() if "lora" not in k}
    params = weights.make_params(shapes, seed, jnp.bfloat16)
    cfg = reference.norm_cfg(ctx.config)
    step = jax.jit(lambda p, t, r: jnp.argmax(reference.logits_at(p, t, r, cfg, reference.bf16_quant)[0]))
    requests, sample = [], []
    for i in range(8):
        seq, P = np.zeros(128, np.int32), 40
        seq[:P] = rng.integers(1, 512, P)
        for j in range(40):
            seq[P + j] = int(step(params, jnp.asarray(seq), jnp.asarray([P + j - 1])))
        requests.append({"prompt": seq[:P].tolist(), "system": False})
        sample.append({"index": i, "tokens": seq[P:P + 40].tolist()})
    chk = drv.check_sample(ctx, params, sample, requests, quant=reference.int8_quant)
    assert chk["tokens"] == 320
    assert chk["widest_logit_gap"] <= limit < chk["control_widest_gap"]
    assert chk["mean_logit_gap"] < 1e-4 < 3e-4 < chk["control_mean_gap"]   # the steadier number, 10x apart

"""Driver ``llm_train``: a LoRA fine-tuning step through ``LLMTrainer.train()``.

Set-up builds ONE trainer (the compiled step with its state) on weights made
from the seed, drives it through its first three steps with the window's own
call (``train(batches)``, batches from the window's own feed), times four
warm steps, and hands that same object to the window: one measured
``train()`` call of ``floor(seconds / step_s)`` steps. The plain reference
follows the first three steps after the window has closed and the program's
state is freed.

``train()`` ends every call with an unconditional full checkpoint (a
device_get of the whole f32 tree and an orbax write, 4.6 GB at this cell's
size). It lies outside the ``llm.train`` span and outside every metric here,
and a run may not write gigabytes to disk, so the driver replaces
``trainer.save`` with a no-op; see PERF.md (Open questions).
"""

from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

import compare
import flops
import harness
import reference
import traffic
import weights

CHECK_STEPS = 3
TIMING_STEPS = 4


def build_trainer(ctx):
    """The program's trainer for this cell (tests break it from here)."""
    from fedml_tpu.train.llm.configurations import (
        DatasetArguments, ExperimentArguments, ModelArguments)
    from fedml_tpu.train.llm.llm_trainer import LLMTrainer

    c, p, tr = ctx.config, ctx.workload["program"], ctx.traffic
    ma = ModelArguments(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], seq_len=tr["seq_len"], rope_theta=float(c["rope_theta"]),
        attention_impl=p["attention_impl"], lora_rank=p["lora_rank"], lora_alpha=float(p["lora_alpha"]),
        remat=bool(p["remat"]), remat_policy=p.get("remat_policy", "full"))
    ea = ExperimentArguments(
        learning_rate=p["learning_rate"], weight_decay=p["weight_decay"], warmup_steps=p["warmup_steps"],
        max_steps=p["max_steps"], per_device_batch_size=p["batch_sequences"], grad_clip=p["grad_clip"],
        seed=ctx.seed & 0x7FFFFFFF, output_dir=os.path.join(ctx.out_dir, "ckpt"))
    trainer = LLMTrainer(ma, DatasetArguments(), ea)
    trainer.save = lambda *a, **k: None  # no 4.6 GB checkpoint per train() call (module docstring)
    return trainer


def param_shapes(trainer, seq_len: int) -> dict:
    import jax
    import jax.numpy as jnp

    dummy = jnp.zeros((1, seq_len), jnp.int32)
    tree = jax.eval_shape(lambda k: trainer.model.init(k, dummy)["params"], jax.random.PRNGKey(0))
    return weights.shapes_of(tree)


def leaf_norms(tree_by_path: dict) -> dict:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64))))) for k, v in tree_by_path.items()}


def adapters_of(params) -> dict:
    import jax

    return {k: np.asarray(jax.device_get(v)) for k, v in weights.flatten(params).items() if weights.is_adapter(k)}


def frozen_checksum(params) -> float:
    import jax
    import jax.numpy as jnp

    frozen = [v for k, v in sorted(weights.flatten(params).items()) if not weights.is_adapter(k)]
    fn = jax.jit(lambda xs: sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in xs))
    return float(fn(frozen))


def adam_mu(opt_state) -> dict:
    """Adam's first moment of the adapter leaves, by path below ``.mu``."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = [getattr(p, "name", None) for p in path]
        if "mu" in names:
            tail = path[names.index("mu") + 1:]
            out["/".join(str(getattr(p, "key", p)) for p in tail)] = np.asarray(jax.device_get(leaf))
    return out


def reference_opt(ctx) -> dict:
    p = ctx.workload["program"]
    return {k: p[k] for k in ("lora_alpha", "lora_rank", "learning_rate", "warmup_steps",
                              "max_steps", "grad_clip", "weight_decay")}


def follow_reference(ctx, shapes: dict, batches, quant=None, keep_rows=None) -> dict:
    """The plain reference over the first steps -> losses and leaf norms."""
    import jax.numpy as jnp

    params = weights.make_params(shapes, ctx.seed, jnp.float32)
    cfg = dict(reference.norm_cfg(ctx.config))
    out = reference.lora_steps(params, batches, cfg, reference_opt(ctx), quant=quant, keep_rows=keep_rows)
    del params
    grad = {k: np.asarray(v) for k, v in weights.flatten(out["first_grad"]).items()}
    return {"losses": out["losses"], "grad": leaf_norms(grad), "grad_vec": grad,
            "delta": leaf_norms(weights.flatten(out["delta"]))}


def judge(verdict: compare.Verdict, program: dict, ref: dict, limits: dict) -> None:
    """Program readings against the reference's, each beside its limit."""
    for i, (lp, lr_) in enumerate(zip(program["losses"], ref["losses"])):
        verdict.add(f"loss{i + 1}_gap", compare.rel_gap(lp, lr_), limits.get("loss_gap"))
    verdict.add("grad_gap", compare.worst_leaf_gap(program["grad"], ref["grad"]), limits.get("grad_gap"))
    still = compare.still_leaves(ref["grad"])
    verdict.add("grad_dir_gap", compare.worst_leaf_turn(program["grad_vec"], ref["grad_vec"], skip=still),
                limits.get("grad_dir_gap"))
    verdict.add("delta_gap", compare.worst_leaf_gap(program["delta"], ref["delta"], skip=still),
                limits.get("delta_gap"))
    if "frozen_moved" in program:
        verdict.add("frozen_moved", program["frozen_moved"], limits.get("frozen_moved"))


def first_steps(ctx, trainer, batches) -> dict:
    """Steps 1..3 through the window's own call; what the reference follows."""
    b1 = 0.9
    params0 = adapters_of(trainer.params)
    m1 = trainer.train(iter(batches[:1]))
    grad = {k: v / (1.0 - b1) for k, v in adam_mu(trainer.opt_state).items()}
    m23 = trainer.train(iter(batches[1:CHECK_STEPS]))
    after = adapters_of(trainer.params)
    return {"losses": [m1["first_loss"], m23["first_loss"], m23["final_loss"]],
            "grad": leaf_norms(grad), "grad_vec": grad,
            "delta": leaf_norms({k: after[k] - params0[k] for k in after})}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    p, tr, c = ctx.workload["program"], ctx.traffic, ctx.config
    batch, seq_len, vocab = int(p["batch_sequences"]), int(tr["seq_len"]), int(c["vocab_size"])
    tokens_per_step = batch * seq_len

    def batch_at(i):
        return traffic.packed_batch(tr, ctx.seed, i, batch, vocab)

    # ---- set-up: weights, trainer, first steps, warm timing ------------------
    trainer = build_trainer(ctx)
    shapes = param_shapes(trainer, seq_len)
    ctx.log("trainer object and parameter shapes")
    params = weights.make_params(shapes, ctx.seed, jnp.float32)
    jax.block_until_ready(params)
    ctx.log("weights made")
    frozen0 = frozen_checksum(params)
    ctx.log("frozen weights summed")
    trainer._build(params)
    del params
    ctx.log("trainer built")
    check_batches = [batch_at(i) for i in range(CHECK_STEPS)]
    program = first_steps(ctx, trainer, check_batches)
    ctx.log(f"first steps: losses {program['losses']}")
    t = time.perf_counter()
    trainer.train(iter([batch_at(CHECK_STEPS + i) for i in range(TIMING_STEPS)]))
    step_s = (time.perf_counter() - t) / TIMING_STEPS
    n_steps = max(int(ctx.workload.get("min_steps", 10)), int(math.floor(ctx.seconds / step_s)))
    first = CHECK_STEPS + TIMING_STEPS
    window_batches = [batch_at(first + i) for i in range(n_steps)]
    ctx.log(f"warm step {step_s:.4f} s -> window of {n_steps} steps")

    # ---- the window: ONE train() call -----------------------------------------
    t0 = time.perf_counter()
    metrics = trainer.train(iter(window_batches))
    t1 = time.perf_counter()
    if metrics["steps"] != n_steps:
        raise harness.HarnessError(f"train() ran {metrics['steps']} steps, not {n_steps}")
    window = {"t0": t0, "t1": t1, "seconds": t1 - t0, "steps": n_steps,
              "tokens": n_steps * tokens_per_step, "tokens_per_step": tokens_per_step,
              "program_tokens_per_s": metrics["tokens_per_sec"],
              "compiles": ctx.compile_log.between(t0, t1),
              "step_flops": flops.train_step_flops(c, p["lora_rank"], batch, seq_len)}
    ctx.log(f"window: {n_steps} steps in {t1 - t0:.3f} s, final loss {metrics['final_loss']:.4f}, "
            f"compiles in window {len(window['compiles'])}")

    # ---- a traced call of a few steps, of its own -------------------------------
    traced = None
    if ctx.trace:
        k = int(ctx.workload.get("trace_steps", 8))
        traced_batches = [batch_at(first + n_steps + i) for i in range(k)]
        ctx.tracer.start()
        with harness.span("train_call"):
            trainer.train(iter(traced_batches))
        raw = ctx.tracer.stop()
        traced = ctx.tracer.reduce.reduce(raw)
        traced.update(steps=k, raw=raw)
        ctx.log(f"traced {k} steps: busy {traced['busy_s']:.3f} s of {traced['window_s']:.3f} s")

    # ---- close: peak memory, frozen weights, free the program -------------------
    program["frozen_moved"] = abs(frozen_checksum(trainer.params) - frozen0)
    peak = harness.memory_peak_bytes(ctx.cell.chips)
    trainer.params = trainer.opt_state = trainer._step_fn = None
    del trainer
    gc.collect()

    # ---- the plain reference follows the first three steps ----------------------
    t = time.perf_counter()
    ref = follow_reference(ctx, shapes, check_batches)
    ctx.log(f"reference: losses {ref['losses']} in {time.perf_counter() - t:.1f} s")
    verdict = compare.Verdict()
    judge(verdict, program, ref, ctx.workload["limits"])

    return {
        "attempted": n_steps, "failed": 0, "verdict": verdict, "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": window["tokens"] / window["seconds"],
                       "setup_s": t0 - ctx.t_process_start},
        "window": window, "trace": traced,
    }

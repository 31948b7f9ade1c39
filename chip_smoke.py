#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

    python3 chip_smoke.py                 # on a machine with a TPU (the driver runs this)
    python3 chip_smoke.py --dry-run-cpu   # same phases, tiny sizes, CPU, interpreted kernels

ONE process holds the chip from start to finish and drives the system's main
paths once through the entry points a user would call, at the full width of
the models the repo supports (depth cut to what a 16 GB chip holds, weights
random from a seed):

  kernel    flash_attention fwd+bwd vs xla_attention at head_dim 128 (MHA + GQA at
            T 1,024, and the training cell's 32-on-8 heads at T 2,048)
  fedavg    fedml_tpu.run_simulation's body (FedMLRunner, sp backend): ResNet-56,
            CIFAR-10 shapes, batch 128, 4 clients/round, 3 rounds
  llm       LLMTrainer(...).train() at Llama-2-7B widths, seq 1024, LoRA r=8,
            pallas attention, bf16 activations
  serving   EndpointManager().deploy(...) around LLMPredictor(paged=True) at the
            same widths, bf16 weights, 8 /predict requests sharing a prefix
  multichip (only when jax.device_count() >= 4) the LLM step at fsdp=4 and
            fsdp=2 x tp=2 with per-chip memory checks, the sp/pp/ep/pp x ep
            steps of __graft_entry__.multichip_steps, FedOpt with
            server_mesh fsdp:4

Every phase must pass; nothing here catches a failure and carries on. The
second-to-last stdout line, ``chip_smoke: summary {...}``, is a JSON object
ending in ``"claim": null``: the wall times it holds are smoke timings on the
named device (compile included), never performance results. The last stdout
line is exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` with the device as JAX reports it — the driver parses that
line and accepts no other key. Exit code 0 only on a TPU with every phase
passing; with no accelerator it exits non-zero before compiling anything and
prints no result.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Real sizes. Depth is the one cut: at Llama-2-7B widths a block is 202M
# params and the LoRA step still differentiates every one of them (f32 params
# + f32 grads = 8 bytes/param), so LLM_LAYERS blocks + the two 131M-param
# vocab matrices must fit 16 GB with activations.
LLM_LAYERS = 2
REAL = dict(
    # (Hq, Hkv, T): MHA and GQA at the llm phase's length, and the shape of
    # the benchmark's training cell (mistral7b_lora_pack2k: 32 on 8, T 2,048)
    kernel=dict(batch=1, head_dim=128, cases=((32, 32, 1024), (32, 4, 1024), (32, 8, 2048))),
    fedavg=dict(model="resnet56", dataset="cifar10", batch_size=128,
                client_num_in_total=8, client_num_per_round=4, comm_round=3),
    llm=dict(vocab_size=32000, d_model=4096, n_layers=LLM_LAYERS, n_heads=32,
             n_kv_heads=32, d_ff=11008, seq_len=1024, per_device_batch=2, steps=6),
    serving=dict(max_seq_len=512, new_tokens=32, slots=4, prefix_words=96),
)
DRY = dict(
    kernel=dict(batch=1, head_dim=16, cases=((4, 4, 128), (4, 2, 128), (4, 1, 256))),
    fedavg=dict(model="lr", dataset="mnist", batch_size=32,
                client_num_in_total=8, client_num_per_round=4, comm_round=3),
    llm=dict(vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
             d_ff=128, seq_len=128, per_device_batch=2, steps=6),
    serving=dict(max_seq_len=256, new_tokens=8, slots=4, prefix_words=96),
)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


# one backend compile: the smoke phase it happened in, the jitted function,
# its seconds, and the value of the round engine's round counter at the time
CompileEvent = collections.namedtuple("CompileEvent", "phase fun secs round")


class CompileLog:
    """Every backend compile of the process, from JAX's own monitoring
    events. A persistent-cache hit still fires the event (its seconds are the
    load time), so hits and misses are counted beside it. A phase owns its
    dotted sub-phases (``multichip`` owns ``multichip.fsdp4``)."""

    def __init__(self):
        import jax.monitoring

        from fedml_tpu.core import telemetry as tel

        self._rounds = tel.counter("engine.rounds")
        self.phase = "startup"
        self.events = []  # CompileEvent
        self.cache = {}   # phase -> {"hits": n, "misses": n}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.events.append(CompileEvent(self.phase, str(kw.get("fun_name", "?")),
                                            float(secs), int(self._rounds.value)))

    def _on_event(self, event, **kw):
        if event in (CACHE_HIT_EVENT, CACHE_MISS_EVENT):
            row = self.cache.setdefault(self.phase, {"hits": 0, "misses": 0})
            row["hits" if event == CACHE_HIT_EVENT else "misses"] += 1

    @staticmethod
    def _owns(phase, name):
        return name == phase or name.startswith(phase + ".")

    def of(self, phase):
        return [e for e in self.events if self._owns(phase, e.phase)]

    def after_first_round(self, phase, rounds0):
        """Compiles of ``phase`` that happened once its first FedAvg round was
        over (``rounds0``: the round counter when the phase began)."""
        return [e for e in self.of(phase) if e.round - rounds0 >= 1]

    def summary(self, phase):
        ev = self.of(phase)
        rows = [row for name, row in self.cache.items() if self._owns(phase, name)]
        return {"compiles": len(ev),
                "compile_s": round(sum(e.secs for e in ev), 2),
                "cache_hits": sum(r["hits"] for r in rows),
                "cache_misses": sum(r["misses"] for r in rows)}


def check(cond, msg):
    """A failed check fails the script: phases never swallow one."""
    if not cond:
        raise AssertionError(msg)


def on_platform(tree, platform):
    import jax

    leaves = jax.tree.leaves(tree)
    check(leaves, "empty tree")
    for leaf in leaves:
        for d in leaf.devices():
            check(d.platform == platform, f"leaf lives on {d}, expected platform {platform}")


# ---------------------------------------------------------------------------
# phase: kernel parity
# ---------------------------------------------------------------------------

# Tolerances, as max|kernel - ref| / max|ref| per tensor (o, dq, dk, dv):
#  * f32 inputs under default_matmul_precision("highest"): kernel and
#    reference both contract in f32, so what is left is summation order and
#    the online-softmax rescaling. Measured on v5e (PR 21): <= 6.3e-5 over
#    T 8..4096, head_dim 16..256. 5e-4 leaves ~8x and still fails if either
#    side silently drops to bf16 passes (measured 2.4e-3..8.5e-3).
#  * bf16 inputs (what the trainer runs): the kernel rounds p and ds to bf16
#    before the PV / dS.K matmuls (the standard flash recipe), the reference
#    is f32 end to end from the same bf16-rounded inputs. Each rounding is
#    <= 2^-8 = 3.9e-3 relative and they chain over 2-3 matmuls. Measured on
#    v5e: 2.2e-3..5.5e-3. 2e-2 leaves ~4x.
#  The kernel runs INSIDE the "highest" context on purpose: bf16 operands
#  must pin their own matmul precision (Mosaic rejects bf16 x fp32-contract).
TOL = {"float32": 5e-4, "bfloat16": 2e-2}


def phase_kernel(sz):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import repeat_kv, xla_attention
    from fedml_tpu.ops.flash_attention import flash_attention

    B, D = sz["batch"], sz["head_dim"]
    worst = {}
    for hq, hkv, T in sz["cases"]:
        ks = jax.random.split(jax.random.PRNGKey(hq * 131 + hkv), 4)
        q32 = jax.random.normal(ks[0], (B, T, hq, D), jnp.float32)
        k32 = jax.random.normal(ks[1], (B, T, hkv, D), jnp.float32)
        v32 = jax.random.normal(ks[2], (B, T, hkv, D), jnp.float32)
        w = jax.random.normal(ks[3], (B, T, hq, D), jnp.float32)  # fixed dO

        def ref_loss(q, k, v):
            k, v = repeat_kv(k, v, hq)
            out = xla_attention(q, k, v, causal=True)
            return jnp.sum(out * w), out

        def ker_loss(q, k, v):
            out = flash_attention(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32) * w), out

        for dtype in (jnp.float32, jnp.bfloat16):
            q, k, v = (x.astype(dtype) for x in (q32, k32, v32))
            with jax.default_matmul_precision("highest"):
                # reference in f32 from the SAME (possibly bf16-rounded) inputs
                (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
                    ref_loss, argnums=(0, 1, 2), has_aux=True))(
                        *(x.astype(jnp.float32) for x in (q, k, v)))
                (_, o_ker), g_ker = jax.jit(jax.value_and_grad(
                    ker_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
            name = jnp.dtype(dtype).name
            for tag, a, b in zip(("o", "dq", "dk", "dv"),
                                 (o_ker,) + tuple(g_ker), (o_ref,) + tuple(g_ref)):
                a = jnp.asarray(a, jnp.float32)
                check(bool(jnp.all(jnp.isfinite(a))), f"kernel {tag} not finite ({hq}/{hkv} {name})")
                err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                worst[f"Hq{hq}_Hkv{hkv}_T{T}_{name}_{tag}"] = err
                check(err < TOL[name],
                      f"flash_attention {tag} off by {err:.3e} (> {TOL[name]}) at "
                      f"Hq={hq} Hkv={hkv} D={D} T={T} {name}")
    return {"shapes": [f"Hq{a}/Hkv{b}/D{D}/T{t}" for a, b, t in sz["cases"]],
            "max_rel_err": {k: float(f"{v:.3e}") for k, v in worst.items()}}


# ---------------------------------------------------------------------------
# phase: FedAvg through the runner
# ---------------------------------------------------------------------------

def _fedavg_args(sz, **over):
    import fedml_tpu as fedml

    return fedml.default_config(
        "simulation", backend="sp",
        model=sz["model"], dataset=sz["dataset"],
        # an empty cache dir: the loaders' deterministic synthetic surrogate
        # at the dataset's real shapes (nothing is downloaded anywhere)
        data_cache_dir="",
        # homo: every client holds the same number of samples, so one compiled
        # local round serves every client of every round
        partition_method="homo",
        client_num_in_total=sz["client_num_in_total"],
        client_num_per_round=sz["client_num_per_round"],
        comm_round=sz["comm_round"], epochs=1, batch_size=sz["batch_size"],
        learning_rate=0.03, frequency_of_the_test=1, random_seed=0, **over)


def _run_fedavg(args):
    """``fedml_tpu.run_simulation(args=args)``, spelled out so the runner (and
    through it the global model) stays reachable for the placement checks —
    these five calls ARE run_simulation's body."""
    import fedml_tpu as fedml

    args.training_type = "simulation"
    args = fedml.init(args)
    device = fedml.device.get_device(args)
    dataset, output_dim = fedml.data.load(args)
    model = fedml.model.create(args, output_dim)
    runner = fedml.FedMLRunner(args, device, dataset, model)
    runner.run()
    return runner.runner.fl_trainer, device


def phase_fedavg(sz, clog, platform):
    from fedml_tpu.core import telemetry as tel

    traces0 = {n: tel.compile_count(n) for n in ("local_train", "eval_batch", "agg_accum")}
    rounds0 = tel.counter("engine.rounds").value
    api, device = _run_fedavg(_fedavg_args(sz))
    check(device.platform == platform, f"get_device returned {device}")
    check(len(api.metrics_history) == sz["comm_round"], "one eval per round expected")
    for m in api.metrics_history:
        check(math.isfinite(m["test_loss"]) and 0.0 <= m["test_acc"] <= 1.0,
              f"bad round metrics {m}")
    on_platform(api.model_trainer.get_model_params(), platform)
    # rounds after the first must not compile anything: the listener stamps
    # every backend compile with the engine's round counter
    late = clog.after_first_round("fedavg", rounds0)
    check(not late, f"compiles after round 1: {late}")
    traces = {n: tel.compile_count(n) - traces0[n] for n in traces0}
    check(traces["local_train"] == 1, f"local_train traced {traces['local_train']}x")
    return {"model": sz["model"], "rounds": sz["comm_round"],
            "clients_per_round": sz["client_num_per_round"],
            "test_loss": [round(float(m["test_loss"]), 4) for m in api.metrics_history],
            "test_acc": [round(float(m["test_acc"]), 4) for m in api.metrics_history],
            "traces": traces}


# ---------------------------------------------------------------------------
# phase: LLM trainer
# ---------------------------------------------------------------------------

def _repeated_batch(vocab, seq_len, batch, steps, seed=0):
    """The same seeded batch every step: a few LoRA steps can only show a
    FALLING loss on data they see again. (``synthetic_token_batches`` builds
    a vocab x vocab transition table — 8 GB of host memory at vocab 32000.)"""
    import numpy as np

    toks = np.random.default_rng(seed).integers(0, vocab, (batch, seq_len), dtype=np.int32)
    mask = np.ones_like(toks, np.float32)
    for _ in range(steps):
        yield toks, mask


def _train_llm(sz, out_dir, devices=None, **mesh):
    from fedml_tpu.train.llm.configurations import (
        DatasetArguments, ExperimentArguments, ModelArguments)
    from fedml_tpu.train.llm.llm_trainer import LLMTrainer

    ma = ModelArguments(
        vocab_size=sz["vocab_size"], d_model=sz["d_model"], n_layers=sz["n_layers"],
        n_heads=sz["n_heads"], n_kv_heads=sz["n_kv_heads"], d_ff=sz["d_ff"],
        seq_len=sz["seq_len"], lora_rank=8, attention_impl="pallas", remat=True)
    ea = ExperimentArguments(
        max_steps=sz["steps"], per_device_batch_size=sz["per_device_batch"],
        learning_rate=2e-3, warmup_steps=1, output_dir=out_dir, **mesh)
    trainer = LLMTrainer(ma, DatasetArguments(), ea, devices=devices)
    global_batch = sz["per_device_batch"] * trainer.mesh.devices.size
    metrics = trainer.train(_repeated_batch(
        sz["vocab_size"], sz["seq_len"], global_batch, sz["steps"]))
    return trainer, metrics


def _check_llm(trainer, metrics, events, interpreted):
    check(metrics["steps"] >= 3, f"too few steps: {metrics}")
    check(math.isfinite(metrics["first_loss"]) and math.isfinite(metrics["final_loss"]),
          f"loss not finite: {metrics}")
    check(metrics["final_loss"] < metrics["first_loss"],
          f"loss did not fall: {metrics['first_loss']} -> {metrics['final_loss']}")
    step_compiles = [e for e in events if e.fun == "jit(train_step)"]
    check(len(step_compiles) == 1,
          f"train step compiled {len(step_compiles)}x: {step_compiles}")
    hlo = trainer._step_fn.compiled.as_text()
    if not interpreted:
        # the Mosaic kernel itself is in the executable: neither interpret
        # mode nor the einsum path ran
        check("tpu_custom_call" in hlo, "no Mosaic custom call in the compiled train step")
    return hlo


def phase_llm(sz, clog, interpreted):
    import jax

    with tempfile.TemporaryDirectory(prefix="chip_smoke_llm_") as out_dir:
        trainer, metrics = _train_llm(sz, out_dir)
        _check_llm(trainer, metrics, clog.of("llm"), interpreted)
        n_params = sum(int(x.size) for x in jax.tree.leaves(trainer.params))
    return {"widths": f"d{sz['d_model']}/h{sz['n_heads']}x{sz['d_model'] // sz['n_heads']}"
                      f"/ff{sz['d_ff']}/v{sz['vocab_size']}",
            "layers": sz["n_layers"], "seq_len": sz["seq_len"], "params": n_params,
            "first_loss": round(metrics["first_loss"], 4),
            "final_loss": round(metrics["final_loss"], 4), "steps": metrics["steps"]}


# ---------------------------------------------------------------------------
# phase: serving
# ---------------------------------------------------------------------------

_CORPUS = ("the quick brown fox jumps over the lazy dog while federated clients "
           "train adapters and the server folds their updates into one model").split()


def phase_serving(sz, llm_sz):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM
    from fedml_tpu.serving.endpoint import EndpointManager
    from fedml_tpu.serving.fedml_predictor import LLMPredictor
    from fedml_tpu.train.llm.tokenizer import train_bpe

    # no "</s>" special: random weights must never end a reply early
    tok = train_bpe([" ".join(_CORPUS)] * 4, vocab_size=min(400, llm_sz["vocab_size"]),
                    special_tokens=("<s>", "<pad>"))
    cfg = TransformerConfig(
        vocab_size=llm_sz["vocab_size"], d_model=llm_sz["d_model"],
        n_layers=llm_sz["n_layers"], n_heads=llm_sz["n_heads"],
        n_kv_heads=llm_sz["n_kv_heads"], d_ff=llm_sz["d_ff"],
        max_seq_len=sz["max_seq_len"], dtype=jnp.bfloat16, remat=False, lora_rank=0)
    # jitted: one program, not an eager op-by-op forward at 7B widths
    params = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),  # bf16 weights
        TransformerLM(cfg).init(key, jnp.zeros((1, 8), jnp.int32))["params"]))(
            jax.random.PRNGKey(0))
    predictor = LLMPredictor(params, cfg, tok, default_max_new_tokens=sz["new_tokens"],
                             paged=True, num_slots=sz["slots"], decode_chunk=8, page_size=16)
    engine = predictor.engine
    mgr = EndpointManager()
    try:
        predictor.warmup()  # compile before serving so no request pays it
        ep = mgr.deploy("chip_smoke_llm", lambda: predictor)

        def words(n, start=0):
            return " ".join(_CORPUS[(start + i) % len(_CORPUS)] for i in range(n))

        system = words(sz["prefix_words"])  # the shared prefix: several KV pages
        prompts = [
            system + " " + words(6, 3),        # seeds the prefix cache
            system + " " + words(5, 11),       # same prefix, other tail
            system + " " + words(30, 7),       # same prefix, long tail
            system + " " + words(6, 3),        # an exact repeat
            words(4, 5),                       # short, unrelated
            words(40, 9),                      # medium, unrelated
            system + " " + words(14, 2),
            words(9, 13),
        ]
        lens = [len(tok.encode(p)) for p in prompts]
        check(max(lens) + sz["new_tokens"] <= sz["max_seq_len"], f"prompts too long: {lens}")
        replies = [None] * len(prompts)
        errors = []

        def send(i):
            try:
                replies[i] = ep.predict(
                    {"prompt": prompts[i], "max_new_tokens": sz["new_tokens"]},
                    timeout_s=600.0)
            except Exception as e:  # noqa: BLE001 - re-raised below via `errors`
                errors.append((i, repr(e)))

        send(0)  # alone first: later requests can only share pages it registered
        threads = [threading.Thread(target=send, args=(i,)) for i in range(1, len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900.0)
        check(not any(t.is_alive() for t in threads), "a /predict request never returned")
        check(not errors, f"requests failed: {errors}")
        for i, r in enumerate(replies):
            ids = r["token_ids"]
            check(len(ids) == sz["new_tokens"],
                  f"request {i}: {len(ids)} tokens, asked for {sz['new_tokens']}")
            check(all(0 <= t < cfg.vocab_size for t in ids), f"request {i}: id outside the vocabulary")
        stats = engine.stats()
        check(stats["kv_prefix_hits"] >= 4, f"prefix pages were not shared: {stats}")
        check(stats["requests_done"] >= len(prompts) + 1, f"engine finished too few: {stats}")
        leaks = engine._alloc.check_leaks()
        check(not leaks["leaked"] and not leaks["bad_free"], f"KV page leak: {leaks}")
    finally:
        mgr.undeploy("chip_smoke_llm")
        engine.shutdown()
    return {"requests": len(prompts), "prompt_tokens": lens, "new_tokens": sz["new_tokens"],
            "prefix_hits": stats["kv_prefix_hits"], "prefix_misses": stats["kv_prefix_misses"],
            "kv_pages_total": stats["kv_pages_total"]}


# ---------------------------------------------------------------------------
# phase: four chips
# ---------------------------------------------------------------------------

def _state_bytes(tree):
    import jax

    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _check_spread(trainer, devices, label):
    """No chip holds the whole training state: by the arrays' own shards, and
    by what each device's allocator says is resident."""
    import jax

    state = (trainer.params, trainer.opt_state)
    total = _state_bytes(state)
    per_dev = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] += int(sh.data.size) * sh.data.dtype.itemsize
    worst = max(per_dev.values())
    check(worst < 0.6 * total,
          f"{label}: one chip holds {worst} of {total} state bytes (shards)")
    in_use = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "bytes_in_use" in stats:  # CPU devices report nothing
            in_use.append(int(stats["bytes_in_use"]))
            check(stats["bytes_in_use"] < 0.6 * total,
                  f"{label}: {d} has {stats['bytes_in_use']} bytes in use, "
                  f"the whole state is {total}")
    # chip 0 ran the single-chip phases, so its PEAK is theirs; on the other
    # chips the peak is this phase's, and must stay a shard's worth too
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices[1:]]
    check(all(p < 0.6 * total for p in peaks),
          f"{label}: a chip peaked at {max(peaks, default=0)} bytes, the whole state is {total}")
    return {"state_bytes": total, "max_shard_bytes_per_chip": worst,
            "bytes_in_use_per_chip": in_use, "peak_bytes_other_chips": peaks}


def _check_kernel_is_sharded(hlo, label, sz, n_batch_shards, n_head_shards):
    """Read the optimized HLO around the Mosaic custom calls. GSPMD has no
    partitioning rule for them: a bare pallas call inside a sharded step gets
    q/k/v all-gathered and runs the GLOBAL batch on every chip. Under the
    shard_map wrapper each call's leading dim is the chip's own
    (batch/shards) x (heads/shards) block, and no all-gather produces a
    full-size q/k/v."""
    import re

    T, D = sz["seq_len"], sz["d_model"] // sz["n_heads"]
    global_bh = sz["per_device_batch"] * n_batch_shards * n_head_shards * sz["n_heads"]
    local_bh = global_bh // (n_batch_shards * n_head_shards)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln and " custom-call(" in ln]
    check(calls, f"{label}: no Mosaic custom call in the optimized HLO")
    shape_re = re.compile(r"\[(\d+),%d,%d\]" % (T, D))
    for ln in calls:
        leading = {int(m) for m in shape_re.findall(ln)}
        check(leading, f"{label}: no [*,{T},{D}] operand on a Mosaic call: {ln[:200]}")
        check(leading <= {local_bh, local_bh * sz["n_kv_heads"] // sz["n_heads"]},
              f"{label}: a Mosaic call runs leading dims {sorted(leading)}, this "
              f"chip's block is {local_bh} (global {global_bh}): {ln[:200]}")
    gathers = [ln for ln in hlo.splitlines() if re.search(r"\sall-gather(-start)?\(", ln)]
    full_qkv = [ln[:160] for ln in gathers
                if re.search(r"\[(\d+,)*%d,(\d+,)*%d\]" % (T, D), ln.split(" all-gather")[0])]
    check(not full_qkv, f"{label}: all-gathers materialize q/k/v-shaped arrays: {full_qkv[:4]}")
    return {"mosaic_calls": len(calls), "all_gathers": len(gathers), "kernel_leading_dim": local_bh}


def phase_multichip(sz_all, clog, interpreted, platform):
    import jax

    import __graft_entry__
    from fedml_tpu.core import telemetry as tel

    devices = jax.devices()[:4]
    out = {}
    sz = dict(sz_all["llm"], per_device_batch=1)
    for label, mesh in (("fsdp4", dict(fsdp=4)), ("fsdp2_tp2", dict(fsdp=2, tp=2))):
        clog.phase = f"multichip.{label}"
        with tempfile.TemporaryDirectory(prefix="chip_smoke_llm_") as out_dir:
            trainer, metrics = _train_llm(sz, out_dir, devices=devices, **mesh)
            hlo = _check_llm(trainer, metrics, clog.of(clog.phase), interpreted)
            row = _check_spread(trainer, devices, label)
            if not interpreted:
                row.update(_check_kernel_is_sharded(
                    hlo, label, sz, n_batch_shards=mesh["fsdp"],
                    n_head_shards=mesh.get("tp", 1)))
            row.update(first_loss=round(metrics["first_loss"], 4),
                       final_loss=round(metrics["final_loss"], 4),
                       **clog.summary(clog.phase))
            out[label] = row
        del trainer
        gc.collect()

    clog.phase = "multichip.steps"
    __graft_entry__.multichip_steps(devices)  # sp=4 ring, pp, ep, pp x ep, fsdp x tp
    out["steps"] = clog.summary(clog.phase)

    clog.phase = "multichip.fedopt"
    traces0 = tel.compile_count("agg_round_step")
    local0 = tel.compile_count("local_train")
    rounds0 = tel.counter("engine.rounds").value
    api, _device = _run_fedavg(_fedavg_args(
        sz_all["fedavg"], federated_optimizer="FedOpt", server_optimizer="adam",
        server_lr=0.01, server_mesh="fsdp:4"))
    srv = api._fedopt_server
    check(type(srv).__name__ == "ShardedFedOptServer", f"server is {type(srv).__name__}")
    check(srv.round_traces == 1 and tel.compile_count("agg_round_step") - traces0 == 1,
          f"sharded round step traced {srv.round_traces}x")
    moments = [l for l in jax.tree.leaves(srv.state) if l.ndim == 1]
    check(moments and all(l.sharding == srv.layout.vec_sharding for l in moments),
          "optimizer moments are not sharded over the server mesh")
    # round 0 already ran on the sharded layout: nothing compiles afterwards
    late = clog.after_first_round(clog.phase, rounds0)
    check(not late and tel.compile_count("local_train") - local0 == 1,
          f"sharded-server rounds compiled after round 1: {late}")
    for m in api.metrics_history:
        check(math.isfinite(m["test_loss"]), f"bad round metrics {m}")
    on_platform(api.model_trainer.get_model_params(), platform)
    out["fedopt_server_mesh"] = dict(round_traces=srv.round_traces,
                                     **clog.summary(clog.phase))
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="same phases at tiny sizes on the CPU with interpreted "
                         "kernels; for debugging, never what the driver runs")
    ns = ap.parse_args(argv)
    dry = ns.dry_run_cpu
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # four virtual devices so the multichip phase is debugged too
            os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()
        print("chip_smoke: DRY RUN on the CPU (tiny sizes, interpreted kernels) — "
              "proves control flow only, nothing about the chip", flush=True)

    import jax

    dev = jax.devices()[0]
    platform, kind, count = dev.platform, dev.device_kind, len(jax.devices())
    print(f"chip_smoke: jax={jax.__version__} platform={platform} "
          f"device_kind={kind!r} device_count={count}", flush=True)
    if platform != ("cpu" if dry else "tpu"):
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform {platform!r} "
                 f"({kind!r} x{count}); --dry-run-cpu runs the CPU rehearsal")

    sys.path.insert(0, HERE)
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"chip_smoke: compile cache at {cache_dir}", flush=True)
    clog = CompileLog()
    sz = DRY if dry else REAL
    phases = {}

    def run(name, fn, *args):
        clog.phase = name
        t0 = time.perf_counter()
        result = fn(*args)
        row = {"wall_s": round(time.perf_counter() - t0, 2), **clog.summary(name), **result}
        phases[name] = row
        gc.collect()
        print(f"chip_smoke: phase {name} ok {json.dumps(row)}", flush=True)

    t_start = time.perf_counter()
    run("kernel", phase_kernel, sz["kernel"])
    run("fedavg", phase_fedavg, sz["fedavg"], clog, platform)
    run("llm", phase_llm, sz["llm"], clog, dry)
    run("serving", phase_serving, sz["serving"], sz["llm"])
    if count >= 4:
        run("multichip", phase_multichip, sz, clog, dry, platform)

    device = {"platform": platform, "kind": kind, "count": count}
    summary = {
        "dry_run_cpu": dry,
        "jax": jax.__version__,
        "timings": f"smoke wall times on {kind} x{count}, compile included — not performance results",
        "total_wall_s": round(time.perf_counter() - t_start, 2),
        "compile_cache": cache_dir,
        "phases": phases,
        "claim": None,
    }
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    # the driver's contract: the last stdout line is this object and nothing more
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

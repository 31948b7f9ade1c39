"""LLM fine-tuning trainer: the HF-Trainer/DeepSpeed replacement.

Reference: ``train/llm/hf_trainer.py:28`` (HFTrainer) + ``distributed.py``
(DeepSpeed ZeRO). Here: build a ('dp','fsdp','tp'[,'sp']) mesh from
ExperimentArguments, shard params/optimizer by the FSDP rules, run the
jitted train step, checkpoint with orbax. LoRA: optimizer is masked to the
adapter leaves, so base weights stay frozen and optimizer state is
rank-sized (the PEFT analogue).
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core import telemetry as tel
from ...core.telemetry import devperf
from ...models.lora import lora_mask
from ...models.transformer import TransformerConfig, TransformerLM
from ...parallel.fsdp import make_fsdp_train_step, param_shardings
from ...parallel.mesh import create_mesh
from ...parallel.ring_attention import active_mesh
from ...utils.checkpoint import CheckpointManager
from ...utils.compile_cache import enable_compile_cache
from .configurations import DatasetArguments, ExperimentArguments, ModelArguments

log = logging.getLogger(__name__)


def synthetic_token_batches(
    vocab: int, seq_len: int, batch: int, steps: int, seed: int = 0
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Deterministic markov token stream (zero-egress stand-in for the
    reference's HF dataset pipelines, train/llm/dataset pipelines)."""
    rng = np.random.default_rng(seed)
    trans = rng.dirichlet(np.ones(vocab) * 0.05, size=vocab).cumsum(axis=1)
    for _ in range(steps):
        toks = np.zeros((batch, seq_len), np.int32)
        toks[:, 0] = rng.integers(0, vocab, batch)
        r = rng.random((batch, seq_len))
        for t in range(1, seq_len):
            toks[:, t] = (trans[toks[:, t - 1]] < r[:, t : t + 1]).sum(axis=1)
        yield toks, np.ones_like(toks, np.float32)


def _overlay(base: dict, new: dict) -> dict:
    """Recursively overwrite matching leaves of `base` with `new` (shape-checked)."""
    out = dict(base)
    for k, v in new.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _overlay(dict(out[k]), v)
        else:
            if k in out and hasattr(out[k], "shape") and tuple(out[k].shape) != tuple(np.shape(v)):
                raise ValueError(f"shape mismatch for {k}: {out[k].shape} vs {np.shape(v)}")
            out[k] = jnp.asarray(v)
    return out


class LLMTrainer:
    def __init__(
        self,
        model_args: ModelArguments,
        data_args: DatasetArguments,
        exp_args: ExperimentArguments,
        devices=None,
    ):
        enable_compile_cache()
        self.model_args = model_args = model_args.resolve_pretrained()
        self.data_args = data_args
        self.exp_args = exp_args
        self.cfg = TransformerConfig(
            vocab_size=model_args.vocab_size,
            d_model=model_args.d_model,
            n_layers=model_args.n_layers,
            n_heads=model_args.n_heads,
            n_kv_heads=model_args.n_kv_heads,
            d_ff=model_args.d_ff,
            max_seq_len=model_args.seq_len,
            rope_theta=model_args.rope_theta,
            attention_impl=model_args.attention_impl,
            lora_rank=model_args.lora_rank,
            lora_alpha=model_args.lora_alpha,
            remat=model_args.remat,
            remat_policy=model_args.remat_policy,
            moe_experts=model_args.moe_experts,
            moe_capacity_factor=model_args.moe_capacity_factor,
            moe_ep_axis="ep" if exp_args.ep > 1 else None,
        )
        self.model = TransformerLM(self.cfg)
        axes, names = exp_args.mesh_shape()
        self.mesh = create_mesh(axes, names, devices)
        log.info("LLM mesh: %s", dict(zip(names, axes)))
        # register the topology (crash dumps / statusz); an explicit
        # exp_args.server_mesh (or "auto" = the training mesh's device set)
        # turns on the sharded SERVER path so federated adapter deltas
        # aggregate sharded over the same chips instead of on one
        from ...core.distributed import mesh as dmesh

        dmesh.note_mesh("llm_trainer", self.mesh)
        server_spec = getattr(exp_args, "server_mesh", None)
        if server_spec:
            if str(server_spec) == "auto" and self.mesh.devices.size > 1:
                server_spec = f"fsdp:{int(self.mesh.devices.size)}"
            dmesh.configure_server_mesh(spec=str(server_spec))

        schedule = optax.warmup_cosine_decay_schedule(
            0.0, exp_args.learning_rate, exp_args.warmup_steps, max(exp_args.max_steps, exp_args.warmup_steps + 1)
        )
        tx = optax.chain(
            optax.clip_by_global_norm(exp_args.grad_clip),
            optax.adamw(schedule, weight_decay=exp_args.weight_decay),
        )
        self._full_tx = tx
        self.params = None
        self.opt_state = None
        self._step_fn = None
        self.ckpt = CheckpointManager(exp_args.output_dir)

    # --- setup -----------------------------------------------------------
    def init_params(self, seed: Optional[int] = None):
        key = jax.random.PRNGKey(seed if seed is not None else self.exp_args.seed)
        # jitted: ONE program (XLA drops the dummy forward, keeps the RNG)
        # instead of an eager op-by-op forward that compiles ~100 tiny
        # executables; traced at the training seq_len so a pallas attention
        # lowers at the shape the step will use
        dummy = jnp.zeros((1, self.model_args.seq_len), jnp.int32)

        def init(k):
            return self.model.init(k, dummy)["params"]

        # born sharded: without out_shardings the whole tree materializes on
        # device 0 before _build reshards it (seen on 4 chips: chip 0 peaked
        # at the FULL f32 state). The pp layout restacks leaves, so it
        # shards after the split instead.
        out_shardings = None
        if self.exp_args.pp == 1:
            out_shardings = param_shardings(jax.eval_shape(init, key), self.mesh)
        params = jax.jit(init, out_shardings=out_shardings)(key)
        if self.model_args.model_name_or_path:
            # overlay pretrained base weights; freshly-initialized LoRA
            # adapter leaves (and anything the checkpoint lacks) survive
            from .checkpoint_import import import_hf_checkpoint

            pretrained = import_hf_checkpoint(self.model_args.model_name_or_path, self.cfg)
            params = _overlay(dict(params), pretrained)
            log.info("loaded pretrained weights from %s", self.model_args.model_name_or_path)
        return params

    def _build(self, params):
        if self.exp_args.pp > 1:
            return self._build_pp(params)
        tx = self._full_tx
        if self.cfg.lora_rank > 0:
            # freeze base weights: adapters get the real optimizer, the rest
            # zero updates (optax.masked would pass raw grads through)
            labels = jax.tree.map(lambda m: "train" if m else "freeze", lora_mask(params))
            tx = optax.multi_transform({"train": self._full_tx, "freeze": optax.set_to_zero()}, labels)

        if self.cfg.moe_experts > 0:
            def apply_fn(p, tokens):
                with active_mesh(self.mesh):
                    logits, state = self.model.apply({"params": p}, tokens, mutable=["losses"])
                aux = sum(jnp.sum(a) for a in jax.tree.leaves(state["losses"]))
                return logits, aux  # aux pre-weighted by MoEConfig.aux_loss_weight
        else:
            def apply_fn(p, tokens):
                with active_mesh(self.mesh):
                    return self.model.apply({"params": p}, tokens)

        seq_axis = "sp" if "sp" in self.mesh.axis_names else None
        batch_axes = tuple(a for a in ("dp", "fsdp") if a in self.mesh.axis_names)
        compile_step, init_fn = make_fsdp_train_step(
            apply_fn, tx, self.mesh, seq_axis=seq_axis, batch_axes=batch_axes
        )
        self.params, self.opt_state = init_fn(params)
        self._devperf_label = "llm_train"
        self._step_fn = devperf.instrument(
            compile_step(self.params, self.opt_state), self._devperf_label,
            n_devices=self.mesh.devices.size,
            flops_per_token_hint=self._flops_per_token_hint(self.params))

    def _flops_per_token_hint(self, params) -> float:
        """FLOPs one trained token REQUIRES (the registry's MFU numerator),
        counted as ``benchmark/flops.py`` counts a step: forward 2 and input
        gradients 2 per matmul weight (the embedding is a row lookup), a
        weight gradient (2 more) only for the leaves that train — every
        matmul weight in full fine-tuning, the adapters alone under LoRA —
        and causal attention over the length the trainer's batches have
        (``model_args.seq_len``, not the model's ``max_seq_len``): forward 2
        and backward 4 matmuls of T/2 x head_dim per head and layer."""
        leaves = jax.tree.leaves(params)
        n_matmul = sum(int(x.size) for x in leaves) - self.cfg.vocab_size * self.cfg.d_model
        n_train = n_matmul
        if self.cfg.lora_rank > 0:
            n_train = sum(int(x.size) for x, m in zip(leaves, jax.tree.leaves(lora_mask(params))) if m)
        attn = (6.0 * self.cfg.n_layers * self.cfg.n_heads * self.cfg.head_dim
                * self.model_args.seq_len)
        return 4.0 * (n_matmul - n_train) + 6.0 * n_train + attn

    def _build_pp(self, params):
        """GPipe pipeline mode (ExperimentArguments.pp > 1): params live in
        the (embed, stages [S,L//S,...], head) layout sharded over 'pp';
        the step is jax.grad through the microbatch schedule."""
        import optax as _optax

        from .pp_trainer import make_pp_loss_fn, shard_pp_params, split_lm_params

        p3 = split_lm_params(params, self.cfg, self.exp_args.pp)
        tx = self._full_tx
        if self.cfg.lora_rank > 0:
            labels3 = jax.tree.map(lambda m: "train" if m else "freeze", lora_mask(p3))
            tx = _optax.multi_transform(
                {"train": self._full_tx, "freeze": _optax.set_to_zero()}, labels3
            )
        from .pp_trainer import pp_ep_axis

        p3 = shard_pp_params(p3, self.mesh, ep_axis=pp_ep_axis(self.cfg, self.mesh))
        loss_fn = make_pp_loss_fn(
            self.cfg, self.mesh, n_microbatches=self.exp_args.pp_microbatches,
            stages_like=p3[1],
        )
        opt_state = tx.init(p3)

        # donate params + opt state like the fsdp path (make_fsdp_train_step
        # donate=True): the train loop overwrites both with the outputs, and
        # without donation XLA double-buffers the full fp32 state
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def pp_train_step(params3, opt_state, tokens, mask):
            # mask is accepted for step-signature parity; the pipelined loss
            # packs full microbatches so no padding mask is needed
            loss, grads = jax.value_and_grad(loss_fn)(params3, tokens, tokens)
            updates, opt_state = tx.update(grads, opt_state, params3)
            return _optax.apply_updates(params3, updates), opt_state, loss

        self.params = p3
        self.opt_state = opt_state
        self._devperf_label = "llm_train_pp"
        self._step_fn = devperf.instrument(
            pp_train_step, self._devperf_label, n_devices=self.mesh.devices.size,
            flops_per_token_hint=self._flops_per_token_hint(p3))
        self._pp_mode = True

    def named_params(self):
        """Params in the named layer_i layout regardless of parallel mode."""
        if getattr(self, "_pp_mode", False):
            from .pp_trainer import merge_lm_params

            e, s, h = self.params
            return merge_lm_params(e, s, h, self.cfg)
        return self.params

    def set_named_params(self, named) -> None:
        """Install named-layout params, converting to the active parallel
        layout (pp stage tuple or fsdp-sharded named tree)."""
        if getattr(self, "_pp_mode", False):
            from .pp_trainer import pp_ep_axis, shard_pp_params, split_lm_params

            self.params = shard_pp_params(
                split_lm_params(named, self.cfg, self.exp_args.pp), self.mesh,
                ep_axis=pp_ep_axis(self.cfg, self.mesh),
            )
        else:
            self.params = jax.device_put(named, param_shardings(named, self.mesh))

    # --- loop ------------------------------------------------------------
    def train(self, batches: Optional[Iterator] = None) -> Dict[str, float]:
        if self.params is None:
            self._build(self.init_params())
        exp = self.exp_args
        if batches is None:
            global_batch = exp.per_device_batch_size * max(1, self.mesh.devices.size)
            if self.data_args.dataset_path:
                batches = self.text_batches(global_batch, exp.max_steps)
            else:
                batches = synthetic_token_batches(
                    self.cfg.vocab_size, self.model_args.seq_len, global_batch, exp.max_steps, exp.seed
                )
        losses, tokens_seen = [], 0
        step = 0
        # tel.timed: tokens/sec consumes the window duration; the span itself
        # shows the whole local-training window in round traces. Inside it:
        # llm.train.step is batch to device + dispatch: the host's own work
        # while the runtime still takes steps in flight (the head of a call),
        # one device step once its bound is reached and the dispatch waits;
        # llm.train.sync is the wait for the device at the window's end,
        # llm.train.save each checkpoint call
        with tel.timed("llm.train", max_steps=exp.max_steps) as sp:
            for step, (toks, mask) in enumerate(batches):
                with tel.span("llm.train.step", step=step):
                    self.params, self.opt_state, loss = self._step_fn(
                        self.params, self.opt_state, jnp.asarray(toks), jnp.asarray(mask)
                    )
                losses.append(loss)
                tokens_seen += toks.size
                if exp.save_steps and (step + 1) % exp.save_steps == 0:
                    # async enqueue: the orbax writer runs behind the next
                    # train steps; the watermark commits on completion, so a
                    # crash mid-write resumes from the previous complete step
                    with tel.span("llm.train.save", step=step + 1):
                        self.save(step + 1, wait=False)  # fedlint: disable=interproc-host-sync amortized: fires every save_steps, and the device_get feeds the async orbax writer that runs behind the next train steps
                if step + 1 >= exp.max_steps:
                    break
            # modelwatch NaN guard + param norm: one jitted pass whose fetch
            # rides the window-end sync below (no extra device round-trip)
            guard = None
            try:
                from ...core.telemetry import modelwatch

                if modelwatch.enabled(exp):
                    guard = modelwatch.train_guard(self.params)
            except Exception:  # noqa: BLE001 - the guard must never break training
                guard = None
            with tel.span("llm.train.sync"):
                jax.block_until_ready(self.params)
        dt = sp.duration_s
        final_loss = float(jax.device_get(losses[-1])) if losses else float("nan")
        tokens_per_sec = tokens_seen / dt if dt > 0 else 0.0
        tel.histogram("llm.tokens_per_sec").observe(tokens_per_sec)
        # fold the window's measured wall into the devperf registry: live
        # per-program MFU/roofline on the same numbers the span recorded
        devperf.observe_window(
            getattr(self, "_devperf_label", "llm_train"), dt,
            steps=step + 1, tokens=tokens_seen)
        metrics = {
            "first_loss": float(jax.device_get(losses[0])) if losses else float("nan"),
            "final_loss": final_loss,
            "steps": step + 1,
            "tokens_per_sec": tokens_per_sec,
        }
        if guard is not None:
            g = np.asarray(guard, np.float64)  # fedlint: disable=host-sync rides the window-end block_until_ready above
            metrics["param_norm"] = float(np.sqrt(max(g[0], 0.0)))
            bad = int(g[1]) + int(g[2])
            if bad > 0 or not np.isfinite(final_loss):
                from ...core.telemetry import flight_recorder

                log.warning("modelwatch: non-finite training window (nan=%d inf=%d loss=%s)",
                            int(g[1]), int(g[2]), final_loss)
                flight_recorder.mark("modelwatch_train_guard", nan=int(g[1]),
                                     inf=int(g[2]), final_loss=float(final_loss))
        log.info("LLM train done: %s", metrics)
        with tel.span("llm.train.save", step=step + 1):
            self.save(step + 1)
        # drain any async mid-training save still in flight before returning:
        # callers treat a returned train() as fully durable
        self.ckpt.wait_until_finished()
        return metrics

    def text_batches(self, global_batch: int, steps: Optional[int] = None, *, seed: Optional[int] = None):
        """Real-text pipeline (reference DatasetArguments path): tokenize
        data_args.dataset_path, pack to seq_len, yield (tokens, mask)."""
        import os

        from .data import TextDataset, load_or_train_tokenizer

        da = self.data_args
        tok_path = da.tokenizer_path
        if tok_path is None and self.model_args.model_name_or_path:
            cand = os.path.join(self.model_args.model_name_or_path, "tokenizer.json")
            if os.path.exists(cand):
                tok_path = cand
        tok = load_or_train_tokenizer(da.dataset_path, tok_path, vocab_size=min(self.cfg.vocab_size, 4096))
        if tok.vocab_size > self.cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab {tok.vocab_size} exceeds model vocab {self.cfg.vocab_size}"
            )
        ds = TextDataset.from_path(
            da.dataset_path, tok, self.model_args.seq_len, text_key=da.text_key
        )
        return ds.batches(global_batch, steps, seed=self.exp_args.seed if seed is None else seed)

    # --- checkpointing ----------------------------------------------------
    def save(self, step: int, *, wait: bool = True) -> None:
        # checkpoints always use the named layout so they are loadable
        # regardless of the parallel mode that produced them
        self.ckpt.save(step, jax.device_get(self.named_params()), wait=wait)

    def restore(self, step: Optional[int] = None) -> bool:
        if self.params is None:
            self._build(self.init_params())
        # checkpoints are always named-layout (save()); restore with the
        # matching template, then convert to the active parallel layout
        restored = self.ckpt.restore(step, template=jax.device_get(self.named_params()))
        if restored is None:
            return False
        self.set_named_params(restored)
        return True

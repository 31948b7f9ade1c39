"""XLA GSPMD FSDP/TP: the DeepSpeed-ZeRO replacement.

Reference: ``train/llm/distributed.py:8-64`` (DeepSpeed ZeRO-2/3 glue,
``gather_parameter:52``). TPU-native (SURVEY §2.a): parameters, gradients
and optimizer state are *sharded by annotation* — path-based PartitionSpec
rules over a ('dp','fsdp','tp') mesh — and XLA inserts the all-gathers /
reduce-scatters ZeRO performs by hand. Optimizer state inherits the param
shardings (ZeRO-1/2); params sharded over 'fsdp' give ZeRO-3.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.pytree import PyTree

# (path regex, spec) — first match wins. Paths look like
# "layer_0/attn/q_proj/kernel".
DEFAULT_RULES: Sequence[Tuple[str, P]] = (
    (r"embed/embedding$", P("tp", "fsdp")),
    # kernel_q mirrors kernel (int8 weight-only serving, serving/quant.py);
    # its per-output-channel scale follows the kernel's OUTPUT axis sharding
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel(_q)?$", P("fsdp", "tp")),
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel_scale$", P("tp")),
    (r"(o_proj|down_proj)/kernel(_q)?$", P("tp", "fsdp")),
    (r"(o_proj|down_proj)/kernel_scale$", P("fsdp")),
    (r"lm_head/kernel(_q)?$", P("fsdp", "tp")),
    (r"lm_head/kernel_scale$", P("tp")),
    (r"lora_a$", P("fsdp", None)),
    (r"lora_b$", P(None, "tp")),
    # MoE expert weights [E, D, F] / [E, F, D]: experts over 'ep', the
    # per-expert matrices over fsdp/tp as usual (axes the mesh lacks drop)
    (r"moe_mlp/(w_gate|w_up)$", P("ep", "fsdp", "tp")),
    (r"moe_mlp/w_down$", P("ep", "tp", "fsdp")),
    (r"moe_mlp/router$", P()),
    (r"(scale|bias)$", P()),
    (r".*", P()),
)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def spec_for_path(path, rules: Sequence[Tuple[str, P]] = DEFAULT_RULES) -> P:
    s = _path_str(path)
    for pattern, spec in rules:
        if re.search(pattern, s):
            return spec
    return P()


def param_shardings(params: PyTree, mesh: Mesh, rules: Sequence[Tuple[str, P]] = DEFAULT_RULES) -> PyTree:
    """Pytree of NamedShardings matching `params`, dropping mesh axes the
    mesh doesn't have and axes that don't divide the dim."""
    axis_names = set(mesh.axis_names)

    def fix(spec: P, leaf) -> NamedSharding:
        parts = []
        for i, axis in enumerate(spec):
            ok = (
                axis is not None
                and axis in axis_names
                and i < leaf.ndim
                and leaf.shape[i] % mesh.shape[axis] == 0
            )
            parts.append(axis if ok else None)
        while parts and parts[-1] is None:
            parts.pop()
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(lambda p, leaf: fix(spec_for_path(p, rules), leaf), params)


def shard_params(params: PyTree, mesh: Mesh, rules=DEFAULT_RULES) -> PyTree:
    return jax.device_put(params, param_shardings(params, mesh, rules))


def causal_lm_loss(logits: jnp.ndarray, tokens: jnp.ndarray, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token CE: predict tokens[t+1] from logits[t]."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        m = mask[:, 1:]
        return (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
    return losses.mean()


def make_fsdp_train_step(
    model_apply: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    rules=DEFAULT_RULES,
    batch_axes: Tuple[str, ...] = ("dp",),
    seq_axis: Optional[str] = None,
    donate: bool = True,
):
    """Build the jitted sharded train step.

    batch sharded over `batch_axes` (and optionally sequence over
    `seq_axis` for the ring-attention path); params/opt-state sharded by
    `rules`. Returns (train_step, init_fn)."""

    def loss_fn(params, tokens, mask):
        out = model_apply(params, tokens)
        # MoE models return (logits, pre-weighted aux load-balancing loss)
        logits, aux = out if isinstance(out, tuple) else (out, 0.0)
        return causal_lm_loss(logits, tokens, mask) + aux

    # named for the trace: the jitted program is jit_train_step there
    def train_step(params, opt_state, tokens, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, mask)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def init_fn(params):
        sharded = shard_params(params, mesh, rules)
        opt_state = jax.jit(
            tx.init, out_shardings=_opt_state_shardings(tx, sharded, mesh, rules)
        )(sharded)
        return sharded, opt_state

    def compile_step(params, opt_state):
        p_shard = param_shardings(params, mesh, rules)
        o_shard = jax.tree.map(
            lambda x: x.sharding if hasattr(x, "sharding") else NamedSharding(mesh, P()), opt_state
        )
        batch_spec = P(batch_axes, seq_axis) if seq_axis else P(batch_axes)
        data_shard = NamedSharding(mesh, batch_spec)
        return jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, data_shard, data_shard),
            out_shardings=(p_shard, o_shard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else (),
        )

    return compile_step, init_fn


def _opt_state_shardings(tx, sharded_params, mesh, rules):
    """Optimizer-state leaves that mirror a param take its sharding (ZeRO);
    scalars replicate.

    Moment leaves are matched to their param by PATH, not by shape: optax
    state trees (e.g. adam's mu/nu) embed the full param path as a suffix of
    the state leaf's path, and two same-shaped params can carry different
    PartitionSpecs (q_proj vs o_proj), so shape-keyed lookup would silently
    mis-shard one of them."""
    shape_state = jax.eval_shape(tx.init, sharded_params)
    p_shardings = param_shardings(sharded_params, mesh, rules)
    by_path = {
        _path_str(path): (sh, leaf.shape)
        for (path, sh), leaf in zip(
            jax.tree_util.tree_flatten_with_path(p_shardings)[0],
            jax.tree.leaves(sharded_params),
        )
    }

    def pick(path, leaf):
        s = _path_str(path)
        for p_path, (sh, p_shape) in by_path.items():
            if (s == p_path or s.endswith("/" + p_path)) and leaf.shape == p_shape:
                return sh
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(pick, shape_state)

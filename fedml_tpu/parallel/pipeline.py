"""Pipeline parallelism (GPipe-style) over a ``pp`` mesh axis.

Beyond-reference capability (the reference has no pipeline parallelism —
SURVEY §2.a lists it absent; its LLM path relies on DeepSpeed ZeRO only).
TPU-native design: the transformer's blocks are split into S stages whose
parameters are STACKED on a leading stage axis and sharded ``P('pp')``, so
each device along ``pp`` holds only its stage's weights. Execution runs
under ``shard_map``: a ``lax.scan`` over M + S - 1 ticks (fill + drain
bubble) where every tick each stage applies its blocks to its current
microbatch activation and ``lax.ppermute`` shifts activations to the next
stage. Gradients flow through the scan/ppermute transpose automatically, so
``jax.grad`` of the pipelined loss needs no hand-written backward schedule.

Per-device peak memory is O(params/S + microbatch activations), the classic
pipeline trade; the bubble fraction is (S-1)/(M+S-1).

Composes with data parallelism: run inside a ('dp','pp') mesh — the batch
dim is sharded over 'dp' outside, microbatching happens per-dp-shard, and
the final loss is psum'd over both axes.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any


def stack_stage_params(per_stage_params: list) -> PyTree:
    """Stack S structurally-identical stage pytrees on a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def split_blocks_into_stages(block_params: PyTree, n_stages: int) -> PyTree:
    """Reshape per-block stacked params [L, ...] -> [S, L//S, ...].

    ``block_params`` leaves must already be stacked over the layer dim (the
    natural layout when blocks are applied with ``lax.scan``)."""

    def fix(leaf):
        L = leaf.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} blocks not divisible by {n_stages} stages")
        return leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])

    return jax.tree.map(fix, block_params)


def _stage_apply(block_fn: Callable, stage_params: PyTree, h: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply this stage's L//S blocks sequentially (scan over the block dim).

    ``block_fn`` may return either ``h`` or ``(h, aux_loss)`` (MoE blocks
    sow a load-balancing aux); returns (h_out, summed aux across blocks)."""

    def body(carry, blk):
        out = block_fn(blk, carry)
        out, aux = out if isinstance(out, tuple) else (out, jnp.zeros((), jnp.float32))
        # dtype-stable carry: a block that internally upcasts must not
        # change the scan carry (or the ppermute'd activation) dtype
        return out.astype(carry.dtype), aux.astype(jnp.float32)

    out, auxs = jax.lax.scan(body, h, stage_params)
    return out, jnp.sum(auxs)


def pipeline_loss_fn(
    block_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    embed_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    head_loss_fn: Callable[[PyTree, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    mesh: Mesh,
    n_microbatches: int,
    pp_axis: str = "pp",
    dp_axis: str | None = "dp",
    stage_specs: PyTree | None = None,
) -> Callable:
    """Build loss(params, tokens, targets) -> scalar, pipelined over pp_axis.

    params = (embed_params, stage_params, head_params) where stage_params
    leaves are [S, L//S, ...] (see split_blocks_into_stages). embed/head
    params are replicated along pp (they live on stages 0 / S-1 logically;
    replication keeps the pytree structure uniform — their FLOPs run on
    every stage but only one stage's result is used, masked).

    tokens/targets: [B, T] int arrays, B divisible by n_microbatches (and by
    the dp axis size when dp_axis is set).

    ``block_fn`` may return (h, aux_loss); per-microbatch aux (e.g. the MoE
    load-balancing loss) is accumulated over valid pipeline ticks only and
    added to the task loss as its microbatch mean — the same value
    gradient-accumulated microbatch training produces.

    ``stage_specs``: per-leaf PartitionSpec pytree for stage params (e.g.
    expert dims over an 'ep' axis — see stage_specs()); defaults to
    everything P(pp_axis). Any mesh axis beyond pp/dp gets a loss pmean so
    replicated-compute transposes scale gradients correctly.
    """
    S = mesh.shape[pp_axis]
    M = n_microbatches

    in_axes = (
        (P(), stage_specs if stage_specs is not None else P(pp_axis), P()),
        P(dp_axis) if dp_axis else P(),  # tokens: batch over dp
        P(dp_axis) if dp_axis else P(),
    )

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=in_axes,
        out_specs=P(),
        check_vma=False,
    )
    def loss_fn(params, tokens, targets):
        embed_params, stage_params, head_params = params
        for leaf in jax.tree.leaves(stage_params):
            if leaf.shape[0] != 1:
                raise ValueError(
                    f"stage count {leaf.shape[0] * S} != mesh '{pp_axis}' size {S}; "
                    "split_blocks_into_stages must use the mesh's pp size"
                )
        stage_params = jax.tree.map(lambda x: x[0], stage_params)  # [1,Ls,...] -> [Ls,...]
        stage_id = jax.lax.axis_index(pp_axis)

        mb, rem = divmod(tokens.shape[0], M)
        if rem:
            raise ValueError(f"batch {tokens.shape[0]} not divisible by {M} microbatches")
        tok_mb = tokens.reshape(M, mb, *tokens.shape[1:])
        tgt_mb = targets.reshape(M, mb, *targets.shape[1:])

        # every device embeds every microbatch input (cheap: table lookup);
        # only stage 0 consumes it — masked injection below keeps SPMD flow
        h_in = embed_fn(embed_params, tok_mb)  # [M, mb, T, D]
        state = jnp.zeros_like(h_in[0])
        # f32 carry regardless of activation dtype (bf16 activations with an
        # f32 loss would otherwise change the scan carry dtype mid-trace)
        loss_acc = jnp.zeros((), jnp.float32)
        aux_acc = jnp.zeros((), jnp.float32)

        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, loss_acc, aux_acc = carry
            # inject the next microbatch on stage 0 (t < M)
            inject = jnp.where(t < M, h_in[jnp.minimum(t, M - 1)], state)
            state = jnp.where(stage_id == 0, inject, state)
            state, stage_aux = _stage_apply(block_fn, stage_params, state)
            # stage s does real work on microbatch t-s at ticks s..s+M-1;
            # aux from fill/drain bubble ticks is garbage — mask it out
            valid = jnp.logical_and(t >= stage_id, t <= stage_id + M - 1)
            aux_acc = aux_acc + jnp.where(valid, stage_aux, 0.0)
            # collect on the last stage once the pipe is full (t >= S-1)
            out_idx = jnp.maximum(t - (S - 1), 0)
            mb_loss = head_loss_fn(head_params, state, tgt_mb[jnp.minimum(out_idx, M - 1)])
            take = jnp.logical_and(stage_id == S - 1, t >= S - 1)
            loss_acc = loss_acc + jnp.where(take, mb_loss.astype(jnp.float32), 0.0)
            state = jax.lax.ppermute(state, pp_axis, fwd_perm)
            return (state, loss_acc, aux_acc), None

        (state, loss_acc, aux_acc), _ = jax.lax.scan(
            tick, (state, loss_acc, aux_acc), jnp.arange(M + S - 1)
        )
        # task loss lives on the last stage, aux on each owning stage ->
        # share across pp; microbatch mean; then mean over dp
        loss = (jax.lax.psum(loss_acc, pp_axis) + jax.lax.psum(aux_acc, pp_axis)) / M
        if dp_axis:
            loss = jax.lax.pmean(loss, dp_axis)
        # pmean over EVERY other mesh axis ('ep', or any axis the computation
        # is merely replicated over): identity on the value, but it scales
        # the shard_map transpose's psum of replicated-param cotangents
        # correctly — without it a dense model on a ('dp','pp','ep') mesh
        # would silently train with gradients multiplied by the ep size
        for ax in mesh.axis_names:
            if ax != pp_axis and ax != dp_axis:
                loss = jax.lax.pmean(loss, ax)
        return loss

    return loss_fn


def stage_specs(stages: PyTree, pp_axis: str = "pp", ep_axis: str | None = None) -> PyTree:
    """Per-leaf PartitionSpecs for a stacked stage tree: everything over
    ``pp`` on dim 0; expert-weight leaves (path contains ``moe_mlp``, name
    w_gate/w_up/w_down — shape [S, Ls, E, ...]) additionally shard the
    expert dim over ``ep``. The router stays replicated over ep — routing
    needs all-expert logits (models/moe.py shard_map path)."""

    def spec(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if ep_axis and "moe_mlp" in keys and keys[-1] in ("w_gate", "w_up", "w_down"):
            return P(pp_axis, None, ep_axis)
        return P(pp_axis)

    return jax.tree_util.tree_map_with_path(spec, stages)


def pp_param_shardings(mesh: Mesh, params_shape: PyTree, pp_axis: str = "pp",
                       ep_axis: str | None = None) -> PyTree:
    """NamedShardings for (embed, stages, head): stages over pp (MoE expert
    dims additionally over ep when given), embed/head replicated."""
    embed_s, stage_s, head_s = params_shape

    def named(spec):
        return lambda _leaf: NamedSharding(mesh, spec)

    stage_sh = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), stage_specs(stage_s, pp_axis, ep_axis),
        is_leaf=lambda x: isinstance(x, P),
    )

    return (
        jax.tree.map(named(P()), embed_s),
        stage_sh,
        jax.tree.map(named(P()), head_s),
    )

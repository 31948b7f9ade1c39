"""Mixture-of-Experts layer with expert parallelism (``ep`` mesh axis).

Beyond-reference capability (SURVEY §2.a lists expert parallelism absent in
the reference). Switch-Transformer-style top-1 routing implemented the
MXU-friendly way: fixed expert capacity C and DENSE dispatch/combine
einsums (no scatter/gather, no dynamic shapes — everything tiles onto the
systolic array and stays jit-compatible).

Expert parallelism is expressed through GSPMD, not hand-written
collectives: expert weights carry a leading expert dim sharded
``P('ep')`` and the dispatched activations are constrained to
``P('ep', ...)``, so under jit on a mesh with an ``ep`` axis XLA inserts
the all-to-all between the token-sharded and expert-sharded layouts.

Load balancing: the Switch aux loss E * sum_e(fraction_e * prob_e), scaled
by ``aux_loss_weight`` and returned alongside the output; trainers add the
sown values to the task loss directly.

``RoutedMoE`` beside it is the DROPLESS top-k layer the serving path runs
(``TransformerConfig.moe_routed_experts``): sigmoid scores over the published
router width in float32, the ``moe_top_k`` largest, gates normalised over the
picks and scaled, a shared expert every token passes, no capacity and no
dropped token. It is told which experts it holds (``moe_held_experts`` of rank
``moe_rank``) and computes its own experts' part of the sum, under plain
``jit``: what the absent experts would add is left out, and on one device the
layer runs without its exchange. The picked (token, held expert) pairs are
sorted by expert into row tiles and go through ``ops/grouped_matmul.py``
three times (gate, up, down): an expert nobody picked is not read.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.lax import with_sharding_constraint as _wsc
from jax.sharding import PartitionSpec as P

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    capacity_factor: float = 1.25
    d_model: int = 512
    d_ff: int = 1376
    aux_loss_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    ep_axis: Optional[str] = None  # None = no sharding constraint (single host)
    # shard_map path only: experts held locally per ep rank (n_experts/ep).
    # Param declarations use this so flax's shape check matches the
    # ep-sharded leaves the pipeline's in_specs deliver. None = all experts.
    local_experts: Optional[int] = None


def _maybe_constrain(x: jnp.ndarray, spec: P, enabled: bool) -> jnp.ndarray:
    if not enabled:
        return x
    try:
        return _wsc(x, spec)
    except (ValueError, RuntimeError):
        # no mesh in scope (e.g. model.init outside the mesh context):
        # the constraint is advisory, skip it
        return x


def _axis_is_bound(ax: Optional[str]) -> bool:
    """True when ``ax`` is a bound named axis, i.e. we are INSIDE a
    shard_map/pmap body (the pipeline path) rather than under plain jit
    (the GSPMD path). Inside jit mesh axis names are not bound."""
    if ax is None:
        return False
    try:
        jax.lax.axis_index(ax)
        return True
    except (NameError, KeyError, ValueError):
        return False


def moe_dispatch(router_logits: jnp.ndarray, capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(dispatch [N,E,C], combine [N,E,C], aux_loss) from router logits [N,E].

    Top-1 routing with per-expert capacity; overflowing tokens are dropped
    (their combine weight is 0 -> they pass through the residual only),
    matching Switch Transformer semantics."""
    N, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]  # [N]

    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [N,E]
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [N,E], value at (n,e)=rank
    pos_in_expert = jnp.sum(pos, axis=-1)  # [N]
    keep = pos_in_expert < capacity

    dispatch = (
        onehot[:, :, None]
        * keep[:, None, None]
        * jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity, dtype=jnp.float32)[:, None, :]
    )  # [N,E,C]
    combine = dispatch * gate[:, None, None]

    # Switch aux loss: E * sum_e mean_n(onehot) * mean_n(probs)
    fraction = jnp.mean(onehot, axis=0)
    prob_mean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(fraction * prob_mean)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense SwiGLU MLP."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
        orig_shape = x.shape
        tokens = x.reshape(-1, D)  # [N, D]
        N = tokens.shape[0]
        capacity = max(1, int(N / E * cfg.capacity_factor))

        E_decl = cfg.local_experts or E  # router is always full-width
        router = self.param("router", nn.initializers.lecun_normal(), (D, E), jnp.float32)
        w_gate = self.param("w_gate", nn.initializers.lecun_normal(), (E_decl, D, F), jnp.float32)
        w_up = self.param("w_up", nn.initializers.lecun_normal(), (E_decl, D, F), jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(), (E_decl, F, D), jnp.float32)

        ep = cfg.ep_axis is not None
        ax = cfg.ep_axis

        logits = tokens.astype(jnp.float32) @ router  # [N, E]
        dispatch, combine, aux = moe_dispatch(logits, capacity)

        def ffn(w_g, w_u, w_d, h):
            return (nn.silu(h @ w_g.astype(cfg.dtype)) * (h @ w_u.astype(cfg.dtype))) @ w_d.astype(cfg.dtype)

        if ep and _axis_is_bound(ax):
            # shard_map path (pipeline parallelism): expert weights arrive
            # pre-sliced over the bound 'ep' axis ([E/ep, D, F] locally —
            # pp_trainer.stage_specs shards the expert dim), so each rank
            # computes its own experts from the full dispatch and the
            # partial combines are psum'd. Router stays replicated: routing
            # needs all-expert logits.
            e_local = w_gate.shape[0]
            e0 = jax.lax.axis_index(ax) * e_local
            disp_l = jax.lax.dynamic_slice_in_dim(dispatch, e0, e_local, axis=1)
            comb_l = jax.lax.dynamic_slice_in_dim(combine, e0, e_local, axis=1)
            expert_in = jnp.einsum("nec,nd->ecd", disp_l.astype(cfg.dtype), tokens.astype(cfg.dtype))
            expert_out = jax.vmap(ffn)(w_gate, w_up, w_down, expert_in)  # [E/ep,C,D]
            out = jnp.einsum("nec,ecd->nd", comb_l.astype(cfg.dtype), expert_out)
            out = jax.lax.psum(out, ax)
        else:
            # GSPMD path (jit): [N,E,C] x [N,D] -> [E,C,D]; the E-dim
            # constraint turns into the token->expert all-to-all over ICI
            expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(cfg.dtype), tokens.astype(cfg.dtype))
            expert_in = _maybe_constrain(expert_in, P(ax, None, None), ep)
            expert_out = jax.vmap(ffn)(w_gate, w_up, w_down, expert_in)  # [E,C,D]
            expert_out = _maybe_constrain(expert_out, P(ax, None, None), ep)
            out = jnp.einsum("nec,ecd->nd", combine.astype(cfg.dtype), expert_out)
        # pre-weighted: trainers add the sown aux losses to the task loss as-is
        return out.reshape(orig_shape), (cfg.aux_loss_weight * aux).astype(jnp.float32)
# sharding rules for these params live in parallel/fsdp.py DEFAULT_RULES
# (moe_mlp/w_* entries) — single source of truth


# ---------------------------------------------------------------------------
# the dropless top-k layer with a share of the experts (the serving path's)
# ---------------------------------------------------------------------------

#: the collection a routed layer sows its facts of the routing into, for
#: programs that ask for it (``mutable=[..., ROUTING_STATS]``): ``load``, the
#: live (token, held expert) pairs of this call by held expert, ``[held]``, and
#: ``row_tiles``, the row tiles those pairs were laid out in, ``[1]``
ROUTING_STATS = "moe_stats"
#: what ``routing_stats`` packs in front of the loads, in this order
ROUTING_HEAD = ("tokens_routed", "local_picks", "experts_hit", "row_tiles")
HIGHEST = jax.lax.Precision.HIGHEST


def live_tokens(x: jnp.ndarray, seq_lens: Optional[jnp.ndarray],
                cache_idx: Optional[jnp.ndarray]) -> jnp.ndarray:
    """``[B, T]`` bool: the tokens of a pass that are somebody's. A paged
    decode step marks a freed slot ``cache_idx < 0``; a padded prefill says how
    many of its T tokens are real (``seq_lens``). The others are routed to no
    expert: they read no weights and count in no statistic."""
    B, T, _ = x.shape
    if cache_idx is not None:
        return jnp.broadcast_to((cache_idx >= 0)[:, None], (B, T))
    if seq_lens is not None:
        return jnp.arange(T)[None, :] < seq_lens[:, None]
    return jnp.ones((B, T), jnp.bool_)


def _sown(sown, name: str) -> list:
    """The leaves a pass's routed layers sowed under ``name``, one a layer."""
    return [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(sown)
            if any(getattr(p, "key", None) == name for p in path)]


def routing_stats(sown, n_live) -> jnp.ndarray:
    """What a program hands the host of one pass's routing, packed in ONE small
    int32 array: ``[*ROUTING_HEAD, load_0 .. load_{held-1}]`` from the
    ``ROUTING_STATS`` collection of that pass (``sown``: one ``load`` and one
    ``row_tiles`` a routed layer) and its live tokens. ``tokens_routed`` = live
    tokens x routed layers; ``local_picks`` the (token, held expert) pairs
    computed; ``experts_hit`` the (layer, held expert) with at least one;
    ``row_tiles`` the tiles the grouped matmuls multiplied: ``1 - experts_hit /
    row_tiles`` of them shared their expert with the tile before (0 in a decode
    step; where the matrix is one block, tiles that found it in VMEM)."""
    loads = jnp.stack(_sown(sown, "load"))  # [routed layers, held]
    head = jnp.stack([jnp.asarray(n_live, jnp.int32) * loads.shape[0], jnp.sum(loads),
                      jnp.sum((loads > 0).astype(jnp.int32)), jnp.sum(jnp.stack(_sown(sown, "row_tiles")))])
    return jnp.concatenate([head, jnp.sum(loads, axis=0)]).astype(jnp.int32)


def route(router_logits: jnp.ndarray, top_k: int, scaling: float, norm_topk: bool,
          select_bias: Optional[jnp.ndarray] = None):
    """``(experts [N, k] int32, gates [N, k] f32)`` from logits ``[N, E]``:
    sigmoid scores, the k largest, gates ``scaling * s_e / (sum of the picked
    s + 1e-20)`` (``norm_topk``) or ``scaling * s_e``. With ``select_bias``
    ``[E]`` the picks are the k largest of ``s + bias``; the gates are still
    made of the picked ``s``: the bias moves the choice and nothing else."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    if select_bias is None:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), top * scaling


def row_tile(n_tokens: int, top_k: int, n_routed: int) -> int:
    """Rows of one tile of the sorted pairs, from shapes: twice what a held
    expert expects (``n_tokens * top_k / n_routed``), a power of two within
    16..128. 16 for a decode step (one or two tokens an expert: the call is
    bound by the weights it reads), 128 for a long prefill (MXU rows)."""
    want = 2.0 * n_tokens * top_k / max(n_routed, 1)
    tm = 16
    while tm < 128 and tm < want:
        tm *= 2
    return tm


def sort_pairs(experts: jnp.ndarray, live: jnp.ndarray, first: int, held: int, tm: int):
    """Lay the live (token, held expert) pairs out by expert in tiles of ``tm``
    rows. ``experts [N, k]`` are ids over the router's width; this device
    holds ``first .. first + held - 1``. Returns

      row_token  [M]    the token whose activations go in each row (0 in padding)
      pair_row   [N, k] the row of each pair (anything where ``mine`` is False)
      mine       [N, k] the pair is live and its expert is held here
      tile_group [M/tm] the held expert (0-based) of each live tile
      n_live     [1]    tiles that hold rows
      load       [held] pairs by held expert

    ``M = N * min(k, held)`` rounded up to tiles plus one tile's padding a held
    expert: every pair has a row whatever the imbalance (no capacity)."""
    N, k = experts.shape
    local = experts - first
    mine = jnp.logical_and(jnp.logical_and(local >= 0, local < held), live[:, None])
    flat = jnp.where(mine, local, held).reshape(-1)                      # ``held`` = not here
    load = jnp.sum(jax.nn.one_hot(flat, held + 1, dtype=jnp.int32), axis=0)[:held]
    tiles_of = -(-load // tm)
    tile_end = jnp.cumsum(tiles_of)
    row0 = (tile_end - tiles_of) * tm                                     # first row of each group
    pair0 = jnp.cumsum(load) - load                                       # first sorted pair of each group
    n_rows = -(-(N * min(k, held)) // tm) * tm + held * tm
    order = jnp.argsort(flat, stable=True)                               # pairs by expert, absent last
    e_sorted = flat[order]
    here = e_sorted < held
    g = jnp.minimum(e_sorted, held - 1)
    dest = jnp.where(here, row0[g] + jnp.arange(N * k) - pair0[g], n_rows)  # n_rows: dropped
    row_token = jnp.zeros((n_rows,), jnp.int32).at[dest].set((order // k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((N * k,), jnp.int32).at[order].set(jnp.minimum(dest, n_rows - 1).astype(jnp.int32))
    tile_group = jnp.minimum(jnp.searchsorted(tile_end, jnp.arange(n_rows // tm), side="right"), held - 1)
    return (row_token, pair_row.reshape(N, k), mine, tile_group.astype(jnp.int32),
            tile_end[-1:].astype(jnp.int32), load)


@functools.lru_cache(maxsize=None)
def _grouped_matmul_impl(platform: str, d_model: int, d_ff: int, tm: int, dtype: str):
    """Which formulation the experts' matmuls run, logged once per distinct
    case: ``ops.grouped_matmul.grouped_matmul`` (compiled on the TPU wherever
    its blocks tile, interpreted on the CPU at any shape) and the gathered
    einsum for a TPU shape the kernel cannot tile. Decided here, from shapes,
    before anything runs."""
    from ..ops import grouped_matmul as gm

    shape = f"d_model={d_model} d_ff={d_ff} row_tile={tm} dtype={dtype}"
    if platform == "tpu" and not gm.tiles(d_model, d_ff, tm, dtype):
        log.warning("routed experts -> gathered-einsum formulation: the kernel cannot tile %s", shape)
        return gm.grouped_matmul_reference
    log.info("routed experts -> pallas grouped matmul (platform=%s, %s)", platform, shape)
    return gm.grouped_matmul


class RoutedMoE(nn.Module):
    """The routed feed-forward of a ``TransformerConfig`` (see the module's
    header): ``y = shared(x) + sum over the picks held here of g_e SwiGLU_e(x)``.
    ``live [B, T]`` says which tokens are somebody's (``live_tokens``)."""

    cfg: Any  # TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, live: jnp.ndarray) -> jnp.ndarray:
        from .transformer import MLP

        cfg = self.cfg
        D, F, E, k = cfg.d_model, cfg.moe_d_ff, cfg.moe_routed_experts, cfg.moe_top_k
        held = cfg.moe_held_experts or E
        first = cfg.moe_rank * held
        if first + held > E or k > E:
            raise ValueError(f"rank {cfg.moe_rank} x {held} held experts and top-{k} do not fit {E} routed experts")
        tokens = x.reshape(-1, D)
        N = tokens.shape[0]
        router = self.param("router", nn.initializers.lecun_normal(), (D, E), jnp.float32)
        w_gate = self.param("w_gate", nn.initializers.lecun_normal(), (held, D, F), jnp.float32)
        w_up = self.param("w_up", nn.initializers.lecun_normal(), (held, D, F), jnp.float32)
        w_down = self.param("w_down", nn.initializers.lecun_normal(), (held, F, D), jnp.float32)

        # the router keeps its published width and its picks, in float32
        logits = jnp.dot(tokens.astype(jnp.float32), router.astype(jnp.float32), precision=HIGHEST)
        more = {}
        if cfg.moe_select_bias:
            more["select_bias"] = self.param("router_bias", nn.initializers.zeros, (E,), jnp.float32)
        experts, gates = route(logits, k, cfg.moe_routed_scaling, cfg.moe_norm_topk, **more)
        tm = row_tile(N, k, E)
        row_token, pair_row, mine, tile_group, n_live, load = sort_pairs(
            experts, live.reshape(-1), first, held, tm)
        self.sow(ROUTING_STATS, "load", load)
        self.sow(ROUTING_STATS, "row_tiles", n_live)

        matmul = _grouped_matmul_impl(jax.default_backend(), D, F, tm, jnp.dtype(cfg.dtype).name)
        with jax.named_scope("moe_experts"):
            rows = tokens[row_token].astype(cfg.dtype)                                    # [M, D]
            gate = matmul(rows, w_gate.astype(cfg.dtype), tile_group, n_live, tm=tm)
            up = matmul(rows, w_up.astype(cfg.dtype), tile_group, n_live, tm=tm)
            out_rows = matmul(nn.silu(gate) * up, w_down.astype(cfg.dtype), tile_group, n_live, tm=tm)
            # rows of tiles past the live ones are unspecified: read only what was laid out
            picked = jnp.where(mine[..., None], out_rows[pair_row].astype(jnp.float32), 0.0)  # [N, k, D]
            routed = jnp.sum(picked * gates[..., None], axis=1).astype(cfg.dtype)
        y = routed.reshape(x.shape)
        if cfg.moe_shared_experts > 0:
            y = y + MLP(cfg, d_ff=F * cfg.moe_shared_experts, name="shared")(x)
        return y

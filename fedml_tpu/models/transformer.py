"""Llama-family causal transformer for the FedLLM path.

Reference: ``train/llm/models/modeling_gpt_neox.py`` + ``models/attention.py``
(HF GPT-NeoX with a flash-attn flag; Llama-2 via model_name_or_path). This is
the TPU-native re-design: RMSNorm + rotary + GQA + SwiGLU in flax, bfloat16
activations, per-layer ``jax.checkpoint`` (remat), and a pluggable attention
impl — XLA einsum, Pallas flash kernel (ops/flash_attention.py), or ring
attention over an 'sp' mesh axis (parallel/ring_attention.py) for
long-context sequence parallelism the reference lacks (SURVEY §5).

Sharding is applied from outside by path rules (parallel/fsdp.py) so the
module stays pure; LoRA adapters are parameters named ``lora_a``/``lora_b``
inside each projection, split from the base tree by
``models.lora.split_lora`` — in federated mode only adapters cross the WAN.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1376
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # full: save nothing per block (lowest memory, ~1/3 extra fwd FLOPs);
    # dots: save matmul outputs, recompute elementwise only (the classic
    # MFU/memory middle ground — jax.checkpoint_policies)
    remat_policy: str = "full"   # full | dots
    attention_impl: str = "auto"  # auto (see _auto_attention_impl) | xla | pallas | ring
    lora_rank: int = 0           # 0 = no adapters
    lora_alpha: float = 16.0
    lora_targets: Tuple[str, ...] = ("q_proj", "k_proj", "v_proj", "o_proj")
    moe_experts: int = 0         # 0 = dense MLP; >0 = Switch-style MoE MLP
    moe_capacity_factor: float = 1.25
    moe_ep_axis: Any = None      # mesh axis name for expert parallelism
    moe_local_experts: Any = None  # shard_map pp path: experts per ep rank
    decode: bool = False         # KV-cache autoregressive decode mode (serving)
    # Paged KV cache (serving/paged_kv.py): when kv_page_size > 0 the decode
    # cache collection is a physical page pool [kv_num_pages, kv_page_size,
    # kv, hd] per layer instead of per-row [B, max_seq_len, ...] slabs, and
    # decode steps address it through per-row runtime block tables — the
    # allocator refcounts pages so requests sharing a system-prompt prefix
    # map the same physical pages. 0 = contiguous slots (PR-6 engine).
    kv_page_size: int = 0
    kv_num_pages: int = 0
    # int8 = weight-only quantized dense kernels (serving/quant.py transform
    # produces the kernel_q/kernel_scale layout). Decode is HBM-bandwidth
    # bound, so halving weight bytes is a direct tokens/sec lever; activations
    # and KV cache stay in ``dtype``.
    weight_quant: str = "none"   # none | int8
    # The layers' kinds, as data: () = every layer is an attention block (what
    # every config above builds); else one entry a layer, "attention" or
    # "mamba" (a selective state-space mixer, models/mamba.py, in the
    # attention's place; the feed-forward is the same). ``hybrid_pattern``
    # builds the periodic patterns published configs describe.
    layer_pattern: Tuple[str, ...] = ()
    use_rope: bool = True         # False: attention without positions
    tie_embeddings: bool = False  # True: logits = x E^T, no lm_head
    norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0        # 0 = ceil(d_model / 16)
    # Latent attention (a layer of kind "mla", models/mla.py): queries through
    # a rank-``q_lora_rank`` bottleneck, keys and values expanded per head from
    # ONE ``kv_lora_rank``-wide latent a token plus one rotary key of
    # ``qk_rope_head_dim`` shared by all heads; what is cached is the two.
    # Query/key width (nope + rope) and value width are their own values:
    # ``head_dim`` below is the other kinds'.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The feed-forward's kind a layer: the first ``first_k_dense_replace``
    # layers keep the dense SwiGLU of ``d_ff``; with ``moe_routed_experts > 0``
    # the layers after them are dropless top-k routed layers (models/moe.py,
    # ``RoutedMoE``): a router ``moe_routed_experts`` wide (the PUBLISHED
    # count), ``moe_top_k`` picks a token, experts of width ``moe_d_ff``,
    # ``moe_shared_experts`` of that width every token passes. This device
    # holds ``moe_held_experts`` of them (0 = all), those of rank ``moe_rank``:
    # experts ``rank * held .. rank * held + held - 1``.
    first_k_dense_replace: int = 0
    moe_routed_experts: int = 0
    moe_held_experts: int = 0
    moe_rank: int = 0
    moe_top_k: int = 1
    moe_d_ff: int = 0
    moe_shared_experts: int = 0
    moe_routed_scaling: float = 1.0
    moe_norm_topk: bool = True
    # an RMSNorm on each sublayer's OUTPUT before the residual add, beside the
    # one on its input: x + N(mixer(N(x))), then h + N(ffn(N(h)))
    sandwich_norm: bool = False
    # Width of one attention head, a value of its own: 0 = d_model // n_heads
    # (what every config above builds), resolved when the config is made, so
    # ``cfg.head_dim`` always reads the width in use.
    head_dim: int = 0
    # What an attention layer sees, a layer: () = every one attends to its whole
    # prefix ("full"); else one entry a layer (read where the layer is of kind
    # "attention"), "full" or "window": a window layer's query at position t
    # sees keys ``t - sliding_window < j <= t`` and always rotates q and k; a
    # full layer rotates them iff ``use_rope``. In the paged engine the window
    # layers' K/V live in a page group of their own (``kv_window_pages`` pages;
    # serving/paged_kv.py), whose pages go back behind a request's horizon.
    attn_kinds: Tuple[str, ...] = ()
    sliding_window: int = 0
    kv_window_pages: int = 0
    qk_norm: bool = False         # an RMSNorm over head_dim on q and on k, before the rotation
    attn_gate: bool = False       # out = W_o(attn * sigmoid(W_g x)), W_g n_heads * head_dim wide
    embed_scale: float = 1.0      # x0 = E[token] * embed_scale
    # a per-expert bias added to the router's scores for the CHOICE of the
    # picks only (the gates come from the scores without it)
    moe_select_bias: bool = False

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    def layer_kind(self, i: int) -> str:
        return self.layer_pattern[i] if self.layer_pattern else "attention"

    def attn_kind(self, i: int) -> str:
        return self.attn_kinds[i] if self.attn_kinds else "full"

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """Indices of the attention layers that see a window."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_kind(i) == "attention" and self.attn_kind(i) == "window")

    def ffn_kind(self, i: int) -> str:
        """"routed" or "dense" (the Switch layer of ``moe_experts`` is the
        dense kind's training-only variant, chosen inside the block)."""
        return "routed" if self.moe_routed_experts > 0 and i >= self.first_k_dense_replace else "dense"

    @property
    def routed_layers(self) -> int:
        return sum(1 for i in range(self.n_layers) if self.ffn_kind(i) == "routed")

    @property
    def latent_layers(self) -> int:
        return sum(1 for k in self.layer_pattern if k == "mla")

    @property
    def latent_width(self) -> int:
        """Values one token leaves in one latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """Columns of a row of the latent leaf: ``latent_width`` filled up with
        zeros to whole 128-lane tiles (576 -> 640). The TPU holds a 576-wide
        row in 640 lanes anyway (XLA's tiled HBM layout), and a page can only
        be DMA'd by whole tiles: the padding is in the shape, not beside it."""
        return -(-self.latent_width // 128) * 128

    @property
    def has_recurrent_state(self) -> bool:
        """Some layer carries a per-request state that is not K/V pages."""
        return "mamba" in self.layer_pattern

    @classmethod
    def from_args(cls, args: Any) -> "TransformerConfig":
        return cls(
            vocab_size=int(getattr(args, "vocab_size", 32000)),
            d_model=int(getattr(args, "d_model", 512)),
            n_layers=int(getattr(args, "n_layers", 4)),
            n_heads=int(getattr(args, "n_heads", 8)),
            n_kv_heads=int(getattr(args, "n_kv_heads", getattr(args, "n_heads", 8))),
            d_ff=int(getattr(args, "d_ff", 1376)),
            max_seq_len=int(getattr(args, "seq_len", 2048)),
            attention_impl=str(getattr(args, "attention_impl", "auto")),
            lora_rank=int(getattr(args, "lora_rank", 0) or 0),
            lora_alpha=float(getattr(args, "lora_alpha", 16.0)),
            remat=bool(getattr(args, "remat", True)),
            remat_policy=str(getattr(args, "remat_policy", "full")),
        )

    @classmethod
    def llama2_7b(cls, **over) -> "TransformerConfig":
        """Llama-2-7B geometry (the Cheetah/FedLLM benchmark model)."""
        base = dict(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32,
            d_ff=11008, max_seq_len=4096,
        )
        base.update(over)
        return cls(**base)


LAYER_KINDS = ("attention", "mamba", "mla")


def hybrid_pattern(n_layers: int, attn_period: int, attn_offset: int) -> Tuple[str, ...]:
    """Layer ``i`` is attention where ``i % attn_period == attn_offset``, a
    Mamba mixer otherwise."""
    return tuple("attention" if i % attn_period == attn_offset else "mamba"
                 for i in range(n_layers))


def rotary_embedding(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Apply RoPE to [B, T, H, D] given positions [B, T]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    # rotation math in f32, activations back to the input dtype — without
    # this, f32 cos/sin silently promote q/k (and everything downstream of
    # attention) to f32, doubling MXU time and activation bytes on TPU
    return out.reshape(x.shape).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (normed * scale).astype(x.dtype)


class LoRALinear(nn.Module):
    """Dense with optional low-rank adapter (W + (alpha/r) A B)."""

    features: int
    cfg: TransformerConfig
    use_bias: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        in_dim = x.shape[-1]
        if self.cfg.weight_quant == "int8":
            # weight-only int8 (symmetric, per-output-channel): the int8
            # operand feeds lax.dot_general DIRECTLY (mixed bf16 x s8 dot) so
            # HBM reads stay int8 and the widening happens inside the matmul
            # pipeline. The old `x @ kq.astype(x.dtype)` emitted an explicit
            # convert HLO, and inside the decode scan XLA materialized a full
            # bf16 copy of EVERY kernel per generated token — int8 decode
            # measured ~375x SLOWER than bf16 (BENCH_r05) instead of ~2x
            # faster. tests/test_serving_quant.py pins both numerics and the
            # no-per-step-retrace compile count.
            kq = self.param("kernel_q", nn.initializers.zeros,
                            (in_dim, self.features), jnp.int8)
            kscale = self.param("kernel_scale", nn.initializers.ones,
                                (self.features,))
            y = jax.lax.dot_general(
                x, kq,
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # apply the f32 scale while still in f32, cast the RESULT back —
            # rounding the scale itself to bf16 would double dequant error
            y = (y * kscale).astype(x.dtype)
        else:
            kernel = self.param("kernel", nn.initializers.lecun_normal(), (in_dim, self.features))
            y = x @ kernel.astype(x.dtype)
        r = self.cfg.lora_rank
        if r > 0 and _lora_target(self.name, self.cfg):
            a = self.param("lora_a", nn.initializers.normal(0.02), (in_dim, r))
            b = self.param("lora_b", nn.initializers.zeros, (r, self.features))
            y = y + (self.cfg.lora_alpha / r) * ((x @ a.astype(x.dtype)) @ b.astype(x.dtype))
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros, (self.features,)).astype(x.dtype)
        return y


def _lora_target(name: Optional[str], cfg: TransformerConfig) -> bool:
    return name is not None and any(t in name for t in cfg.lora_targets)


def repeat_kv(k: jnp.ndarray, v: jnp.ndarray, n_heads: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GQA: repeat kv heads up to n_heads (no-op when already equal)."""
    n_kv = k.shape[2]
    if n_kv != n_heads:
        rep = n_heads // n_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def xla_attention(q, k, v, causal: bool = True, mask: Optional[jnp.ndarray] = None,
                  window: int = 0):
    """Plain einsum attention; XLA fuses + tiles this well for short T.
    ``mask`` overrides the causal triangle (decode path: [T_q, T_k] valid
    positions); both paths share this one body so they cannot diverge. With
    ``window`` the triangle keeps only the ``window`` newest keys of a query."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is None and causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), jnp.bool_), tk - tq)
        if window:
            mask = jnp.logical_and(mask, jnp.triu(jnp.ones((tq, tk), jnp.bool_), tk - tq - window + 1))
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@functools.lru_cache(maxsize=None)
def _auto_attention_impl(platform: str, seq_len: int) -> str:
    """What ``attention_impl="auto"`` means, logged once per distinct case:
    the pallas flash kernel where it runs COMPILED (platform ``tpu``) and a
    rung of its block ladder tiles the sequence (``ops.flash_attention.tiles``:
    any length below 128 or a multiple of it; each kernel then takes the
    largest rung up to 512 that divides the length, ``block_sizes`` — on v5e
    the kernel compiled and matched XLA at every such shape tried, T 8..16384,
    head_dim 16..256, PR 21); XLA einsum attention otherwise (sequences no
    rung tiles, and the CPU, where interpret-mode flash would be pure
    overhead). An explicit ``"pallas"`` is never rerouted — it runs the
    kernel or raises."""
    from ..ops.flash_attention import tiles

    impl = "pallas" if platform == "tpu" and tiles(seq_len) else "xla"
    log.info("attention_impl=auto -> %s (platform=%s, seq_len=%d)",
             impl, platform, seq_len)
    return impl


@functools.lru_cache(maxsize=None)
def _paged_attention_impl(platform: str, head_dim: int, page_size: int,
                          n_heads: int, n_kv_heads: int, dtype: str):
    """Which formulation paged decode reads the pool with, logged once per
    distinct case: ``ops.paged_attention.paged_attention`` — compiled on the
    TPU wherever its blocks tile (``ops.paged_attention.tiles``), interpreted
    on the CPU at any shape (tests, ``chip_smoke.py --dry-run-cpu``) — and the
    gather + repeat + masked-einsum ``paged_attention_reference`` for a TPU
    shape the kernel cannot tile. Decided here, from shapes, before anything
    runs: a kernel that fails to compile raises, it is never retried plain."""
    from ..ops import paged_attention as pa

    shape = (f"head_dim={head_dim} page_size={page_size} n_heads={n_heads} "
             f"n_kv_heads={n_kv_heads} dtype={dtype}")
    if platform == "tpu" and not pa.tiles(head_dim, page_size, n_heads,
                                          n_kv_heads, dtype):
        log.warning("paged decode attention -> reference formulation (full-"
                    "context gather + repeat_kv): the kernel cannot tile %s", shape)
        return pa.paged_attention_reference
    log.info("paged decode attention -> pallas kernel (platform=%s, %s)",
             platform, shape)
    return pa.paged_attention


#: bytes of float32 scores ``[n_heads, T, S]`` past which a pass over a
#: contiguous row cache no longer builds them: the rows' kernel runs instead
PREFILL_SCORE_BYTES = 512 * 2 ** 20


@functools.lru_cache(maxsize=None)
def _row_attention_impl(platform: str, T: int, S: int, n_heads: int, head_dim: int):
    """Which formulation a multi-token pass over a contiguous row cache (a
    prefill, a suffix pass) attends with, logged once per distinct case: the
    masked einsum over the whole row while its ``[n_heads, T, S]`` float32
    scores stay under ``PREFILL_SCORE_BYTES`` (every pass of a 2,048-token
    row), and past that ``ops.flash_attention.flash_attention_rows`` where it
    runs compiled and tiles the shape: the scores never exist and only the
    blocks a query can see are visited. Decided here, from shapes."""
    from ..ops.flash_attention import rows_tile

    big = 4 * n_heads * T * S > PREFILL_SCORE_BYTES
    impl = "pallas" if platform == "tpu" and big and rows_tile(S) else "xla"
    if big:
        log.info("row-cache attention -> %s (platform=%s, T=%d, S=%d, n_heads=%d, head_dim=%d)",
                 impl, platform, T, S, n_heads, head_dim)
    return impl


def _sharded_flash_attention(q, k, v, window: int = 0):
    """The pallas kernel under whatever mesh the train step is sharded over.

    GSPMD has no partitioning rule for a Mosaic custom call: left bare inside
    a sharded step it all-gathers q/k/v and runs the WHOLE batch on every
    chip. Attention is independent per (batch row, head), so under an active
    mesh the call is wrapped in ``shard_map`` over the batch axes
    (``dp``/``fsdp``) and, when both head counts divide it, the ``tp`` axis —
    each chip runs the kernel on exactly the block it already holds."""
    from ..ops.flash_attention import flash_attention
    from ..parallel.ring_attention import get_active_mesh

    kernel = functools.partial(flash_attention, causal=True, window=window)
    mesh = get_active_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)
    batch_axes = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    tp = mesh.shape.get("tp", 1)
    head_axis = "tp" if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    spec = P(batch_axes or None, None, head_axis, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


class Attention(nn.Module):
    cfg: TransformerConfig
    kind: str = "full"  # TransformerConfig.attn_kind: "full" or "window"

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache_idx: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``block_tables``: the tables of THIS layer's page group (the window
        group's for a window layer; ``Block`` picks)."""
        cfg = self.cfg
        B, T, _ = x.shape
        hd = cfg.head_dim
        window = cfg.sliding_window if self.kind == "window" else 0
        q = LoRALinear(cfg.n_heads * hd, cfg, name="q_proj")(x).reshape(B, T, cfg.n_heads, hd)
        k = LoRALinear(cfg.n_kv_heads * hd, cfg, name="k_proj")(x).reshape(B, T, cfg.n_kv_heads, hd)
        v = LoRALinear(cfg.n_kv_heads * hd, cfg, name="v_proj")(x).reshape(B, T, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = RMSNorm(cfg.norm_eps, name="q_norm")(q)
            k = RMSNorm(cfg.norm_eps, name="k_norm")(k)
        if window or cfg.use_rope:
            q = rotary_embedding(q, positions, cfg.rope_theta)
            k = rotary_embedding(k, positions, cfg.rope_theta)
        if cfg.decode:
            if cfg.kv_page_size > 0:
                out = self._paged_decode_attention(q, k, v, B, T, cache_idx, block_tables, window)
            else:
                out = self._decode_attention(q, k, v, B, T, window)
        else:
            impl = cfg.attention_impl
            if impl == "auto":
                impl = _auto_attention_impl(jax.default_backend(), T)
            if impl == "pallas":
                # GQA-native: the kernel maps query heads to kv heads itself —
                # repeat_kv here would materialize G copies of K/V in HBM
                out = _sharded_flash_attention(q, k, v, window)
            elif impl == "ring":
                from ..parallel.ring_attention import ring_attention_inner

                if window:
                    raise ValueError("attention_impl='ring' has no window: a window layer's keys are local "
                                     "to a few sequence shards; use 'pallas' or 'xla'")
                k, v = repeat_kv(k, v, cfg.n_heads)
                out = ring_attention_inner(q, k, v)
            else:
                k, v = repeat_kv(k, v, cfg.n_heads)
                out = xla_attention(q, k, v, causal=True, window=window)
        out = out.reshape(B, T, cfg.n_heads * hd)
        if cfg.attn_gate:
            gate = LoRALinear(cfg.n_heads * hd, cfg, name="g_proj")(x)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)
        return LoRALinear(cfg.d_model, cfg, name="o_proj")(out)

    def _decode_attention(self, q, k, v, B: int, T: int, window: int) -> jnp.ndarray:
        """KV-cache attention over contiguous rows (flax 'cache' collection):
        the engine's prefill rows and ``generation.generate``. Supports
        prefill (T = prompt length) and single-token steps (T = 1): new k/v
        are written at the running cache index, shared by every row, and
        queries attend to everything written so far, a window layer's to the
        ``window`` newest of it. Static shapes: the cache is
        [B, max_seq_len, kv, hd] with an index mask; a pass whose scores over
        the whole row would be too many runs the rows' kernel instead
        (``_row_attention_impl``)."""
        cfg = self.cfg
        hd = cfg.head_dim
        S = cfg.max_seq_len
        ck = self.variable("cache", "k", jnp.zeros, (B, S, cfg.n_kv_heads, hd), q.dtype)
        cv = self.variable("cache", "v", jnp.zeros, (B, S, cfg.n_kv_heads, hd), q.dtype)
        cidx = self.variable("cache", "idx", lambda: jnp.zeros((), jnp.int32))
        idx = cidx.value
        if self.is_mutable_collection("cache"):
            ck.value = jax.lax.dynamic_update_slice(ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0))
            cidx.value = idx + T
        if _row_attention_impl(jax.default_backend(), T, S, cfg.n_heads, hd) == "pallas":
            from ..ops.flash_attention import flash_attention_rows

            return flash_attention_rows(q, ck.value, cv.value, idx, window=window)
        k_all, v_all = repeat_kv(ck.value, cv.value, cfg.n_heads)  # [B, S, h, hd]
        q_pos = idx + jnp.arange(T)  # absolute position of each query
        k_pos = jnp.arange(S)[None, :]
        valid = k_pos <= q_pos[:, None]  # [T, S] causal+written
        if window:
            valid = jnp.logical_and(valid, k_pos > q_pos[:, None] - window)
        return xla_attention(q, k_all, v_all, mask=valid)

    def _paged_decode_attention(self, q, k, v, B: int, T: int,
                                cache_idx: Optional[jnp.ndarray],
                                block_tables: Optional[jnp.ndarray], window: int) -> jnp.ndarray:
        """Block-table KV attention over a physical page pool (the paged
        serving engine's mode, serving/paged_kv.py). The cache collection is
        [kv_num_pages, kv_page_size, kv, hd] per layer — one pool shared by
        every in-flight request; row ``b``'s logical position ``l`` lives at
        page ``block_tables[b, l // page]``, slot ``l % page``. Both the
        block tables [B, max_blocks] and the per-row write index
        ``cache_idx`` [B] are RUNTIME data, so one executable per (cfg, B)
        serves every admission mix.

        Write: the new k/v token scatters to (bt[b, idx//page], idx%page).
        The allocator guarantees the page being written has refcount 1 (a
        shared prefix page is never the write target — requests sharing a
        prefix get fresh private pages from the first non-shared chunk on),
        so copy-on-write never needs an actual copy.

        Read: ``ops/paged_attention.py`` walks each row's live pages in the
        pool itself (``_paged_attention_impl`` says which form of it runs); a
        row attends to positions ``<= cache_idx[b]`` — its own written prefix
        plus the token just scattered. Unallocated block-table entries point
        at the reserved trash page 0 and lie beyond every row's index, so
        their garbage is never read. ``paged_step`` hands a freed slot
        ``cache_idx = -1``: length 0, no page read, output ignored.

        A WINDOW layer's pool is the window group's (``kv_window_pages``
        pages) and its tables that group's: same logical indexing, but the
        entries behind the row's horizon ``cache_idx - window`` point at the
        trash page again (their pages went back), and the walk starts at the
        horizon, not at 0: those entries are neither copied nor scored."""
        cfg = self.cfg
        hd = cfg.head_dim
        ps = cfg.kv_page_size
        n_pages = cfg.kv_window_pages if window else cfg.kv_num_pages
        if T != 1:
            raise ValueError(f"paged decode requires T=1 steps, got T={T}")
        if cache_idx is None or block_tables is None:
            raise ValueError("paged decode requires cache_idx and block_tables")
        if n_pages < 2:
            raise ValueError("kv_num_pages (and with window layers kv_window_pages) must be >= 2 "
                             "(page 0 is the trash page)")
        ck = self.variable("cache", "k", jnp.zeros, (n_pages, ps, cfg.n_kv_heads, hd), q.dtype)
        cv = self.variable("cache", "v", jnp.zeros, (n_pages, ps, cfg.n_kv_heads, hd), q.dtype)
        # the contiguous rows' shared scalar write index, kept so the two
        # cache pytrees stay congruent for gather/scatter; unused here
        self.variable("cache", "idx", lambda: jnp.zeros((), jnp.int32))
        # a freed slot's token goes to the trash page whatever its table holds
        w_idx = jnp.maximum(cache_idx, 0)
        page = jnp.take_along_axis(
            block_tables, (w_idx // ps)[:, None], axis=1)[:, 0]  # [B]
        page = jnp.where(cache_idx < 0, 0, page)
        off = w_idx % ps
        if self.is_mutable_collection("cache"):
            ck.value = ck.value.at[page, off].set(k[:, 0].astype(ck.value.dtype))
            cv.value = cv.value.at[page, off].set(v[:, 0].astype(cv.value.dtype))
        attend = _paged_attention_impl(jax.default_backend(), hd, ps, cfg.n_heads,
                                       cfg.n_kv_heads, jnp.dtype(q.dtype).name)
        if window:
            starts = jnp.maximum(cache_idx + 1 - window, 0)
            return attend(q[:, 0], ck.value, cv.value, block_tables, cache_idx + 1, starts)
        return attend(q[:, 0], ck.value, cv.value, block_tables, cache_idx + 1)


class MLP(nn.Module):
    cfg: TransformerConfig
    d_ff: int = 0  # 0 = cfg.d_ff

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        gate = LoRALinear(d_ff, cfg, name="gate_proj")(x)
        up = LoRALinear(d_ff, cfg, name="up_proj")(x)
        return LoRALinear(cfg.d_model, cfg, name="down_proj")(nn.silu(gate) * up)


class Block(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"  # the mixer before the feed-forward: LAYER_KINDS
    ffn: str = "dense"       # the feed-forward: TransformerConfig.ffn_kind
    attn: str = "full"       # what an attention mixer sees: TransformerConfig.attn_kind

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache_idx: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None,
                 seq_lens: Optional[jnp.ndarray] = None,
                 snap_lens: Optional[jnp.ndarray] = None,
                 window_tables: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        if self.kind == "mamba":
            from .mamba import MambaMixer

            mixed = MambaMixer(cfg, name="mamba")(
                RMSNorm(cfg.norm_eps, name="mamba_norm")(x), seq_lens, snap_lens, cache_idx)
        elif self.kind == "mla":
            from .mla import LatentAttention

            mixed = LatentAttention(cfg, name="attn")(
                RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions, cache_idx, block_tables)
        else:
            mixed = Attention(cfg, self.attn, name="attn")(
                RMSNorm(cfg.norm_eps, name="attn_norm")(x), positions, cache_idx,
                window_tables if self.attn == "window" else block_tables)
        if cfg.sandwich_norm:
            mixed = RMSNorm(cfg.norm_eps, name="mixer_out_norm")(mixed)
        x = x + mixed
        h = RMSNorm(cfg.norm_eps, name="mlp_norm")(x)
        if self.ffn == "routed":
            from .moe import RoutedMoE, live_tokens

            y = RoutedMoE(cfg, name="moe")(h, live_tokens(h, seq_lens, cache_idx))
            if cfg.sandwich_norm:
                y = RMSNorm(cfg.norm_eps, name="mlp_out_norm")(y)
            x = x + y
        elif cfg.moe_experts > 0:
            from .moe import MoEConfig, MoEMLP

            moe_cfg = MoEConfig(
                n_experts=cfg.moe_experts,
                capacity_factor=cfg.moe_capacity_factor,
                d_model=cfg.d_model,
                d_ff=cfg.d_ff,
                dtype=cfg.dtype,
                ep_axis=cfg.moe_ep_axis,
                local_experts=cfg.moe_local_experts,
            )
            y, aux = MoEMLP(moe_cfg, name="moe_mlp")(h)
            # visible via apply(..., mutable=["losses"]); no-op otherwise
            self.sow("losses", "moe_aux", aux)
            x = x + y
        else:
            y = MLP(cfg, name="mlp")(h)
            if cfg.sandwich_norm:
                y = RMSNorm(cfg.norm_eps, name="mlp_out_norm")(y)
            x = x + y
        return x


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, train: bool = False,
                 positions: Optional[jnp.ndarray] = None,
                 cache_idx: Optional[jnp.ndarray] = None,
                 block_tables: Optional[jnp.ndarray] = None,
                 seq_lens: Optional[jnp.ndarray] = None,
                 snap_lens: Optional[jnp.ndarray] = None,
                 window_tables: Optional[jnp.ndarray] = None,
                 logit_rows: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``seq_lens`` [B]: how many of this pass's T tokens are real (the
        rest is right padding; default all). ``snap_lens`` [B]: after how many
        of them a recurrent layer also keeps its state for the prefix cache.
        Attention layers ignore both (padded K/V are overwritten before they
        can be read: ``generation._rewind_cache``). ``window_tables``: the
        window page group's block tables (paged decode of a model with window
        layers). ``logit_rows`` [B]: the one position a row whose logits are
        wanted: the result is ``[B, 1, vocab]`` and the head runs over B
        tokens, not B x T (a prefill samples one token)."""
        cfg = self.cfg
        if cfg.attn_kinds and (len(cfg.attn_kinds) != cfg.n_layers or set(cfg.attn_kinds) - {"full", "window"}
                               or ("window" in cfg.attn_kinds and cfg.sliding_window < 1)):
            raise ValueError(f"attn_kinds must name 'full' or 'window' for each of {cfg.n_layers} layers, with "
                             f"sliding_window >= 1 beside a window layer, got {cfg.attn_kinds!r} and "
                             f"sliding_window={cfg.sliding_window}")
        if cfg.layer_pattern and (len(cfg.layer_pattern) != cfg.n_layers
                                  or set(cfg.layer_pattern) - set(LAYER_KINDS)):
            raise ValueError(f"layer_pattern must name one of {LAYER_KINDS} for each of "
                             f"{cfg.n_layers} layers, got {cfg.layer_pattern!r}")
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="embed")
        x = embed(tokens)
        if cfg.embed_scale != 1.0:
            x = x.astype(jnp.float32) * cfg.embed_scale
        x = x.astype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        block = Block
        if cfg.remat:
            if cfg.remat_policy not in ("full", "dots"):
                raise ValueError(
                    f"remat_policy must be 'full' or 'dots', got {cfg.remat_policy!r}"
                )
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            block = nn.remat(Block, static_argnums=(), policy=policy)
        for i in range(cfg.n_layers):
            x = block(cfg, cfg.layer_kind(i), cfg.ffn_kind(i), cfg.attn_kind(i), name=f"layer_{i}")(
                x, positions, cache_idx, block_tables, seq_lens, snap_lens, window_tables)
        if logit_rows is not None:
            x = jnp.take_along_axis(x, logit_rows[:, None, None], axis=1)
        x = RMSNorm(cfg.norm_eps, name="final_norm")(x)
        if cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = LoRALinear(cfg.vocab_size, cfg, name="lm_head")(x)
        return logits.astype(jnp.float32)

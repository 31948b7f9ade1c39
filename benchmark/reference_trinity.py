"""The plain reference of Trinity-Mini (``model_type: afmoe``): float32 ``jax.numpy``, one sequence.

Written from the published config's keys (``configs/trinity-mini.json``) and the
family's modelling code as the issue's author described it; what no config key
states is listed under ``assumed`` in that file. With D = hidden_size, H query
heads on K kv heads of width d = head_dim (H d != D), N(.) an RMSNorm with its
own scale, for the token x at position t:

  embedding  x0 = E[token] * sqrt(D)                                   (mup_enabled)
  layer i    h = x + N2(Attn_i(N1(x)));  y = h + N4(FFN_i(N3(h)))      (four norms a layer)
  Attn_i     q = N_q(x W_q) a head, k = N_k(x W_k) a head (over d, own scales), v = x W_v, g = x W_g;
             layer_types[i] == "sliding_attention": q, k rotated (rope_theta, no scaling) and key j
             visible to query t iff t - sliding_window < j <= t;  "full_attention": NO positions, j <= t;
             scores / sqrt(d), softmax in float32;  out = (softmax(.) v * sigmoid(g)) W_o
  FFN_i      i < num_dense_layers: SwiGLU of intermediate_size;  else s = sigmoid(x W_r) over ALL
             router_width experts, the num_experts_per_tok largest of s + b (b the selection bias: the
             CHOICE only), g_e = route_scale * s_e / (sum of the picked s + 1e-20) (route_norm),
             y = SwiGLU_shared(x) + sum_{e picked, e held} g_e SwiGLU_e(x)
  the end    a final RMSNorm, an untied head

No kernels, no cache, no batching, nothing imported from the program; every
matmul at ``Precision.HIGHEST``. Computed in blocks so that a 16.9 k-token
sequence fits beside 8.48 GB of weights: the attention a kv head's group of
query heads and ``Q_BLOCK`` queries at a time (their ``[G, Q_BLOCK, T]`` scores
are all that is live), the routed layer one expert at a time over every token
with the gate as a mask (``lax.map`` over the stacked experts). The weights
stay in the dtype they arrive in and are raised to float32 a matrix at a time.

Departures from the source, none of them mathematics: rotary pairs are the
interleaved (2i, 2i+1) pairs, as the program stores them (the checkpoint stores
the rotate-half permutation of the same columns); the experts are read from
stacked ``[E, in, out]`` arrays and the selection bias from ``router_bias``;
with ``expert_rank`` / ``router_width`` only the experts held add to y (this
configuration holds all 128).

``quant`` is ``reference.py``'s control: every matmul operand rounded to int8
(W8A8); the router, the softmax, the norms and the gates stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import F32, HIGHEST, QUANT, _mm, bf16_quant, int8_quant, rmsnorm, rope  # noqa: F401

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_hidden_layers",
        "rms_norm_eps", "rope_theta", "sliding_window", "layer_types", "num_dense_layers", "num_experts",
        "num_experts_per_tok", "num_shared_experts", "route_scale", "route_norm", "mup_enabled")
Q_BLOCK = 512  # queries whose scores over the whole sequence are live at once


def norm_cfg(cfg: dict) -> dict:
    """The keys the reference reads, from the configuration's file."""
    out = {k: cfg[k] for k in KEYS}
    out["layer_types"] = tuple(out["layer_types"])
    out["router_width"] = int(cfg.get("router_width", cfg["num_experts"]))
    out["expert_rank"] = int(cfg.get("expert_rank", 0))
    return out


def swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant), w_down, quant)


def head_norm(x, scale, eps):
    """RMSNorm over the last axis of ``[T, heads, d]`` with one scale ``[d]``."""
    return rmsnorm(x, scale, eps)


def attention(p, x, positions, kind, cfg, quant):
    t = x.shape[0]
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = head_norm(_mm(x, p["q_proj"]["kernel"], quant).reshape(t, h, d), p["q_norm"]["scale"], eps)
    k = head_norm(_mm(x, p["k_proj"]["kernel"], quant).reshape(t, kv, d), p["k_norm"]["scale"], eps)
    v = _mm(x, p["v_proj"]["kernel"], quant).reshape(t, kv, d)
    gate = jax.nn.sigmoid(_mm(x, p["g_proj"]["kernel"], quant))
    window = kind == "sliding_attention"
    if window:
        q, k = rope(q, positions, cfg["rope_theta"]), rope(k, positions, cfg["rope_theta"])
    g = h // kv
    qb = min(Q_BLOCK, t)
    assert t % qb == 0, (t, qb)
    qg = q.reshape(t // qb, qb, kv, g, d)
    pos_q = positions.reshape(t // qb, qb)

    def block(args):
        q_blk, p_blk = args                                          # [qb, kv, g, d], [qb]
        s = jnp.einsum("qcgd,kcd->cgqk", q_blk, k, precision=HIGHEST) / math.sqrt(d)
        seen = positions[None, :] <= p_blk[:, None]
        if window:
            seen = jnp.logical_and(seen, positions[None, :] > p_blk[:, None] - cfg["sliding_window"])
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("cgqk,kcd->qcgd", a, v, precision=HIGHEST)

    o = jax.lax.map(block, (qg, pos_q)).reshape(t, h * d)
    return _mm(o * gate, p["o_proj"]["kernel"], quant)


def gates(p, x, cfg):
    """[T, router_width] float32: g_e where expert e is one of the token's picks, else 0."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HIGHEST))
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(F32), cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["route_norm"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["route_scale"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(top)


def routed(p, x, cfg, quant):
    g = gates(p, x, cfg)
    held = cfg["num_experts"]
    first = cfg["expert_rank"] * held
    sh = p["shared"]
    y = swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"], sh["down_proj"]["kernel"], quant)

    def one(args):  # an expert held here over every token, its gate the mask
        w_gate, w_up, w_down, g_e = args
        return g_e[:, None] * swiglu(x, w_gate, w_up, w_down, quant)

    def add(acc, args):
        return acc + one(args), None

    g_held = jax.lax.dynamic_slice_in_dim(g, first, held, axis=1).T     # [held, T]
    y, _ = jax.lax.scan(add, y, (p["w_gate"], p["w_up"], p["w_down"], g_held))
    return y


def block(p, x, i, positions, cfg, quant):
    eps = cfg["rms_norm_eps"]
    a = attention(p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps), positions, cfg["layer_types"][i], cfg, quant)
    x = x + rmsnorm(a, p["mixer_out_norm"]["scale"], eps)
    hcur = rmsnorm(x, p["mlp_norm"]["scale"], eps)
    if i < cfg["num_dense_layers"]:
        m = p["mlp"]
        y = swiglu(hcur, m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"], quant)
    else:
        y = routed(p["moe"], hcur, cfg, quant)
    return x + rmsnorm(y, p["mlp_out_norm"]["scale"], eps)


def hidden(params, tokens, cfg, quant=None):
    """Final-normed hidden states [T, D] of ONE sequence ``tokens`` [T]."""
    x = params["embed"]["embedding"].astype(F32)[tokens]
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = block(params[f"layer_{i}"], x, i, positions, cfg, quant)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits_at(params, tokens, rows, cfg, quant=None):
    """Logits [len(rows), V] at positions ``rows`` of one sequence."""
    return _mm(hidden(params, tokens, cfg, quant)[rows], params["lm_head"]["kernel"], quant)

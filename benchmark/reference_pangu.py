"""The plain reference of openPangu-Ultra-MoE: float32 ``jax.numpy``, one sequence.

Written from the published config's keys (``configs/openpangu-ultra-moe-718b.json``)
and the family's description. With D = hidden_size, H heads, d_n / d_r / d_v =
qk_nope / qk_rope / v head dims, r_q / r = q / kv lora ranks, N(.) an RMSNorm
with its own scale, for the token x at position t:

  layer      h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h)))        (sandwich_norm)
  Attn       c_q = N_q(x W_dq);  [q_n ; q_r] = c_q W_uq a head;  [c_kv ; k_r] = x W_dkv (k_r ONE
             for all heads);  c = N_kv(c_kv);  [k_n ; v] = c W_ukv a head;  q_r, k_r rotated;
             score_i = (q_n,i . k_n,i + q_r,i . k_r) / sqrt(d_n + d_r), causal softmax,
             out = concat_i(sum_s p_i,s v_i,s) W_o
  FFN        the dense SwiGLU of intermediate_size in the first first_k_dense_replace layers;
             after them  s = sigmoid(x W_r) over ALL router_width experts, T = the
             num_experts_per_tok largest, g_e = routed_scaling_factor * s_e / (sum_{j in T} s_j +
             1e-20), y = SwiGLU_shared(x) + sum_{e in T, e held} g_e SwiGLU_e(x)
  the end    a final RMSNorm, an untied head

The attention is the EXPANDED form (keys and values of every head rebuilt from
the latents), the routed layer a loop over the experts HELD (``n_routed_experts``
of them, those of rank ``expert_rank``: ids ``rank * held ..``), every expert
over every token with the gate as a mask. No kernels, no cache, no batching,
nothing imported from the program; every matmul at ``Precision.HIGHEST``. The
weights stay in the dtype they arrive in (the benchmark's bfloat16) and are
raised to float32 a matrix at a time, the experts one at a time, the attention a
group of heads at a time: the timed sizes fit beside 9.84 GB of weights.

Departures from the published description, none of them mathematics: rotary
pairs are the interleaved (2i, 2i+1) pairs, as the program stores them (the
checkpoint stores the rotate-half permutation of the same columns); what the
experts NOT held would add is left out (the share: the guide's section 4), as
in the program; the multi-token-prediction module is not part of the main
model's logits and is absent.

``quant`` is ``reference.py``'s control: every matmul operand rounded to int8
(W8A8); the router, the softmax and the norms stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import F32, HIGHEST, QUANT, _mm, bf16_quant, int8_quant, rmsnorm, rope  # noqa: F401

KEYS = ("hidden_size", "num_attention_heads", "num_hidden_layers", "rms_norm_eps", "rope_theta", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob")
HEAD_GROUP = 32  # heads whose [T, T] scores are live at once


def norm_cfg(cfg: dict) -> dict:
    """The keys the reference reads, from the configuration's file."""
    out = {k: cfg[k] for k in KEYS}
    out["sandwich_norm"] = bool(cfg.get("sandwich_norm", False))
    out["router_width"] = int(cfg.get("router_width", cfg["n_routed_experts"]))
    out["expert_rank"] = int(cfg.get("expert_rank", 0))
    return out


def swiglu(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant), w_down, quant)


def attention(p, x, positions, cfg, quant):
    t = x.shape[0]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = rmsnorm(_mm(x, p["q_a_proj"]["kernel"], quant), p["q_a_norm"]["scale"], eps)
    q = _mm(c_q, p["q_b_proj"]["kernel"], quant).reshape(t, h, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], positions, theta)
    ckr = _mm(x, p["kv_a_proj"]["kernel"], quant)
    c = rmsnorm(ckr[:, :r], p["kv_a_norm"]["scale"], eps)
    k_r = rope(ckr[:, None, r:], positions, theta)[:, 0]                         # [t, dr]
    kv = _mm(c, p["kv_b_proj"]["kernel"], quant).reshape(t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    causal = positions[:, None] >= positions[None, :]
    outs = []
    for h0 in range(0, h, HEAD_GROUP):
        sl = slice(h0, min(h0 + HEAD_GROUP, h))
        s = (jnp.einsum("qhd,khd->hqk", q_n[:, sl], k_n[:, sl], precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_r[:, sl], k_r, precision=HIGHEST)) / math.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", a, v[:, sl], precision=HIGHEST))
    o = jnp.concatenate(outs, axis=1).reshape(t, h * dv)
    return _mm(o, p["o_proj"]["kernel"], quant)


def gates(p, x, cfg):
    """[T, router_width] float32: g_e where expert e is one of the token's picks, else 0."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(F32), precision=HIGHEST))
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], idx].set(top)


def routed(p, x, cfg, quant):
    g = gates(p, x, cfg)
    held = cfg["n_routed_experts"]
    first = cfg["expert_rank"] * held
    sh = p["shared"]
    y = swiglu(x, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"], sh["down_proj"]["kernel"], quant)
    for e in range(held):  # the experts held here, one at a time
        y = y + g[:, first + e, None] * swiglu(x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], quant)
    return y


def block(p, x, i, positions, cfg, quant):
    eps = cfg["rms_norm_eps"]
    a = attention(p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps), positions, cfg, quant)
    if cfg["sandwich_norm"]:
        a = rmsnorm(a, p["mixer_out_norm"]["scale"], eps)
    x = x + a
    hcur = rmsnorm(x, p["mlp_norm"]["scale"], eps)
    if i < cfg["first_k_dense_replace"]:
        m = p["mlp"]
        y = swiglu(hcur, m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"], quant)
    else:
        y = routed(p["moe"], hcur, cfg, quant)
    if cfg["sandwich_norm"]:
        y = rmsnorm(y, p["mlp_out_norm"]["scale"], eps)
    return x + y


def hidden(params, tokens, cfg, quant=None):
    """Final-normed hidden states [T, D] of ONE sequence ``tokens`` [T]."""
    x = params["embed"]["embedding"].astype(F32)[tokens]
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        x = block(params[f"layer_{i}"], x, i, positions, cfg, quant)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits_at(params, tokens, rows, cfg, quant=None):
    """Logits [len(rows), V] at positions ``rows`` of one sequence."""
    return _mm(hidden(params, tokens, cfg, quant)[rows], params["lm_head"]["kernel"], quant)

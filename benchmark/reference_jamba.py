"""The plain reference of AI21-Jamba2-3B: float32 ``jax.numpy``, one sequence.

Written from the published config's keys (``configs/jamba2-3b.json``): 28
pre-norm blocks, each ``x + mixer(RMSNorm(x))`` then ``x + SwiGLU(RMSNorm(x))``;
the mixer is causal softmax attention (20 query heads on ONE key/value head of
128, NO rotary or other positions, no biases) where ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba-1 selective state-space layer with the
family's inner norms elsewhere; final RMSNorm; logits against the token
embedding (tied head). With ``d = mamba_expand * hidden_size``:

    (u, z) = split(x W_in)
    c_t    = silu(b_conv + sum_{j<K} w_conv[j] * u_{t-K+1+j})     zeros to the left
    (dt_raw, B_t, C_t) = split(c_t W_x), each through its own RMSNorm
    dt_t   = softplus(dt_raw W_dt + b_dt)
    h_t    = exp(dt_t (x) A) * h_{t-1} + (dt_t * c_t) (x) B_t,    A = -exp(A_log), h_0 = 0
    y_t    = h_t C_t + D * c_t;    out = (y * silu(z)) W_out

No kernels, no cache, no batching, nothing imported from the program; every
matmul at ``Precision.HIGHEST``; the recurrence is a sequential ``lax.scan``,
one token a step. Forward only, so a layer's intermediates are freed before
the next layer's are made: the timed sizes fit beside the weights.

Departure, a layout and not mathematics: ``A_log`` (and so the state) is read
``[d_state, d]``, as the program stores it (the checkpoint stores ``[d,
d_state]``).

``quant`` is ``reference.py``'s control: every matmul operand rounded to int8
(W8A8); the recurrence and the norms stay float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference import F32, HIGHEST, QUANT, _mm, bf16_quant, int8_quant, rmsnorm  # noqa: F401

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "num_hidden_layers", "rms_norm_eps",
        "attn_layer_period", "attn_layer_offset", "mamba_d_state", "mamba_d_conv", "mamba_dt_rank", "mamba_expand")


def norm_cfg(cfg: dict) -> dict:
    """The keys the reference reads, from the published config.json."""
    if int(cfg.get("num_experts", 1)) != 1:
        raise ValueError("the reference has the dense feed-forward only (num_experts 1)")
    out = {k: cfg[k] for k in KEYS}
    out["head_dim"] = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    out["tie_word_embeddings"] = bool(cfg.get("tie_word_embeddings", False))
    return out


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def attention(p, x, cfg, quant):
    t = x.shape[0]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _mm(x, p["q_proj"]["kernel"], quant).reshape(t, kv, h // kv, hd)
    k = _mm(x, p["k_proj"]["kernel"], quant).reshape(t, kv, hd)
    v = _mm(x, p["v_proj"]["kernel"], quant).reshape(t, kv, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST) / math.sqrt(hd)
    pos = jnp.arange(t)
    s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", a, v, precision=HIGHEST).reshape(t, h * hd)
    return _mm(o, p["o_proj"]["kernel"], quant)


def mamba(p, x, cfg, quant):
    t = x.shape[0]
    eps, n, r, k = cfg["rms_norm_eps"], cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    uz = _mm(x, p["in_proj"]["kernel"], quant)
    u, z = uz[:, :d], uz[:, d:]
    left = jnp.concatenate([jnp.zeros((k - 1, d), F32), u], axis=0)
    w = p["conv_kernel"].astype(F32)  # [K, d]
    c = jax.nn.silu(p["conv_bias"].astype(F32) + sum(w[j] * left[j:j + t] for j in range(k)))
    proj = _mm(c, p["x_proj"]["kernel"], quant)
    dt_raw = rmsnorm(proj[:, :r], p["dt_norm"]["scale"], eps)
    b = rmsnorm(proj[:, r:r + n], p["b_norm"]["scale"], eps)
    cc = rmsnorm(proj[:, r + n:], p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(_mm(dt_raw, p["dt_proj"]["kernel"], quant) + p["dt_proj"]["bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))  # [N, d]
    skip = p["D"].astype(F32)

    def step(h, xs):
        dt_t, c_t, b_t, cc_t = xs
        h = jnp.exp(dt_t[None, :] * a) * h + (dt_t * c_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * cc_t[:, None], axis=0) + skip * c_t

    _, y = jax.lax.scan(step, jnp.zeros((n, d), F32), (dt, c, b, cc))
    return _mm(y * jax.nn.silu(z), p["out_proj"]["kernel"], quant)


def block(p, x, i, cfg, quant):
    eps = cfg["rms_norm_eps"]
    if is_attention(cfg, i):
        x = x + attention(p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps), cfg, quant)
    else:
        x = x + mamba(p["mamba"], rmsnorm(x, p["mamba_norm"]["scale"], eps), cfg, quant)
    hcur = rmsnorm(x, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    gate = _mm(hcur, m["gate_proj"]["kernel"], quant)
    up = _mm(hcur, m["up_proj"]["kernel"], quant)
    return x + _mm(jax.nn.silu(gate) * up, m["down_proj"]["kernel"], quant)


def hidden(params, tokens, cfg, quant=None):
    """Final-normed hidden states [T, D] of ONE sequence ``tokens`` [T]."""
    x = params["embed"]["embedding"].astype(F32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = block(params[f"layer_{i}"], x, i, cfg, quant)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def head(params, cfg):
    """[D, V]: the embedding's transpose where the head is tied."""
    if cfg["tie_word_embeddings"]:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


def logits_at(params, tokens, rows, cfg, quant=None):
    """Logits [len(rows), V] at positions ``rows`` of one sequence."""
    return _mm(hidden(params, tokens, cfg, quant)[rows], head(params, cfg), quant)

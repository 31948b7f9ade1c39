"""The plain reference: the two configurations' block in float32 ``jax.numpy``.

Written from the published descriptions (Mistral-7B-v0.3 and InternLM2-7B
share it): token embedding, pre-norm RMSNorm, rotary positions on q and k,
grouped-query causal softmax attention, SwiGLU, no biases, final RMSNorm,
untied output head; a LoRA adapter adds (alpha/r) x A B to a projection.
No kernels, no cache, no batching, nothing imported from the program. Every
matmul runs at ``Precision.HIGHEST`` (on a TPU a float32 matmul is bf16
otherwise). One sequence at a time, a block at a time under
``jax.checkpoint``, so the timed sizes fit beside the weights.

Departures, both layouts and not mathematics: rotary pairs are the
interleaved (2i, 2i+1) pairs of the RoFormer paper, as the program stores
them (the HF checkpoints store the rotate-half permutation of the same
heads); InternLM2's fused ``wqkv`` is read as three projections.

``quant`` puts the reference in the program's place at a lower precision
(the control of ``correct``): every matmul operand is rounded to int8 with
one scale per row of the contraction, the W8A8 step a later PR would be
tempted by. Gradients pass straight through the rounding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# -- precision ---------------------------------------------------------------

def _int8_rows(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def int8_quant(x, w):
    """W8A8: activations one scale per token, weights one per output column."""
    return _int8_rows(x, -1), _int8_rows(w, 0)


def bf16_quant(x, w):
    def r(a):
        return a + jax.lax.stop_gradient(a.astype(jnp.bfloat16).astype(F32) - a)
    return r(x), r(w)


QUANT = {"none": None, "int8": int8_quant, "bf16": bf16_quant}


def _mm(x, w, quant):
    x, w = x.astype(F32), w.astype(F32)
    if quant is not None:
        x, w = quant(x, w)
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the block ---------------------------------------------------------------

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x [T, H, D]; rotate the pairs (2i, 2i+1) by positions * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).reshape(x.shape)


def linear(p, x, lora_scale, quant):
    y = _mm(x, p["kernel"], quant)
    if "lora_a" in p:
        y = y + lora_scale * _mm(_mm(x, p["lora_a"], quant), p["lora_b"], quant)
    return y


def attention(p, x, positions, cfg, lora_scale, quant):
    t = x.shape[0]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rope(linear(p["q_proj"], x, lora_scale, quant).reshape(t, h, hd), positions, cfg["rope_theta"])
    k = rope(linear(p["k_proj"], x, lora_scale, quant).reshape(t, kv, hd), positions, cfg["rope_theta"])
    v = linear(p["v_proj"], x, lora_scale, quant).reshape(t, kv, hd)
    g = h // kv
    q = q.reshape(t, kv, g, hd)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = positions[:, None] >= positions[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", a, v, precision=HIGHEST).reshape(t, h * hd)
    return linear(p["o_proj"], o, lora_scale, quant)


def block(p, x, positions, cfg, lora_scale, quant):
    eps = cfg["rms_norm_eps"]
    x = x + attention(p["attn"], rmsnorm(x, p["attn_norm"]["scale"], eps), positions, cfg, lora_scale, quant)
    hcur = rmsnorm(x, p["mlp_norm"]["scale"], eps)
    m = p["mlp"]
    gate = linear(m["gate_proj"], hcur, lora_scale, quant)
    up = linear(m["up_proj"], hcur, lora_scale, quant)
    return x + linear(m["down_proj"], jax.nn.silu(gate) * up, lora_scale, quant)


def norm_cfg(cfg: dict) -> dict:
    """The keys the reference reads, from a published config.json."""
    out = {k: cfg[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                               "num_hidden_layers", "rms_norm_eps", "rope_theta")}
    out["head_dim"] = int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])
    return out


def hidden(params, tokens, cfg, lora_scale=0.0, quant=None):
    """Final-normed hidden states [T, D] of ONE sequence ``tokens`` [T]."""
    x = params["embed"]["embedding"].astype(F32)[tokens]
    positions = jnp.arange(tokens.shape[0])
    for i in range(cfg["num_hidden_layers"]):
        f = jax.checkpoint(functools.partial(block, cfg=cfg, lora_scale=lora_scale, quant=quant))
        x = f(params[f"layer_{i}"], x, positions)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits_at(params, tokens, rows, cfg, quant=None):
    """Logits [len(rows), V] at positions ``rows`` of one sequence."""
    hcur = hidden(params, tokens, cfg, 0.0, quant)[rows]
    return _mm(hcur, params["lm_head"]["kernel"], quant)


def loss_sum(params, tokens, mask, cfg, lora_scale, quant=None):
    """Sum over one sequence of mask[t+1] x cross-entropy(logits[t], tokens[t+1])."""
    hcur = hidden(params, tokens, cfg, lora_scale, quant)[:-1]
    lg = _mm(hcur, params["lm_head"]["kernel"], quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum((lse - picked) * mask[1:].astype(F32))


# -- LoRA step: masked mean loss, clip by global norm, AdamW ------------------

def split_adapters(params):
    def walk(node, pick):
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                sub = walk(v, pick)
                if sub:
                    out[k] = sub
            elif (k in ("lora_a", "lora_b")) == pick:
                out[k] = v
        return out
    return walk(params, True), walk(params, False)


def merge(a, b):
    out = dict(b)
    for k, v in a.items():
        out[k] = merge(v, b.get(k, {})) if isinstance(v, dict) else v
    return out


def make_row_grad(cfg, lora_scale, quant=None):
    """jitted (adapters, frozen, tokens[T], mask[T]) -> (loss sum, d/d adapters)."""
    def f(adapters, frozen, tokens, mask):
        return loss_sum(merge(adapters, frozen), tokens, mask, cfg, lora_scale, quant)
    return jax.jit(jax.value_and_grad(f))


def lr_at(count, peak, warmup_steps, decay_steps):
    """optax.warmup_cosine_decay_schedule(0, peak, warmup, decay, end 0) in
    plain arithmetic: linear from 0 over ``warmup_steps`` then a half cosine
    over the remaining ``decay_steps - warmup_steps``."""
    if count < warmup_steps:
        return peak * count / warmup_steps
    frac = min(max(count - warmup_steps, 0) / max(decay_steps - warmup_steps, 1), 1.0)
    return peak * 0.5 * (1.0 + math.cos(math.pi * frac))


def lora_steps(params, batches, cfg, opt, quant=None, keep_rows=None):
    """Follow ``len(batches)`` LoRA steps. Returns per-step losses, the first
    gradient as Adam receives it (after the clip) and the adapters' change.
    ``opt``: lora_alpha, lora_rank, learning_rate, warmup_steps, max_steps,
    grad_clip, weight_decay. ``keep_rows`` plants a fault: only those rows of
    each batch count (the mean is over the rest)."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    row_grad = make_row_grad(cfg, opt["lora_alpha"] / opt["lora_rank"], quant)
    adapters, frozen = split_adapters(params)
    start = adapters
    tmap = jax.tree_util.tree_map
    mu = tmap(jnp.zeros_like, adapters)
    nu = tmap(jnp.zeros_like, adapters)
    losses, first_grad = [], None
    for count, (toks, mask) in enumerate(batches):
        rows = range(toks.shape[0]) if keep_rows is None else keep_rows
        total, grads = 0.0, None
        for r in rows:
            val, g = row_grad(adapters, frozen, jnp.asarray(toks[r]), jnp.asarray(mask[r]))
            total = total + val
            grads = g if grads is None else tmap(jnp.add, grads, g)
        denom = max(float(sum(mask[r][1:].sum() for r in rows)), 1.0)
        losses.append(float(total) / denom)
        grads = tmap(lambda x: x / denom, grads)
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(x))) for x in jax.tree_util.tree_leaves(grads)))
        if gnorm >= opt["grad_clip"]:
            grads = tmap(lambda x: x / gnorm * opt["grad_clip"], grads)
        if first_grad is None:
            first_grad = grads
        t = count + 1
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        lr = lr_at(count, opt["learning_rate"], opt["warmup_steps"], opt["max_steps"])
        wd = opt.get("weight_decay", 0.0)
        adapters = tmap(
            lambda p, m, v: p - lr * ((m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p),
            adapters, mu, nu)
    delta = tmap(jnp.subtract, adapters, start)
    return {"losses": losses, "first_grad": first_grad, "delta": delta}

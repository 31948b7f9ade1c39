"""Operations and bytes openPangu-Ultra-MoE's layers need, from shapes alone.

The companion of ``flops.py`` for a configuration whose attention is latent
(MLA), whose feed-forward is routed after ``first_k_dense_replace`` dense
layers, and of which this chip holds a share: ``cfg`` is
``configs/openpangu-ultra-moe-718b.json``'s keys, where ``n_routed_experts``
counts the experts HELD and ``router_width`` the router's outputs. As there,
everything is the REQUIRED work of any correct implementation: the experts'
part is counted from the (token, held expert) pairs the ROUTING made and from
the held experts that were HIT, never from what this program computes or reads.
"""

from __future__ import annotations


def latent_width(cfg: dict) -> int:
    """Values a token leaves in one layer's cache."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_params(cfg: dict) -> int:
    """W_dq, W_uq, W_dkv, W_ukv, W_o of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * rq + rq * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert: a SwiGLU of moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * int(cfg.get("router_width", cfg["n_routed_experts"]))


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def n_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_layer_params(cfg: dict) -> int:
    """MLA + shared expert(s) + router + the routed experts held here."""
    return (mla_params(cfg) + cfg["n_shared_experts"] * expert_params(cfg) + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    return mla_params(cfg) + dense_ffn_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def norm_params(cfg: dict) -> int:
    per_layer = (4 if cfg.get("sandwich_norm") else 2) * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"]


def matmul_params(cfg: dict) -> int:
    """Every matrix held here: the layers, the untied embedding and the head."""
    return (cfg["first_k_dense_replace"] * dense_layer_params(cfg) + n_expert_layers(cfg) * expert_layer_params(cfg)
            + 2 * head_params(cfg))


def total_params(cfg: dict) -> int:
    return matmul_params(cfg) + norm_params(cfg)


def non_expert_read_params(cfg: dict) -> int:
    """Weights every decode token-step reads whatever the routing: all but the
    routed experts and the embedding (a row a slot)."""
    return (matmul_params(cfg) - head_params(cfg)
            - n_expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)) + norm_params(cfg)


def dense_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs a token needs whatever the routing: MLA's five matrices in
    every layer, the dense feed-forward, the shared expert(s) and the router."""
    n_e = n_expert_layers(cfg)
    per = (cfg["num_hidden_layers"] * mla_params(cfg) + cfg["first_k_dense_replace"] * dense_ffn_params(cfg)
           + n_e * (cfg["n_shared_experts"] * expert_params(cfg) + router_params(cfg)))
    return 2.0 * per


def attn_flops_per_ctx(cfg: dict, absorbed: bool) -> float:
    """Attention FLOPs of one query token per key it sees, all layers: expanded
    (prefill) H x (d_n + d_r + d_v) multiply-adds, absorbed (decode) H x
    ((r + d_r) + r)."""
    h = cfg["num_attention_heads"]
    if absorbed:
        per = h * (latent_width(cfg) + cfg["kv_lora_rank"])
    else:
        per = h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return 2.0 * per * cfg["num_hidden_layers"]


def serve_flops(cfg: dict, prefill_tokens: list, decode_positions: list, local_picks: int) -> float:
    """Required FLOPs of serving, as ``flops.serve_flops`` counts them, plus
    ``2 x expert_params`` for each of the ``local_picks`` (token, held expert)
    pairs the routing made. ``prefill_tokens`` is (tokens_computed,
    first_position) per request, ``decode_positions`` the context at each
    decoded token; the head only where a token is sampled."""
    per_token, n_head = dense_flops_per_token(cfg), head_params(cfg)
    total = 2.0 * expert_params(cfg) * local_picks
    for n_tok, start in prefill_tokens:
        total += per_token * n_tok + 2.0 * n_head
        total += attn_flops_per_ctx(cfg, False) * (n_tok * start + n_tok * (n_tok + 1) / 2.0)
    for ctx in decode_positions:
        total += per_token + 2.0 * n_head + attn_flops_per_ctx(cfg, True) * ctx
    return total


def latent_bytes_per_token(cfg: dict, elem_bytes: int = 2) -> int:
    return cfg["num_hidden_layers"] * latent_width(cfg) * elem_bytes


def decode_step_bytes(cfg: dict, live_tokens: float, experts_hit: float, weight_bytes: int = 2) -> float:
    """Bytes one decode token-step MUST read: the non-expert weights once, one
    expert's three matrices for each (layer, held expert) HIT that step, and
    the latents of the tokens live."""
    return (non_expert_read_params(cfg) * weight_bytes + experts_hit * expert_params(cfg) * weight_bytes
            + live_tokens * latent_bytes_per_token(cfg))


def mla_decode_call_cost(cfg: dict, live_tokens: float, elem_bytes: int = 2):
    """(FLOPs, bytes) of ONE call of the latent decode kernel (one layer, one
    token-step) over ``live_tokens`` cached tokens: every head against the
    r + d_r wide row and the r wide value; each latent read once."""
    flops = 2.0 * cfg["num_attention_heads"] * (latent_width(cfg) + cfg["kv_lora_rank"]) * live_tokens
    return flops, float(live_tokens * latent_width(cfg) * elem_bytes)


def grouped_matmul_cost(cfg: dict, pairs: float, experts_hit: float, elem_bytes: int = 2):
    """(FLOPs, bytes) of the experts' three matmuls over ``pairs`` (token, held
    expert) rows that hit ``experts_hit`` (layer, expert): 2 x expert_params a
    pair; each hit expert's matrices once, each row in (D, twice) and out (F,
    twice; D) once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nbytes = experts_hit * expert_params(cfg) * elem_bytes + pairs * (3 * d + 3 * f) * elem_bytes
    return 2.0 * expert_params(cfg) * pairs, float(nbytes)

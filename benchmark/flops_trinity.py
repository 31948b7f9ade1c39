"""Operations and bytes Trinity-Mini's layers need, from shapes alone.

The companion of ``flops_pangu.py`` for a configuration whose attention is
gated GQA with a head size of its own, in layers of two kinds (a window of
``sliding_window`` keys, or the whole prefix), and whose feed-forward is routed
after ``num_dense_layers`` dense layers, every expert held: ``cfg`` is
``configs/trinity-mini.json``'s keys. As there, everything is the REQUIRED work
of any correct implementation: a window layer's query is counted at the
``min(keys before it + 1, sliding_window)`` keys it sees, never at the keys a
program visits; the experts' part from the (token, expert) pairs the ROUTING
made and the experts that were HIT.
"""

from __future__ import annotations

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def n_layers_of(cfg: dict, kind: str) -> int:
    return sum(1 for t in cfg["layer_types"] if KINDS[t] == kind)


def attn_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_g, W_o of one layer."""
    d, h, kv, hd = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return d * h * hd + 2 * d * kv * hd + d * h * hd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert: a SwiGLU of moe_intermediate_size."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * int(cfg.get("router_width", cfg["num_experts"]))


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def n_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expert_layer_params(cfg: dict) -> int:
    """Attention + shared expert(s) + router + the routed experts held here."""
    return (attn_params(cfg) + cfg["num_shared_experts"] * expert_params(cfg) + router_params(cfg)
            + cfg["num_experts"] * expert_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    return attn_params(cfg) + dense_ffn_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def small_params(cfg: dict) -> int:
    """What is no matrix: four norms a layer, the q and k norms' scales, the
    final norm, and the selection bias of each routed layer."""
    per_layer = 4 * cfg["hidden_size"] + 2 * cfg["head_dim"]
    return (cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"]
            + n_expert_layers(cfg) * int(cfg.get("router_width", cfg["num_experts"])))


def matmul_params(cfg: dict) -> int:
    """Every matrix held here: the layers, the untied embedding and the head."""
    return (cfg["num_dense_layers"] * dense_layer_params(cfg) + n_expert_layers(cfg) * expert_layer_params(cfg)
            + 2 * head_params(cfg))


def total_params(cfg: dict) -> int:
    return matmul_params(cfg) + small_params(cfg)


def non_expert_read_params(cfg: dict) -> int:
    """Weights every decode token-step reads whatever the routing: all but the
    routed experts and the embedding (a row a slot)."""
    return (matmul_params(cfg) - head_params(cfg)
            - n_expert_layers(cfg) * cfg["num_experts"] * expert_params(cfg)) + small_params(cfg)


def dense_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs a token needs whatever the routing: the attention's five
    matrices in every layer, the dense feed-forward, the shared expert(s), the router."""
    per = (cfg["num_hidden_layers"] * attn_params(cfg) + cfg["num_dense_layers"] * dense_ffn_params(cfg)
           + n_expert_layers(cfg) * (cfg["num_shared_experts"] * expert_params(cfg) + router_params(cfg)))
    return 2.0 * per


def attn_flops_per_key(cfg: dict) -> float:
    """Attention FLOPs of one query token per key it sees, ONE layer: every
    head's score (head_dim multiply-adds) and its share of the value."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]


def keys_seen(cfg: dict, first: int, n: int) -> tuple:
    """(full, window): keys the queries at positions ``first .. first + n - 1``
    see in ONE layer of each kind: ``t + 1`` and ``min(t + 1, sliding_window)``."""
    w = cfg["sliding_window"]
    last = first + n
    full = (last * (last + 1) - first * (first + 1)) // 2
    ramp_end = min(max(first, w), last)          # positions below w see t + 1 keys
    ramp_start = min(first, ramp_end)
    window = (ramp_end * (ramp_end + 1) - ramp_start * (ramp_start + 1)) // 2 + (last - ramp_end) * w
    return full, window


def attn_flops(cfg: dict, first: int, n: int) -> float:
    """Attention FLOPs of ``n`` query tokens from position ``first`` on, all layers."""
    full, window = keys_seen(cfg, first, n)
    return attn_flops_per_key(cfg) * (n_layers_of(cfg, "full") * full + n_layers_of(cfg, "window") * window)


def serve_flops(cfg: dict, prefill_tokens: list, decode_positions: list, local_picks: int) -> float:
    """Required FLOPs of serving, as ``flops_pangu.serve_flops`` counts them:
    ``prefill_tokens`` is (tokens_computed, first_position) per request,
    ``decode_positions`` the context at each decoded token (the keys before
    it); the head only where a token is sampled; ``2 x expert_params`` for each
    of the ``local_picks`` (token, expert) pairs the routing made."""
    per_token, n_head = dense_flops_per_token(cfg), head_params(cfg)
    total = 2.0 * expert_params(cfg) * local_picks
    for n_tok, start in prefill_tokens:
        total += per_token * n_tok + 2.0 * n_head + attn_flops(cfg, start, n_tok)
    for ctx in decode_positions:
        total += per_token + 2.0 * n_head + attn_flops(cfg, ctx, 1)
    return total


def kv_bytes_per_token_layer(cfg: dict, elem_bytes: int = 2) -> int:
    """K and V of one token in one layer's cache."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * elem_bytes


def decode_step_bytes(cfg: dict, kv_tokens_full: float, kv_tokens_window: float, experts_hit: float,
                      weight_bytes: int = 2) -> float:
    """Bytes one decode token-step MUST read: the non-expert weights once, one
    expert's three matrices for each (layer, expert) HIT that step, and K and V
    of the keys the live rows see: ``kv_tokens_full`` of them in each full
    layer, ``kv_tokens_window`` (each row's ``min(len, sliding_window)``) in
    each window layer."""
    kv = kv_bytes_per_token_layer(cfg) * (n_layers_of(cfg, "full") * kv_tokens_full
                                          + n_layers_of(cfg, "window") * kv_tokens_window)
    return non_expert_read_params(cfg) * weight_bytes + experts_hit * expert_params(cfg) * weight_bytes + kv


def paged_attention_cost(cfg: dict, kv_tokens: float, rows: float, elem_bytes: int = 2):
    """(FLOPs, bytes) of the paged decode kernel over ``kv_tokens`` (row, key)
    pairs in ONE layer (``rows`` query rows): every head against each key's
    head and value; each key's K and V read once, the queries and results beside them."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return (attn_flops_per_key(cfg) * kv_tokens,
            float(kv_tokens * kv_bytes_per_token_layer(cfg, elem_bytes) + rows * 2 * h * hd * elem_bytes))


def flash_rows_cost(cfg: dict, first: int, n: int, window: bool, elem_bytes: int = 2):
    """(FLOPs, bytes) of ONE call of the prefill's attention kernel: ``n``
    queries from position ``first`` on, the visible (query, key) pairs only;
    q in and out once, and K and V of every key some query sees once a kv
    head."""
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    full, win = keys_seen(cfg, first, n)
    pairs = win if window else full
    keys = min(first + n, n + cfg["sliding_window"] - 1) if window else first + n
    return attn_flops_per_key(cfg) * pairs, float((2 * n * h * hd + 2 * keys * kv * hd) * elem_bytes)


def grouped_matmul_cost(cfg: dict, pairs: float, experts_hit: float, elem_bytes: int = 2):
    """(FLOPs, bytes) of the experts' three matmuls: ``flops_pangu.grouped_matmul_cost``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nbytes = experts_hit * expert_params(cfg) * elem_bytes + pairs * (3 * d + 3 * f) * elem_bytes
    return 2.0 * expert_params(cfg) * pairs, float(nbytes)

"""Operations and bytes AI21-Jamba2-3B's layers need, from shapes alone.

The companion of ``flops.py`` for a configuration whose layers are of two
kinds (``attn_layer_period`` / ``attn_layer_offset``), whose head is tied and
whose prefill runs a selective scan. ``cfg`` is ``configs/jamba2-3b.json``'s
published keys. As there, everything is the REQUIRED work.
"""

from __future__ import annotations

# one (channel, state) element of one token of the scan: dt*A, exp (counted as
# one operation), dA*h, (dt*u)*B, the add, h*C, the add of the sum over states
SCAN_OPS_PER_ELEMENT = 7
# and per channel: dt*u, D*u, the add of the skip
SCAN_OPS_PER_CHANNEL = 3


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def layer_kinds(cfg: dict) -> list:
    return ["attention" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def n_layers(cfg: dict, kind: str) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == kind)


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def attn_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return 2 * d * cfg["num_attention_heads"] * hd + 2 * d * cfg["num_key_value_heads"] * hd


def mamba_matmul_params(cfg: dict) -> int:
    """in_proj, x_proj, dt_proj, out_proj."""
    d, di, n, r = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def mamba_other_params(cfg: dict) -> int:
    """conv kernel and bias, dt bias, A_log, D, the three inner norms."""
    di, n, r, k = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    return di * k + di + di + di * n + di + (r + 2 * n)


def layer_params(cfg: dict, kind: str) -> int:
    mixer = attn_matmul_params(cfg) if kind == "attention" else mamba_matmul_params(cfg) + mamba_other_params(cfg)
    return mixer + mlp_params(cfg) + 2 * cfg["hidden_size"]  # + the block's two norms


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Every weight, the tied embedding ONCE."""
    return (sum(layer_params(cfg, k) for k in layer_kinds(cfg)) + embedding_params(cfg)
            + cfg["hidden_size"])  # + the final norm


def block_matmul_params(cfg: dict) -> int:
    """Weights a token is multiplied by in the 28 blocks (no head)."""
    return (n_layers(cfg, "mamba") * mamba_matmul_params(cfg) + n_layers(cfg, "attention") * attn_matmul_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg))


def scan_flops_per_token(cfg: dict) -> float:
    """One Mamba layer's recurrence for one token (vector-unit work)."""
    di = d_inner(cfg)
    return float(di * cfg["mamba_d_state"] * SCAN_OPS_PER_ELEMENT + di * SCAN_OPS_PER_CHANNEL)


def conv_flops_per_token(cfg: dict) -> float:
    return 2.0 * d_inner(cfg) * cfg["mamba_d_conv"]


def state_bytes(cfg: dict, conv_elem_bytes: int = 2) -> int:
    """One request's recurrent state over all Mamba layers: h in float32, the
    convolution's last d_conv-1 inputs in bfloat16."""
    di = d_inner(cfg)
    per_layer = cfg["mamba_d_state"] * di * 4 + (cfg["mamba_d_conv"] - 1) * di * conv_elem_bytes
    return n_layers(cfg, "mamba") * per_layer


def scan_call_cost(cfg: dict, tokens: int):
    """(FLOPs, bytes) of ONE call of the scan kernel over ``tokens`` positions
    of one sequence: the operations above; each operand read and each result
    written once in the float32 the kernel takes them (u, dt in; y out; B, C;
    A, D; the state in, and out twice: at the true length and at the snapshot)."""
    di, n = d_inner(cfg), cfg["mamba_d_state"]
    nbytes = 4 * (3 * tokens * di + 2 * tokens * n + n * di + di + 3 * n * di)
    return tokens * scan_flops_per_token(cfg), float(nbytes)


def kv_bytes_per_token(cfg: dict, elem_bytes: int = 2) -> int:
    return n_layers(cfg, "attention") * 2 * cfg["num_key_value_heads"] * head_dim(cfg) * elem_bytes


def decode_step_bytes(cfg: dict, live_kv_tokens: float, live_slots: float, weight_bytes: int = 2) -> float:
    """Bytes one decode token-step must move: every weight once (the blocks and
    the tied embedding as the head; the lookup's rows are nothing beside it),
    the keys and values of the tokens live in the two attention layers, and
    each LIVE slot's recurrent state read and written."""
    weights = (total_params(cfg)) * weight_bytes
    return weights + live_kv_tokens * kv_bytes_per_token(cfg) + live_slots * 2.0 * state_bytes(cfg)


def serve_flops(cfg: dict, prefill_tokens: list, decode_positions: list) -> float:
    """Required FLOPs of serving, as ``flops.serve_flops`` counts them:
    ``prefill_tokens`` is (tokens_computed, first_position) per request,
    ``decode_positions`` the context length at each decoded token. Per token:
    2 x the blocks' matmul weights, the scan and the convolution of the 26
    Mamba layers; attention 4 x heads x head_dim x context in the 2 attention
    layers; the tied head only where a token is sampled."""
    per_token = (2.0 * block_matmul_params(cfg)
                 + n_layers(cfg, "mamba") * (scan_flops_per_token(cfg) + conv_flops_per_token(cfg)))
    n_head = embedding_params(cfg)
    attn_per_ctx = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * n_layers(cfg, "attention")
    total = 0.0
    for n_tok, start in prefill_tokens:
        total += per_token * n_tok + 2.0 * n_head
        total += attn_per_ctx * (n_tok * start + n_tok * (n_tok + 1) / 2.0)
    for ctx in decode_positions:
        total += per_token + 2.0 * n_head + attn_per_ctx * ctx
    return total

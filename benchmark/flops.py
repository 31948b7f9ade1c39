"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can move the
numerator. Everything is the REQUIRED work: a frozen weight needs no dW, a
recomputed forward (remat) is not counted, causal attention needs half the
square. ``cfg`` is a configuration file under ``benchmark/configs`` (the
published ``config.json`` keys).
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block's seven projections (GQA q/k/v/o + SwiGLU)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return q + kv + o + mlp


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied by: the blocks and the untied
    output head. The embedding is a row lookup, not a matmul."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)


def total_params(cfg: dict) -> int:
    norms = (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
    return matmul_params(cfg) + head_params(cfg) + norms  # + embedding


def lora_params(cfg: dict, rank: int, targets=("q_proj", "k_proj", "v_proj", "o_proj")) -> int:
    d, hd = cfg["hidden_size"], head_dim(cfg)
    out = {"q_proj": (d, cfg["num_attention_heads"] * hd),
           "k_proj": (d, cfg["num_key_value_heads"] * hd),
           "v_proj": (d, cfg["num_key_value_heads"] * hd),
           "o_proj": (cfg["num_attention_heads"] * hd, d)}
    per_layer = sum(rank * (out[t][0] + out[t][1]) for t in targets)
    return cfg["num_hidden_layers"] * per_layer


def causal_attention_unit(cfg: dict, seq_len: int) -> float:
    """FLOPs of ONE [T,hd]x[hd,T]-sized matmul over all query heads of one
    sequence, halved for the causal triangle."""
    return 2.0 * (seq_len * seq_len / 2.0) * head_dim(cfg) * cfg["num_attention_heads"]


def train_step_flops(cfg: dict, lora_rank: int, batch: int, seq_len: int) -> float:
    """Required FLOPs of one LoRA step on ``batch`` sequences of ``seq_len``:
    forward 2 N, input gradients 2 N (no dW for frozen weights), adapters
    forward + both gradients 6 N_lora, causal attention forward 2 units and
    backward 4 units (dV, dP, dQ, dK) per layer and sequence."""
    tokens = batch * seq_len
    n = matmul_params(cfg)
    n_lora = lora_params(cfg, lora_rank) if lora_rank else 0
    dense = tokens * (4.0 * n + 6.0 * n_lora)
    attn = batch * cfg["num_hidden_layers"] * 6.0 * causal_attention_unit(cfg, seq_len)
    return dense + attn


# matmul-units one call of each flash kernel needs (see the kernels'
# signatures: the two backward kernels each recompute S and dP)
FLASH_UNITS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_cost(cfg: dict, kind: str, batch: int, seq_len: int, elem_bytes: int = 2):
    """(FLOPs, bytes) one call of a flash-attention kernel needs for ``batch``
    sequences: matmul units above; bytes are each operand read and each
    result written once (q, k, v, o, do, dq or dk+dv), statistics ignored."""
    flops = batch * FLASH_UNITS[kind] * causal_attention_unit(cfg, seq_len)
    hd = head_dim(cfg)
    q = batch * seq_len * cfg["num_attention_heads"] * hd * elem_bytes
    kv = batch * seq_len * cfg["num_key_value_heads"] * hd * elem_bytes
    nbytes = {"fwd": 2 * q + 2 * kv,          # q,k,v in; o out
              "dq": 3 * q + 2 * kv + q,       # q,k,v,o,do in; dq out
              "dkv": 3 * q + 2 * kv + 2 * kv  # q,k,v,o,do in; dk,dv out
              }[kind]
    return flops, float(nbytes)


def kv_bytes_per_token(cfg: dict, elem_bytes: int = 2) -> int:
    return cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"] * head_dim(cfg) * elem_bytes


def decode_step_bytes(cfg: dict, live_kv_tokens: float, weight_bytes: int = 2, kv_elem_bytes: int = 2) -> float:
    """Bytes one decode token-step must read: every matmul weight once
    (blocks + head; the embedding gives one row a slot) and the keys and
    values of the tokens that are live."""
    return matmul_params(cfg) * weight_bytes + live_kv_tokens * kv_bytes_per_token(cfg, kv_elem_bytes)


def serve_flops(cfg: dict, prefill_tokens: list, decode_positions: list) -> float:
    """Required FLOPs of serving: ``prefill_tokens`` is a list of
    (tokens_computed, first_position) per request, ``decode_positions`` the
    context length at each decoded token. Blocks 2 N_blocks per token; the
    head only where a token is sampled; attention 4 d_model ctx per layer."""
    n_blocks = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    n_head = head_params(cfg)
    attn_per_ctx = 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * cfg["num_hidden_layers"]
    total = 0.0
    for n_tok, start in prefill_tokens:
        total += 2.0 * n_blocks * n_tok + 2.0 * n_head
        total += attn_per_ctx * (n_tok * start + n_tok * (n_tok + 1) / 2.0)
    for ctx in decode_positions:
        total += 2.0 * (n_blocks + n_head) + attn_per_ctx * ctx
    return total

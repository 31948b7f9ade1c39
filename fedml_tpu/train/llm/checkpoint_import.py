"""HF llama-family checkpoint <-> TransformerLM pytree conversion.

Reference: the FedLLM path loads pretrained Llama-2/Pythia checkpoints by
name (``train/llm/configurations.py:141`` ``ModelArguments.model_name_or_path``,
``hf_trainer.py:28``, ``python/spotlight_prj/fedllm/README.md``). Here the
import is a pure tensor-name/layout mapping from the HF llama serialization
to the TPU-native flax pytree — no torch, no network.

Name map (HF -> pytree path, kernels transposed [out,in] -> [in,out]):

    model.embed_tokens.weight                      embed/embedding        (no T)
    model.layers.{i}.self_attn.{q,k,v}_proj.weight layer_{i}/attn/*_proj/kernel  (T + rope perm for q,k)
    model.layers.{i}.self_attn.o_proj.weight       layer_{i}/attn/o_proj/kernel  (T)
    model.layers.{i}.mlp.{gate,up,down}_proj.weight layer_{i}/mlp/*_proj/kernel  (T)
    model.layers.{i}.input_layernorm.weight        layer_{i}/attn_norm/scale
    model.layers.{i}.post_attention_layernorm.weight layer_{i}/mlp_norm/scale
    model.norm.weight                              final_norm/scale
    lm_head.weight                                 lm_head/kernel         (T)

RoPE convention: HF llama stores q/k projections for the rotate_half
convention (pair = (j, j+d/2)); models/transformer.py uses the interleaved
convention (pair = (2j, 2j+1)). ``_rope_perm`` reorders each head's output
rows so the two produce identical attention — the same permutation HF's own
Meta->HF conversion script applies, inverted.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import numpy as np

from ...models.transformer import TransformerConfig, hybrid_pattern
from .safetensors_io import load_checkpoint_tensors, save_safetensors


def _rope_perm(n_heads: int, head_dim: int, inverse: bool = False) -> np.ndarray:
    """Row permutation mapping rotate_half head layout -> interleaved."""
    half = head_dim // 2
    perm_one = np.empty(head_dim, dtype=np.int64)
    for j in range(half):
        perm_one[2 * j] = j          # interleaved even slot <- first half
        perm_one[2 * j + 1] = j + half  # odd slot <- second half
    if inverse:
        inv = np.empty_like(perm_one)
        inv[perm_one] = np.arange(head_dim)
        perm_one = inv
    return np.concatenate([perm_one + h * head_dim for h in range(n_heads)])


def config_from_hf(model_dir: str, **overrides: Any) -> TransformerConfig:
    """Build a TransformerConfig from an HF config.json."""
    with open(os.path.join(model_dir, "config.json")) as f:
        return config_from_hf_keys(json.load(f), **overrides)


def config_from_hf_keys(hf: Dict[str, Any], **overrides: Any) -> TransformerConfig:
    """The same from the published keys themselves. Llama-family keys map as
    they always did. A file with the Jamba family's keys (``attn_layer_period``
    / ``attn_layer_offset`` and ``mamba_*``) also gives the layer pattern
    (attention where ``i % period == offset``, a Mamba mixer elsewhere), the
    mixer's sizes, no rotary positions, the norm's epsilon and a tied head.
    Its sparse variants are refused by name: the routed layer exists
    (``models/moe.RoutedMoE``), expert layers inside a hybrid pattern are not
    wired. A file with the latent-attention keys of the DeepSeek-V3 / openPangu
    family (``kv_lora_rank``, ``q_lora_rank``, ``qk_*_head_dim``, ``v_head_dim``)
    gives layers of kind ``"mla"`` with those sizes, ``first_k_dense_replace``
    dense layers before routed ones (``n_routed_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``, ``n_shared_experts``,
    ``routed_scaling_factor``, ``norm_topk_prob``), the sandwich norms and the
    norm's epsilon. The SHARE of a deployment rides the same file:
    ``n_routed_experts`` counts the experts HELD, ``router_width`` the
    published count the router keeps (absent: all are held) and ``expert_rank``
    which rank's experts these are. ``num_nextn_predict_layers`` must be 0: the
    prediction module is not loaded."""
    base = dict(
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        d_ff=hf["intermediate_size"],
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
    )
    if "attn_layer_period" in hf:
        if int(hf.get("num_experts", 1)) > 1:
            raise ValueError(
                f"num_experts={hf['num_experts']} (top-{hf.get('num_experts_per_tok')}): the "
                "routed layer (models/moe.RoutedMoE) exists, but expert layers inside a hybrid "
                "pattern are not wired")
        base.update(
            layer_pattern=hybrid_pattern(hf["num_hidden_layers"], hf["attn_layer_period"],
                                         hf["attn_layer_offset"]),
            use_rope=False,
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            mamba_d_state=hf["mamba_d_state"], mamba_d_conv=hf["mamba_d_conv"],
            mamba_expand=hf["mamba_expand"], mamba_dt_rank=hf["mamba_dt_rank"],
        )
    if "kv_lora_rank" in hf:
        if int(hf.get("num_nextn_predict_layers", 0)) != 0:
            raise ValueError(
                f"num_nextn_predict_layers={hf['num_nextn_predict_layers']}: the multi-token-prediction "
                "module is not loaded (a step yields one token a slot); set it to 0")
        held = int(hf.get("n_routed_experts", 0))
        base.update(
            layer_pattern=("mla",) * hf["num_hidden_layers"],
            q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"], qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            sandwich_norm=bool(hf.get("sandwich_norm", False)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            first_k_dense_replace=int(hf.get("first_k_dense_replace", 0)),
            moe_routed_experts=int(hf.get("router_width", held)), moe_held_experts=held,
            moe_rank=int(hf.get("expert_rank", 0)),
            moe_top_k=int(hf.get("num_experts_per_tok", 1)),
            moe_d_ff=int(hf.get("moe_intermediate_size", 0)),
            moe_shared_experts=int(hf.get("n_shared_experts", 0)),
            moe_routed_scaling=float(hf.get("routed_scaling_factor", 1.0)),
            moe_norm_topk=bool(hf.get("norm_topk_prob", True)),
        )
    if "layer_types" in hf and "sliding_window" in hf:
        kinds = {"sliding_attention": "window", "full_attention": "full"}
        if set(hf["layer_types"]) - set(kinds) or len(hf["layer_types"]) != hf["num_hidden_layers"]:
            raise ValueError(f"layer_types must name one of {sorted(kinds)} for each of "
                             f"{hf['num_hidden_layers']} layers, got {hf['layer_types']!r}")
        if hf.get("score_func", "sigmoid") != "sigmoid" or int(hf.get("n_group", 1)) != 1 \
                or int(hf.get("topk_group", 1)) != 1:
            raise ValueError(f"score_func={hf.get('score_func')!r}, n_group={hf.get('n_group')}, topk_group="
                             f"{hf.get('topk_group')}: the routed layer scores with a sigmoid over one group")
        held = int(hf.get("num_experts", 0))
        base.update(
            attn_kinds=tuple(kinds[t] for t in hf["layer_types"]),
            sliding_window=int(hf["sliding_window"]),
            use_rope=False,  # the full layers'; a window layer always rotates
            head_dim=int(hf.get("head_dim", 0)),
            qk_norm=True, attn_gate=True, sandwich_norm=True,
            embed_scale=float(hf["hidden_size"]) ** 0.5 if hf.get("mup_enabled") else 1.0,
            norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
            tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
            first_k_dense_replace=int(hf.get("num_dense_layers", 0)),
            moe_routed_experts=int(hf.get("router_width", held)), moe_held_experts=held,
            moe_rank=int(hf.get("expert_rank", 0)),
            moe_top_k=int(hf.get("num_experts_per_tok", 1)),
            moe_d_ff=int(hf.get("moe_intermediate_size", 0)),
            moe_shared_experts=int(hf.get("num_shared_experts", 0)),
            moe_routed_scaling=float(hf.get("route_scale", 1.0)),
            moe_norm_topk=bool(hf.get("route_norm", True)),
            moe_select_bias=held > 0,
        )
    base.update(overrides)
    return TransformerConfig(**base)


def import_hf_checkpoint(
    model_dir: str, cfg: Optional[TransformerConfig] = None, dtype: Any = np.float32
) -> Dict[str, Any]:
    """Load an HF llama safetensors checkpoint into the TransformerLM param
    pytree. Returns the {'embed': ..., 'layer_i': ..., ...} params dict."""
    cfg = cfg or config_from_hf(model_dir)
    raw = load_checkpoint_tensors(model_dir)

    def get(name: str) -> np.ndarray:
        if name not in raw:
            raise KeyError(f"checkpoint missing tensor {name!r} (have {len(raw)} tensors)")
        return np.asarray(raw[name], dtype=np.float32).astype(dtype)

    q_perm = _rope_perm(cfg.n_heads, cfg.head_dim)
    kv_perm = _rope_perm(cfg.n_kv_heads, cfg.head_dim)

    params: Dict[str, Any] = {
        "embed": {"embedding": get("model.embed_tokens.weight")},
        "final_norm": {"scale": get("model.norm.weight")},
    }
    if "lm_head.weight" in raw:
        params["lm_head"] = {"kernel": get("lm_head.weight").T}
    else:  # tied embeddings (e.g. tinyllama variants)
        params["lm_head"] = {"kernel": get("model.embed_tokens.weight").T}
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        params[f"layer_{i}"] = {
            "attn": {
                "q_proj": {"kernel": get(p + "self_attn.q_proj.weight")[q_perm].T},
                "k_proj": {"kernel": get(p + "self_attn.k_proj.weight")[kv_perm].T},
                "v_proj": {"kernel": get(p + "self_attn.v_proj.weight").T},
                "o_proj": {"kernel": get(p + "self_attn.o_proj.weight").T},
            },
            "mlp": {
                "gate_proj": {"kernel": get(p + "mlp.gate_proj.weight").T},
                "up_proj": {"kernel": get(p + "mlp.up_proj.weight").T},
                "down_proj": {"kernel": get(p + "mlp.down_proj.weight").T},
            },
            "attn_norm": {"scale": get(p + "input_layernorm.weight")},
            "mlp_norm": {"scale": get(p + "post_attention_layernorm.weight")},
        }
    return params


def export_hf_checkpoint(params: Dict[str, Any], cfg: TransformerConfig, model_dir: str) -> None:
    """Write the param pytree back to HF llama layout (single shard).

    Exact inverse of import_hf_checkpoint (LoRA adapters, if present, must be
    merged into kernels first — models/lora.py)."""
    os.makedirs(model_dir, exist_ok=True)
    q_inv = _rope_perm(cfg.n_heads, cfg.head_dim, inverse=True)
    kv_inv = _rope_perm(cfg.n_kv_heads, cfg.head_dim, inverse=True)

    def np32(x) -> np.ndarray:
        return np.asarray(x, dtype=np.float32)

    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np32(params["embed"]["embedding"]),
        "model.norm.weight": np32(params["final_norm"]["scale"]),
        "lm_head.weight": np32(params["lm_head"]["kernel"]).T,
    }
    for i in range(cfg.n_layers):
        lay = params[f"layer_{i}"]
        p = f"model.layers.{i}."
        out[p + "self_attn.q_proj.weight"] = np32(lay["attn"]["q_proj"]["kernel"]).T[q_inv]
        out[p + "self_attn.k_proj.weight"] = np32(lay["attn"]["k_proj"]["kernel"]).T[kv_inv]
        out[p + "self_attn.v_proj.weight"] = np32(lay["attn"]["v_proj"]["kernel"]).T
        out[p + "self_attn.o_proj.weight"] = np32(lay["attn"]["o_proj"]["kernel"]).T
        out[p + "mlp.gate_proj.weight"] = np32(lay["mlp"]["gate_proj"]["kernel"]).T
        out[p + "mlp.up_proj.weight"] = np32(lay["mlp"]["up_proj"]["kernel"]).T
        out[p + "mlp.down_proj.weight"] = np32(lay["mlp"]["down_proj"]["kernel"]).T
        out[p + "input_layernorm.weight"] = np32(lay["attn_norm"]["scale"])
        out[p + "post_attention_layernorm.weight"] = np32(lay["mlp_norm"]["scale"])
    save_safetensors(out, os.path.join(model_dir, "model.safetensors"), metadata={"format": "pt"})
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(
            {
                "architectures": ["LlamaForCausalLM"],
                "model_type": "llama",
                "vocab_size": cfg.vocab_size,
                "hidden_size": cfg.d_model,
                "num_hidden_layers": cfg.n_layers,
                "num_attention_heads": cfg.n_heads,
                "num_key_value_heads": cfg.n_kv_heads,
                "intermediate_size": cfg.d_ff,
                "max_position_embeddings": cfg.max_seq_len,
                "rope_theta": cfg.rope_theta,
                "rms_norm_eps": 1e-5,
            },
            f,
            indent=2,
        )

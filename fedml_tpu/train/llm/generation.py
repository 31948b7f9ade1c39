"""Autoregressive generation with a KV cache: the plain reference of the
serving engine (serving/continuous_batching.py, which also admits requests
through ``_prefill_fn``) and ``LLMPredictor``'s engine-less mode.

Reference analogue: BASELINE config 5 serves Llama-2 inference via
docker/Triton (``device_model_deployment.py:68``); here decode is
TPU-native — the transformer runs in ``decode=True`` mode (flax "cache"
collection holding [B, max_seq_len, kv, hd] key/value buffers written at a
running index), prefill is one batched pass over the prompt, and the
per-token loop is a single jitted ``lax.scan`` carrying (cache, token,
position, rng). Compilation is split so serving stays warm: prefill
compiles once per 16-token PROMPT-LENGTH BUCKET (right-padding + a runtime
true length — see ``_rewind_cache`` for the exactness argument), the
token-loop executable is shared across ALL prompt lengths (start position
is a runtime value) and bucketed over max_new_tokens; both caches are
LRU-bounded.

Correctness keystone (tests/test_generation.py): stepped KV-cache logits
equal the full non-cached forward bit-for-bit positions.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...core.telemetry import track_compiles
from ...models.mamba import pack_state, unpack_state
from ...models.moe import ROUTING_STATS, routing_stats
from ...models.transformer import TransformerConfig, TransformerLM


def decode_model(cfg: TransformerConfig) -> TransformerLM:
    """The decode-mode twin of a training config (same params)."""
    return TransformerLM(dataclasses.replace(cfg, decode=True, remat=False, attention_impl="xla"))


# Two compile units, LRU-bounded:
#   prefill — keyed by (cfg, B, 16-token length bucket): one forward pass;
#   decode scan — keyed by (cfg, B, max_new bucket, greedy?, eos?): the
#     expensive unit, SHARED across all prompt lengths because the cache
#     shape is static [B, max_seq_len, ...] and the start position is a
#     runtime value. Temperature is a runtime scalar (only greedy-vs-
#     sampled changes the program). max_new is bucketed to multiples of 16
#     and the output sliced, so sweeping max_new doesn't grow the cache.
_MAX_CACHED = 32
_COMPILED: "dict" = {}
_CACHE_LOCK = __import__("threading").Lock()


def _lru_get(key_, build):
    # serving runs under ThreadingHTTPServer: eviction/refresh pops race
    # without the lock (build() itself runs outside it — compiling under a
    # lock would serialize unrelated requests)
    with _CACHE_LOCK:
        fn = _COMPILED.get(key_)
        if fn is not None:
            _COMPILED[key_] = _COMPILED.pop(key_)  # refresh LRU order
            return fn
    fn = build()
    with _CACHE_LOCK:
        _COMPILED.setdefault(key_, fn)
        while len(_COMPILED) > _MAX_CACHED:
            _COMPILED.pop(next(iter(_COMPILED)))
        return _COMPILED.get(key_, fn)


def _sample(logits, key, temperature):
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(key, logits / jnp.maximum(temperature, 1e-6), axis=-1)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _rewind_cache(cache, true_len):
    """Set every layer's KV write index to the TRUE prompt length. Prompts
    are right-padded to a bucket before prefill; the padded slots' garbage
    keys/values sit at positions >= true_len, and with the index rewound
    each of those slots is OVERWRITTEN by a real decoded token before any
    query position can attend to it — so bucketed prefill is exact."""

    def fix(path, x):
        if getattr(path[-1], "key", None) == "idx":
            return jnp.full_like(x, true_len)
        return x

    return jax.tree_util.tree_map_with_path(fix, cache)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _leaf_at(tree, path):
    """The leaf of ``tree`` at ``path`` (a ``tree_map_with_path`` key path)."""
    for p in path:
        tree = tree[getattr(p, "key", p)]
    return tree


def _mutable(cfg: TransformerConfig) -> list:
    """The collections a serving pass takes back from the model."""
    return ["cache", ROUTING_STATS] if cfg.routed_layers else ["cache"]


def _routing(cfg: TransformerConfig, state, n_live) -> tuple:
    """``(routing_stats,)`` of a pass of a model with routed layers, else ``()``."""
    return (routing_stats(state[ROUTING_STATS], n_live),) if cfg.routed_layers else ()


def _prefill_fn(cfg: TransformerConfig, B: int, P_bucket: int):
    """Compiled per PROMPT-LENGTH BUCKET (multiples of 16), not per exact
    length: serving traffic with varied prompt lengths shares executables
    (a fresh compile per length was the old behavior's latency cliff).
    ``true_len`` is a runtime scalar; so is ``snap_len``, the position at
    which a recurrent layer also keeps its state for the prefix cache
    (``models/mamba.py``; attention layers read neither). The cache handed on
    is PACKED (``models/mamba.pack_state``: a dense model's is unchanged). A
    model with routed layers also hands on the pass's routing, packed
    (``models/moe.routing_stats``): a third result, for those models only."""

    def build():
        model = decode_model(cfg)

        def run(params, prompt_padded, true_len, snap_len=None):
            positions = jnp.broadcast_to(jnp.arange(P_bucket), (B, P_bucket))
            logits, state = model.apply(
                {"params": params}, prompt_padded, positions=positions, mutable=_mutable(cfg),
                seq_lens=jnp.broadcast_to(true_len, (B,)),
                snap_lens=None if snap_len is None else jnp.broadcast_to(snap_len, (B,)),
                logit_rows=jnp.broadcast_to(true_len - 1, (B,)),  # the head over B tokens, not B x P
            )
            first = logits[:, 0]
            out = pack_state(cfg, _rewind_cache(state["cache"], true_len)), first
            return out + _routing(cfg, state, B * true_len)

        # compile observability: counter("jax.compiles.prefill") advances per
        # TRACE, not per call — the serving compile-count guards read it
        return jax.jit(track_compiles(run, name="prefill"))

    return _lru_get(("prefill", cfg, B, P_bucket), build)


def _decode_fn(cfg: TransformerConfig, B: int, max_new: int, sampled: bool,
               eos_ids: Optional[Tuple[int, ...]]):
    def build():
        model = decode_model(cfg)

        def is_eos(tok):
            return jnp.isin(tok, jnp.asarray(eos_ids))

        def run(params, cache, first_logits, pos0, key, temperature):
            cache = unpack_state(cfg, cache)  # as _prefill_fn hands it on
            key, sub = jax.random.split(key)
            temp = temperature if sampled else jnp.float32(0.0)
            first = _sample(first_logits, sub, temp)

            def step(carry, _):
                cache, tok, pos, key, done = carry
                key, sub = jax.random.split(key)
                logits, state = model.apply(
                    {"params": params, "cache": cache},
                    tok[:, None],
                    positions=pos[:, None],
                    mutable=["cache"],
                )
                nxt = _sample(logits[:, -1], sub, temp)
                if eos_ids is not None:
                    nxt = jnp.where(done, eos_ids[0], nxt)
                    done = jnp.logical_or(done, is_eos(nxt))
                return (state["cache"], nxt, pos + 1, key, done), tok

            done0 = jnp.zeros((B,), bool) if eos_ids is None else is_eos(first)
            (_, last, _, _, _), toks = jax.lax.scan(
                step, (cache, first, pos0, key, done0), None, length=max_new - 1
            )
            return jnp.concatenate([toks.swapaxes(0, 1), last[:, None]], axis=1)

        # "jax.compiles.decode_scan" is the int8 regression guard's witness:
        # a per-call (or per-token) retrace of the scan shows up here (the
        # r05 int8 collapse's suspected mechanism), and bench.py --stage
        # decode_int8 refuses to publish when the count exceeds the key count
        return jax.jit(track_compiles(run, name="decode_scan"))

    return _lru_get(("decode", cfg, B, max_new, sampled, eos_ids), build)


def generate(
    params,
    cfg: TransformerConfig,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    *,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Generate [B, max_new_tokens] continuations of ``prompt`` [B, P].

    temperature 0 = greedy; otherwise categorical sampling at the given
    temperature (a runtime scalar — no recompile per value). ``eos_id``
    may be one id or a sequence (llama-3 instruct models stop on
    <|eot_id|> while config.json lists several); positions after any EOS
    are filled (the scan still runs to full length — static shapes)."""
    B, P = prompt.shape
    eos_ids: Optional[Tuple[int, ...]] = None
    if eos_id is not None:
        eos_ids = tuple(eos_id) if isinstance(eos_id, (list, tuple)) else (int(eos_id),)
    if P < 1:
        raise ValueError("prompt must contain at least one token")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if P + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt {P} + new {max_new_tokens} exceeds max_seq_len {cfg.max_seq_len}"
        )
    key = key if key is not None else jax.random.PRNGKey(0)
    # bucket the scan length so distinct max_new values share an executable
    # (the validation above guarantees the min is still >= max_new_tokens)
    bucket = min(-(-max_new_tokens // 16) * 16, cfg.max_seq_len - P)
    # bucket the PROMPT length too (right-pad + runtime true length): all
    # lengths in a 16-bucket share one prefill executable; see _rewind_cache
    # for why the padding is exact
    P_b = min(-(-P // 16) * 16, cfg.max_seq_len)
    prompt_padded = jnp.pad(prompt, ((0, 0), (0, P_b - P))) if P_b != P else prompt
    cache, first_logits = _prefill_fn(cfg, B, P_b)(
        params, prompt_padded, jnp.int32(P)
    )[:2]  # a model with routed layers hands on its routing too
    out = _decode_fn(cfg, B, bucket, temperature > 0.0, eos_ids)(
        params, cache, first_logits, jnp.full((B,), P, jnp.int32), key,
        jnp.float32(temperature),
    )
    return out[:, :max_new_tokens]


def generate_text(
    params,
    cfg: TransformerConfig,
    tokenizer,
    prompt_text: str,
    max_new_tokens: int = 64,
    **kw,
) -> str:
    """Tokenizer-roundtrip convenience used by the serving predictor."""
    ids = jnp.asarray([tokenizer.encode(prompt_text)], jnp.int32)
    out = generate(params, cfg, ids, max_new_tokens, **kw)
    return tokenizer.decode([int(t) for t in out[0]])

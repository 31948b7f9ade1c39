"""User-facing predictor contract for model serving.

Reference: python/fedml/serving/fedml_predictor.py:4-22 — subclasses must
implement predict() (or async_predict); ready() gates the readiness probe.
Includes a JaxPredictor convenience that jits a pure forward function once
and serves it (the TPU-native hot path: one compiled XLA executable per
endpoint, inputs batched to fixed shapes to avoid recompiles).
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Optional

from ..core import telemetry as tel
from ..core.telemetry import trace_context


class FedMLPredictor(abc.ABC):
    def __init__(self):
        if type(self).predict is FedMLPredictor.predict and type(self).async_predict is FedMLPredictor.async_predict:
            raise NotImplementedError("At least one of the predict methods must be implemented.")

    def predict(self, *args, **kwargs):
        raise NotImplementedError

    async def async_predict(self, *args, **kwargs):
        raise NotImplementedError

    def ready(self) -> bool:
        return True


class JaxPredictor(FedMLPredictor):
    """Serve a jitted forward fn over JSON: {"inputs": [[...]]} -> {"outputs": ...}."""

    def __init__(self, forward_fn: Callable, params: Any, preprocess: Optional[Callable] = None,
                 postprocess: Optional[Callable] = None):
        import jax

        self._fn = jax.jit(forward_fn)
        self._params = params
        self._pre = preprocess
        self._post = postprocess
        self._ready = False

    def warmup(self, example: Any) -> None:
        import jax

        jax.block_until_ready(self._fn(self._params, example))
        self._ready = True

    def ready(self) -> bool:
        return self._ready

    def predict(self, request: dict, *args, **kwargs):
        import jax.numpy as jnp
        import numpy as np

        x = request["inputs"]
        if self._pre is not None:
            x = self._pre(x)
        out = self._fn(self._params, jnp.asarray(np.asarray(x, dtype=np.float32)))
        if self._post is not None:
            return self._post(out)
        return {"outputs": np.asarray(out).tolist()}


class LLMPredictor(FedMLPredictor):
    """LLM text-generation endpoint (BASELINE config 5 shape): KV-cache
    decode. ``paged=True`` serves through the continuous-batching engine
    (serving/continuous_batching.py); without it every request is one
    ``generation.generate_text`` call, the plain reference. Request:
    {"prompt": str, "max_new_tokens": int?, "temperature": float?} ->
    {"text": str} (the engine adds "token_ids": [int] and "timing":
    {request_id, queue_wait_s, ttft_s, tpot_s}, its own readings for this
    request).

    Build from a checkpoint dir (HF llama safetensors + tokenizer.json) or
    pass (params, cfg, tokenizer) directly."""

    def __init__(self, params, cfg, tokenizer, default_max_new_tokens: int = 64,
                 eos_id: "int | tuple | None" = None,
                 num_slots: Optional[int] = None,
                 decode_chunk: Optional[int] = None,
                 paged: bool = False,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 admission=None,
                 state_snapshots: int = 8):
        import os

        self._params = params
        self._cfg = cfg
        self._tok = tokenizer
        self._max_new = int(default_max_new_tokens)
        # stop token: explicit id wins (from_checkpoint reads config.json's
        # eos_token_id); else fall back to a '</s>' special if defined
        self._eos_id = eos_id if eos_id is not None else getattr(
            tokenizer, "special_tokens", {}
        ).get("</s>")
        self._ready = True  # flips False->True around warmup() when used
        # pool role under disaggregated serving (DisaggregatedReplicaSet
        # children get FEDML_SERVE_ROLE=prefill|decode): prefill replicas
        # exist to absorb cold long prompts + cache warming
        self.role = os.environ.get("FEDML_SERVE_ROLE", "mixed")
        # deployment capacity: explicit args win; the env seam sizes
        # subprocess replicas without code changes
        self.engine = None
        if paged:
            from .continuous_batching import PagedContinuousBatchingEngine

            slots = int(num_slots if num_slots is not None
                        else os.environ.get("FEDML_SERVE_SLOTS", "8"))
            chunk = int(decode_chunk if decode_chunk is not None
                        else os.environ.get("FEDML_SERVE_CHUNK", "8"))
            max_queue = int(os.environ.get("FEDML_SERVE_MAX_QUEUE", "4096"))
            ps = int(page_size if page_size is not None
                     else os.environ.get("FEDML_SERVE_PAGE_SIZE", "16"))
            np_env = os.environ.get("FEDML_SERVE_KV_PAGES")
            pages = (int(num_pages) if num_pages is not None
                     else int(np_env) if np_env else None)
            self.engine = PagedContinuousBatchingEngine(
                params, cfg, num_slots=slots, chunk=chunk,
                page_size=ps, num_pages=pages, max_queue=max_queue,
                admission=admission,
                state_snapshots=int(state_snapshots))

    @classmethod
    def from_checkpoint(cls, path: str, quantize: str = "none", **kw) -> "LLMPredictor":
        """``quantize="int8"`` serves the checkpoint with weight-only int8
        kernels (serving/quant.py): halved decode HBM traffic, activations
        and KV cache unchanged."""
        import json
        import os

        from ..train.llm.checkpoint_import import config_from_hf, import_hf_checkpoint
        from ..train.llm.data import load_or_train_tokenizer

        if quantize not in ("none", "int8"):
            # validate BEFORE the (potentially multi-GB) checkpoint import
            raise ValueError(f"unknown quantize mode {quantize!r}")
        cfg = config_from_hf(path)
        params = import_hf_checkpoint(path, cfg)
        if quantize == "int8":
            from .quant import quantize_model_int8

            cfg, params = quantize_model_int8(cfg, params)
        tok = load_or_train_tokenizer(None, os.path.join(path, "tokenizer.json"))
        if "eos_id" not in kw:
            # config.json's eos_token_id is authoritative (token STRINGS
            # vary across llama generations; the id does not lie)
            with open(os.path.join(path, "config.json")) as f:
                eos = json.load(f).get("eos_token_id")
            if isinstance(eos, list) and eos:
                # llama-3 style multi-EOS: generation stops on ANY of them
                kw["eos_id"] = tuple(int(e) for e in eos)
            elif isinstance(eos, int):
                kw["eos_id"] = eos
        return cls(params, cfg, tok, **kw)

    def warmup(self, example_prompt: str = "warmup") -> None:
        """Compile the default request shape before readiness is reported
        (mirrors JaxPredictor.warmup: without this, the first real request
        pays the full prefill+scan compile and can exceed the gateway's
        timeout / trip health eviction)."""
        self._ready = False
        self.predict({"prompt": example_prompt})
        self._ready = True

    def ready(self) -> bool:
        return self._ready

    def predict(self, request: dict, *args, **kwargs):
        import jax

        from ..train.llm.generation import generate_text

        if self.engine is not None:
            rid = trace_context.request_id()
            prompt = str(request["prompt"])
            with tel.span("serving.predict.encode", request_id=rid, chars=len(prompt)):
                prompt_ids = self._tok.encode(prompt)
            tenant = str(request.get("tenant", "default"))
            if request.get("prefill_only"):
                # cache warming (prefill-pool traffic): one decoded token
                # forces the full prefill, and the engine registers
                # the prompt's chunks in its prefix cache on admit — later
                # requests sharing this prefix skip its compute + pages
                self.engine.generate(prompt_ids, 1, tenant=tenant)
                return {"warmed": True, "prompt_tokens": len(prompt_ids)}
            # this thread just parks on its future; the
            # engine's worker interleaves every in-flight request through
            # one always-running decode step (ThreadingHTTPServer gives a
            # thread per connection, so concurrency comes for free)
            handle = self.engine.submit(
                prompt_ids,
                int(request.get("max_new_tokens", self._max_new)),
                temperature=float(request.get("temperature", 0.0)),
                seed=int(request.get("seed", 0)),
                eos_id=self._eos_id,
                tenant=tenant,
                request_id=rid,
            )
            with tel.span("serving.predict.wait", request_id=rid,
                          prompt_tokens=len(prompt_ids)):
                toks = handle.result(timeout=600.0)
            ids = [int(t) for t in toks]
            with tel.span("serving.predict.decode_text", request_id=rid, tokens=len(ids)):
                text = self._tok.decode(ids)
            # token_ids ride along: the text alone cannot say how many tokens
            # were generated (decode drops ids outside the tokenizer's vocab);
            # timing is the engine's own (RequestHandle), so a client needs no
            # hook inside the replica to split its latency
            return {"text": text, "token_ids": ids,
                    "timing": {"request_id": rid,
                               "queue_wait_s": handle.queue_wait_s,
                               "ttft_s": handle.ttft_s,
                               "tpot_s": handle.tpot_s}}
        text = generate_text(
            self._params,
            self._cfg,
            self._tok,
            str(request["prompt"]),
            max_new_tokens=int(request.get("max_new_tokens", self._max_new)),
            temperature=float(request.get("temperature", 0.0)),
            key=jax.random.PRNGKey(int(request.get("seed", 0))),
            eos_id=self._eos_id,
        )
        return {"text": text}

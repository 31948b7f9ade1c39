"""Driver ``llm_serve_pangu``: ``llm_serve``'s open-loop chat client in front of
a model with latent attention and routed experts, of which this chip holds a
share (openPangu-Ultra-MoE-718B: one dense and four expert layers, 16 of 256
experts, an eighth of the vocabulary).

The client, the window, the sample and the verdict are ``llm_serve``'s, by
import (``Client``, ``Handles``, ``StatsSampler``, ``Served._window``,
``pick_sample``, ``reduce_trace``); ``measure`` is ``llm_serve_jamba``'s, which
parses a traced run's trace AFTER the last reply. What names the dense block
there is this file's own: the model's config comes from the configuration's
keys through the program's ``checkpoint_import.config_from_hf_keys`` (the share
with them), the weights from ``weights_pangu``, the reference from
``reference_pangu``, the required work from ``flops_pangu``. It exposes the same
``Served`` / ``run`` / ``check_sample`` that ``tools/sweep_rate.py`` and
``tools/readings.py`` drive.

Two things differ:

* warm-up sends every system-prompt length once more WHOLE, behind a prefix
  nobody shares: what a system-prompt request costs when its pages are gone
  (evicted, or, in the rate sweep, a system prompt of another seed), so that
  this prefill is compiled too.
* the window carries what the engine counted of the routing (the program's
  ``stats()``: tokens routed, (token, held expert) pairs computed, held experts
  hit, pairs by held expert), from which ``flops_pangu`` counts the experts'
  required work and the readers their rooflines.
"""

from __future__ import annotations

import os
import time

import numpy as np

import compare
import flops_pangu
import harness
import reference_pangu
import traffic
import weights_pangu

_HERE = os.path.dirname(os.path.abspath(__file__))
base = harness.load_module(os.path.join(_HERE, "llm_serve.py"))
jamba = harness.load_module(os.path.join(_HERE, "llm_serve_jamba.py"))

ENDPOINT_NAME = base.ENDPOINT_NAME
pick_sample, reduce_trace, send_one = base.pick_sample, base.reduce_trace, base.send_one
build_predictor = base.build_predictor  # LLMPredictor(paged=True): nothing but what the config carries
ROUTING_COUNTERS = ("moe_tokens_routed", "moe_local_picks", "moe_experts_hit")


def model_config(ctx):
    import jax.numpy as jnp

    from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys

    cfg = config_from_hf_keys(ctx.config, max_seq_len=ctx.workload["program"]["max_seq_len"],
                              dtype=jnp.bfloat16, remat=False, lora_rank=0)
    # a program from before these layers existed reads the keys it knows and builds a dense block:
    # that is not this cell, and it fails here, at once
    if getattr(cfg, "kv_lora_rank", 0) != ctx.config["kv_lora_rank"] or not getattr(cfg, "moe_routed_experts", 0):
        raise harness.HarnessError("the program cannot run this configuration: config_from_hf_keys gives no latent "
                                   "attention and no routed experts for its keys")
    return cfg


def param_shapes(cfg) -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerLM

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                          jax.random.PRNGKey(0))
    return weights_pangu.shapes_of(tree)


def make_gap_fn(cfg: dict, pad_to: int, max_rows: int, quant=None):
    """jitted (params, tokens[pad_to], rows[max_rows], served[max_rows]) ->
    (gap of the served token below the reference's best, the reference's
    best token) at each row."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, rows, served):
        lg = reference_pangu.logits_at(params, tokens, rows, cfg, quant)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(lg, axis=-1)

    return jax.jit(f)


def check_sample(ctx, params, sample, requests, quant=None) -> dict:
    """``llm_serve.check_sample`` against ``reference_pangu``: the widest and
    the mean gap of a served token below the reference's best; with ``quant``,
    the gaps of the token the lower precision puts first (the control)."""
    import jax.numpy as jnp

    chk = ctx.workload["check"]
    cfg = reference_pangu.norm_cfg(ctx.config)
    pad_to = int(chk["pad_to"])
    max_rows = max(int(v) for v in ctx.traffic["max_new_tokens"]["values"])
    ref_fn = make_gap_fn(cfg, pad_to, max_rows)
    low_fn = make_gap_fn(cfg, pad_to, max_rows, quant) if quant is not None else None
    widest, control_widest, n_tokens, differ = 0.0, 0.0, 0, 0
    total, control_total = 0.0, 0.0
    for rec in sample:
        prompt, served = requests[rec["index"]]["prompt"], rec["tokens"]
        n, P = len(served), len(prompt)
        seq = np.zeros((pad_to,), np.int32)
        seq[:P + n - 1] = (prompt + served[:-1])
        rows = np.minimum(P - 1 + np.arange(max_rows), P + n - 2).astype(np.int32)
        tok = np.asarray(served + [served[-1]] * (max_rows - n), np.int32)
        gaps, best = ref_fn(params, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(tok))
        gaps, best = np.asarray(gaps)[:n], np.asarray(best)[:n]
        widest = max(widest, float(gaps.max()))
        total += float(gaps.sum())
        differ += int((best != np.asarray(served)).sum())
        n_tokens += n
        if low_fn is not None:
            _, low_best = low_fn(params, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(tok))
            cg, _ = ref_fn(params, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(low_best))
            control_widest = max(control_widest, float(np.asarray(cg)[:n].max()))
            control_total += float(np.asarray(cg)[:n].sum())
    return {"widest_logit_gap": widest, "control_widest_gap": control_widest,
            "mean_logit_gap": total / max(n_tokens, 1), "control_mean_gap": control_total / max(n_tokens, 1),
            "tokens": n_tokens, "tokens_not_reference_best": differ, "requests": len(sample)}


class Served(base.Served):
    """The deployed endpoint with everything warmed (see the module's header
    for the extra pass); ``close`` is ``llm_serve``'s, ``measure``
    ``llm_serve_jamba``'s."""

    measure = jamba.Served.measure

    def __init__(self, ctx):  # noqa: D107 - replaces, does not extend: the parent's names the dense block
        import jax.numpy as jnp

        from fedml_tpu.serving.endpoint import EndpointManager

        self.ctx = ctx
        p, tr = ctx.workload["program"], ctx.traffic
        self.vocab = int(ctx.config["vocab_size"])
        self.timeout_s = float(p["client_timeout_s"])
        cfg = model_config(ctx)
        self.params = weights_pangu.make_params(param_shapes(cfg), ctx.seed, jnp.bfloat16)
        self.predictor = build_predictor(ctx, self.params, cfg)
        self.engine = self.predictor.engine
        self.handles = base.Handles(self.engine)
        if ctx.trace:
            base.add_trace_spans(self.engine)
        self.mgr = EndpointManager()
        self.ep = self.mgr.deploy(ENDPOINT_NAME, lambda: self.predictor)
        try:
            warm = traffic.warmup_prompts(tr, ctx.seed, self.vocab)
            sys_len = int(tr.get("system_prompt_tokens", 0))
            if sys_len and tr.get("system_prompt_share", 0.0) > 0.0:
                rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, 6])
                lens = sorted({int(v) for v in tr["user_tokens"]["values"]})
                warm += [rng.integers(1, self.vocab, sys_len + n).tolist() for n in lens]
            for prompt in warm:
                reply = send_one(self.ep, prompt, p["decode_chunk"] + 1, self.timeout_s)
                if len(reply["token_ids"]) != p["decode_chunk"] + 1:
                    raise harness.HarnessError(f"warm-up reply has {len(reply['token_ids'])} tokens")
        except BaseException:
            self.close()
            raise
        ctx.log(f"warmed {len(warm)} prompt shapes; compile+load {ctx.compile_log.seconds():.1f} s "
                f"in {len(ctx.compile_log.events)} programs")

    def _window(self, requests, records, samples, stats0, stats1, t_start, t_close, t_end) -> dict:
        w = super()._window(requests, records, samples, stats0, stats1, t_start, t_close, t_end)
        ctx, p, tr = self.ctx, self.ctx.workload["program"], self.ctx.traffic
        page = int(p["page_size"])
        sys_len = int(tr.get("system_prompt_tokens", 0)) // page * page
        prefill, decode = [], []
        for r in w["ok_records"]:
            req = requests[r["index"]]
            P = len(req["prompt"])
            start = sys_len if req["system"] else 0
            prefill.append((P - start, start))
            decode += [P + j for j in range(1, len(r["tokens"]))]
        w["prefill_passes"] = prefill
        if "moe_local_picks" in stats1:  # a program that counts its routing
            for k in ROUTING_COUNTERS:
                w[k] = stats1[k] - stats0[k]
            w["moe_expert_load"] = [b - a for a, b in zip(stats0["moe_expert_load"], stats1["moe_expert_load"])]
            w["flops"] = flops_pangu.serve_flops(ctx.config, prefill, decode, w["moe_local_picks"])
        else:
            w.pop("flops", None)  # llm_serve's count is the dense block's
        return w


def run(ctx) -> dict:
    served = Served(ctx)
    try:
        requests = traffic.open_loop_requests(ctx.traffic, ctx.seed, ctx.seconds, served.vocab)["requests"]
        traced = {} if ctx.trace else None
        window = served.measure(requests, ctx.seconds, traced)
        peak = harness.memory_peak_bytes(ctx.cell.chips)
    finally:
        served.close()
    if window["compiles"]:
        ctx.log(f"COMPILED IN THE WINDOW: {[(c[1], round(c[2], 3)) for c in window['compiles']]}")
    ctx.log(f"window: {window['ok']}/{len(requests)} requests ok, {window['out_tokens']} tokens in "
            f"{window['seconds']:.2f} s, compiles in window {len(window['compiles'])}, "
            f"page leaks {window['page_leaks']}, routing "
            f"{ {k: window.get(k) for k in ROUTING_COUNTERS + ('moe_expert_load',)} }")
    trace = None
    if traced and "raw" in traced:
        trace = reduce_trace(ctx, traced["raw"])
        ctx.log(f"traced {trace['window_s']:.2f} s: busy {trace['busy_s']:.2f} s, {trace['chunks']} decode chunks")

    # ---- the plain reference over a sample, the program's state freed -------------
    t = time.perf_counter()
    sample = pick_sample(window["ok_records"], requests, int(ctx.workload["check"]["sample_requests"]), ctx.seed)
    verdict = compare.Verdict()
    if sample:
        chk = check_sample(ctx, served.params, sample, requests)
        ctx.log(f"reference: {chk} in {time.perf_counter() - t:.1f} s")
        for name in ("widest_logit_gap", "mean_logit_gap"):
            verdict.add(name, chk[name], ctx.workload["limits"].get(name))
    verdict.add("failed_requests", window["failed"], 0)
    verdict.add("page_leaks", window["page_leaks"], 0)
    return {
        "attempted": len(requests), "failed": window["failed"], "verdict": verdict,
        "memory_peak_bytes": peak,
        "end_to_end": {
            "serve_latency_p95_ms": base._p95_ms(window["latencies_s"]),
            "serve_out_tokens_per_s": window["out_tokens"] / max(window["seconds"], 1e-9),
            "setup_s": window["t_start"] - ctx.t_process_start},
        "window": window, "trace": trace, "requests": requests, "sample": sample, "params": served.params,
    }

"""Driver ``llm_serve_trinity``: ``llm_serve``'s open-loop chat client in front of
a model whose attention layers are of two kinds, a window of keys or the whole
prefix, with routed experts behind them (Trinity-Mini: one dense and four expert
layers, every one of the 128 experts and the whole vocabulary on the chip).

The client, the trace's reduction and the verdict are ``llm_serve``'s, by import
(``Client``, ``Handles``, ``Served._window``, ``reduce_trace``). What names the
model is this file's own: the model's config comes from the configuration's keys
through the program's ``checkpoint_import.config_from_hf_keys``, the weights from
``weights_trinity``, the reference from ``reference_trinity``, the required work
from ``flops_trinity``. It exposes the same ``Served`` / ``run`` /
``check_sample`` that ``tools/sweep_rate.py`` and ``tools/readings.py`` drive.

What differs from ``llm_serve_pangu``:

* the sample. Prompts run from 512 to 16,640 tokens, so the sample is drawn by
  length class: the longest finished request (more than 4 windows), one between
  1 and 4 windows, one shorter than the window, with and without the system
  prompt, the rest from the seed. Each is compared at the smallest of the
  ``check.pad_to`` lengths that holds it, prefill and every decoded position,
  logits and not tokens.
* the window carries what the engine counted of its two page groups
  (``stats()``: the window group's pages live beside what the live rows would
  hold with no horizon, sampled through the window; pages released behind a
  horizon; admissions deferred a group) beside the routing's counters.
* ``measure`` parses a traced run's trace AFTER the last reply, as
  ``llm_serve_jamba``'s does.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

import compare
import flops_trinity
import harness
import reference_trinity
import traffic
import weights_trinity

_HERE = os.path.dirname(os.path.abspath(__file__))
base = harness.load_module(os.path.join(_HERE, "llm_serve.py"))

ENDPOINT_NAME = base.ENDPOINT_NAME
reduce_trace, send_one = base.reduce_trace, base.send_one
build_predictor = base.build_predictor  # LLMPredictor(paged=True): nothing but what the config carries
ROUTING_COUNTERS = ("moe_tokens_routed", "moe_local_picks", "moe_experts_hit")
GROUP_COUNTERS = ("kv_window_pages_released", "kv_admit_deferred_full", "kv_admit_deferred_window")


def model_config(ctx):
    import jax.numpy as jnp

    from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys

    cfg = config_from_hf_keys(ctx.config, max_seq_len=ctx.workload["program"]["max_seq_len"],
                              dtype=jnp.bfloat16, remat=False, lora_rank=0)
    # a program from before these layers existed reads the keys it knows and builds a dense block with
    # heads of hidden_size / heads: that is not this cell, and it fails here, at once
    if (not getattr(cfg, "attn_kinds", ()) or cfg.head_dim != ctx.config["head_dim"]
            or not getattr(cfg, "moe_routed_experts", 0)):
        raise harness.HarnessError("the program cannot run this configuration: config_from_hf_keys gives no window "
                                   "layers, no head size of its own and no routed experts for its keys")
    return cfg


def param_shapes(cfg) -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerLM

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                          jax.random.PRNGKey(0))
    return weights_trinity.shapes_of(tree)


def make_gap_fn(cfg: dict, pad_to: int, max_rows: int, quant=None):
    """jitted (params, tokens[pad_to], rows[max_rows], served[max_rows]) ->
    (gap of the served token below the reference's best, the reference's
    best token) at each row."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, rows, served):
        lg = reference_trinity.logits_at(params, tokens, rows, cfg, quant)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(lg, axis=-1)

    return jax.jit(f)


def length_class(n_tokens: int, window: int) -> str:
    return "over_4_windows" if n_tokens > 4 * window else "1_to_4_windows" if n_tokens > window else "under_window"


def pick_sample(records, requests, k: int, seed: int, window: int):
    """The longest finished request, then one of each other length class (the
    longest first: at most one request over 4 windows, the reference's dearest),
    then at least one with and one without the system prompt, then a draw
    from the seed among the requests of at most 4 windows."""
    done = [r for r in records if r["tokens"]]
    if not done:
        return []
    size = lambda r: len(requests[r["index"]]["prompt"]) + len(r["tokens"])  # noqa: E731
    cls = lambda r: length_class(size(r), window)  # noqa: E731
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 5])
    rest = sorted(done, key=lambda r: r["index"])
    rng.shuffle(rest)
    sample = [max(done, key=size)]
    for want in ("over_4_windows", "1_to_4_windows", "under_window"):
        if all(cls(r) != want for r in sample):
            hit = next((r for r in rest if cls(r) == want), None)
            if hit is not None:
                sample.append(hit)
    rest = [r for r in rest if cls(r) != "over_4_windows"]
    for want_sys in (True, False):
        if all(requests[r["index"]]["system"] != want_sys for r in sample):
            hit = next((r for r in rest if requests[r["index"]]["system"] == want_sys), None)
            if hit is not None:
                sample.append(hit)
    for r in rest:
        if len(sample) >= k:
            break
        if r not in sample:
            sample.append(r)
    return sample


def check_sample(ctx, params, sample, requests, quant=None) -> dict:
    """``llm_serve.check_sample`` against ``reference_trinity``: the widest and
    the mean gap of a served token below the reference's best; with ``quant``,
    the gaps of the token the lower precision puts first (the control). A
    sequence is compared at the smallest of ``check.pad_to`` that holds it."""
    import jax.numpy as jnp

    chk = ctx.workload["check"]
    cfg = reference_trinity.norm_cfg(ctx.config)
    pads = sorted(int(p) for p in chk["pad_to"])
    max_rows = max(int(v) for v in ctx.traffic["max_new_tokens"]["values"])
    fns = {}
    widest, control_widest, n_tokens, differ = 0.0, 0.0, 0, 0
    total, control_total = 0.0, 0.0
    classes, by_request = {}, []
    for rec in sample:
        prompt, served = requests[rec["index"]]["prompt"], rec["tokens"]
        n, P = len(served), len(prompt)
        pad_to = next(p for p in pads if p >= P + n - 1)
        if pad_to not in fns:
            fns[pad_to] = (make_gap_fn(cfg, pad_to, max_rows),
                           make_gap_fn(cfg, pad_to, max_rows, quant) if quant is not None else None)
        ref_fn, low_fn = fns[pad_to]
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
        c = length_class(P + n, int(ctx.config["sliding_window"]))
        classes[c] = classes.get(c, 0) + 1
        by_request.append({"prompt": P, "tokens": n, "system": bool(requests[rec["index"]].get("system")),
                           "mean_gap": round(float(gaps.mean()), 5), "widest_gap": round(float(gaps.max()), 4),
                           "not_best": int((best != np.asarray(served)).sum())})
        if low_fn is not None:
            _, low_best = low_fn(params, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(tok))
            cg, _ = ref_fn(params, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(low_best))
            control_widest = max(control_widest, float(np.asarray(cg)[:n].max()))
            control_total += float(np.asarray(cg)[:n].sum())
    return {"widest_logit_gap": widest, "control_widest_gap": control_widest,
            "mean_logit_gap": total / max(n_tokens, 1), "control_mean_gap": control_total / max(n_tokens, 1),
            "tokens": n_tokens, "tokens_not_reference_best": differ, "requests": len(sample), "classes": classes,
            "by_request": by_request}


class GroupSampler(base.StatsSampler):
    """``llm_serve.StatsSampler`` that also keeps the window-group pages the
    live requests map and what their window layers would hold with no horizon."""

    def __init__(self, engine, period_s: float = 0.1):
        super().__init__(engine, period_s)
        self.groups = []

    def run(self):
        while not self._stop.is_set():
            s = self.engine.stats()
            t = time.perf_counter()
            self.samples.append((t, s["slots_active"], s["kv_tokens_live"], s["queue_depth"]))
            self.groups.append((t, s.get("kv_window_pages_held", 0), s.get("kv_window_pages_unbounded", 0),
                                s.get("kv_pages_live", 0)))
            self._stop.wait(self.period_s)


class Served(base.Served):
    """The deployed endpoint with everything warmed; ``close`` is ``llm_serve``'s."""

    def __init__(self, ctx):  # noqa: D107 - replaces, does not extend: the parent's names the dense block
        import jax.numpy as jnp

        from fedml_tpu.serving.endpoint import EndpointManager

        self.ctx = ctx
        p, tr = ctx.workload["program"], ctx.traffic
        self.vocab = int(ctx.config["vocab_size"])
        self.timeout_s = float(p["client_timeout_s"])
        cfg = model_config(ctx)
        self.params = weights_trinity.make_params(param_shapes(cfg), ctx.seed, jnp.bfloat16)
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
                # every system-prompt length once more WHOLE, behind a prefix nobody shares: what such a
                # request costs when the shared pages are gone from either group
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

    def measure(self, requests, seconds: float, traced: dict = None) -> dict:
        """``llm_serve.Served.measure`` with the page groups sampled and the trace's parse after the drain."""
        import jax

        ctx, p = self.ctx, self.ctx.workload["program"]
        client = base.Client(self.ep, int(p["client_threads"]), self.timeout_s)
        sampler = GroupSampler(self.engine)
        stats0 = self.engine.stats()
        tracer_thread, bounds = None, {}
        t_start = time.perf_counter()
        sampler.start()
        if traced is not None:
            def trace_part():
                time.sleep(float(ctx.workload.get("trace_start_share", 0.4)) * seconds)
                ctx.tracer.start()
                bounds["trace_t0"] = time.perf_counter()
                with harness.span("trace_window"):
                    time.sleep(float(ctx.workload.get("trace_seconds", 5.0)))
                bounds["trace_t1"] = time.perf_counter()
                jax.profiler.stop_trace()  # writes the file; ctx.tracer.stop() would also parse it here

            tracer_thread = threading.Thread(target=trace_part, daemon=True, name="bench-tracer")
            tracer_thread.start()
        client.send_all(requests, t_start)
        t_close = t_start + seconds
        client.drain(len(requests), t_close + float(p["drain_s"]))
        t_end = time.perf_counter()
        sampler.stop()
        client.stop()
        if tracer_thread is not None:
            tracer_thread.join(timeout=120.0)
            red = ctx.tracer.reduce
            traced["raw"] = red.load_xplane(red.find_xplane(ctx.tracer.dir), ctx.tracer.cpu_rehearsal)
        stats1 = self.engine.stats()
        records = [client.records.get(r["index"]) for r in requests]
        w = self._window(requests, records, sampler.samples, stats0, stats1, t_start, t_close, t_end)
        in_window = [g for g in sampler.groups if t_start <= g[0] <= t_close]
        w["window_pages_held"] = [g[1] for g in in_window]
        w["window_pages_unbounded"] = [g[2] for g in in_window]
        w["full_pages_live"] = [g[3] for g in in_window]
        w.update(bounds)
        return w

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
            decode += [P + j - 1 for j in range(1, len(r["tokens"]))]  # the keys before each decoded token
        w["prefill_passes"] = prefill
        w.pop("flops", None)  # llm_serve's count is the dense block's
        if "moe_local_picks" in stats1:  # a program that counts its routing
            for k in ROUTING_COUNTERS:
                w[k] = stats1[k] - stats0[k]
            w["moe_expert_load"] = [b - a for a, b in zip(stats0["moe_expert_load"], stats1["moe_expert_load"])]
            w["flops"] = flops_trinity.serve_flops(ctx.config, prefill, decode, w["moe_local_picks"])
        for k in GROUP_COUNTERS:
            if k in stats1:
                w[k] = stats1[k] - stats0[k]
        w["window_bound_pages"] = stats1.get("kv_window_bound_pages")
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
            f"page leaks {window['page_leaks']}, groups "
            f"{ {k: window.get(k) for k in GROUP_COUNTERS} }, window pages held max "
            f"{max(window['window_pages_held'] or [0])}, routing { {k: window.get(k) for k in ROUTING_COUNTERS} }")
    trace = None
    if traced and "raw" in traced:
        trace = reduce_trace(ctx, traced["raw"])
        ctx.log(f"traced {trace['window_s']:.2f} s: busy {trace['busy_s']:.2f} s, {trace['chunks']} decode chunks")

    # ---- the plain reference over a sample, the program's state freed -------------
    t = time.perf_counter()
    sample = pick_sample(window["ok_records"], requests, int(ctx.workload["check"]["sample_requests"]), ctx.seed,
                         int(ctx.config["sliding_window"]))
    verdict = compare.Verdict()
    if sample:
        chk = check_sample(ctx, served.params, sample, requests)
        ctx.log(f"reference: {chk} in {time.perf_counter() - t:.1f} s")
        for name in ("widest_logit_gap", "mean_logit_gap"):
            verdict.add(name, chk[name], ctx.workload["limits"].get(name))
        # the comparison has to cross the horizon: a sample with no request over 4 windows shows nothing of it
        verdict.add("sample_over_4_windows_missing", 0 if chk["classes"].get("over_4_windows") else 1, 0)
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

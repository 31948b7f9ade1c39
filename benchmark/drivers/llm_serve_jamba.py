"""Driver ``llm_serve_jamba``: ``llm_serve``'s open-loop chat client in front of
a model whose layers are of two kinds (AI21-Jamba2-3B: 26 Mamba layers beside 2
attention layers, a tied head).

The client, the window, the sample and the verdict are ``llm_serve``'s, by
import: ``Client``, ``Handles``, ``StatsSampler``, ``Served._window``,
``pick_sample``, ``reduce_trace``. What names the dense block there is this
file's own: the model's config comes from the published keys through the
program's ``checkpoint_import.config_from_hf_keys``, the weights from
``weights_jamba``, the reference from ``reference_jamba``, the required work
from ``flops_jamba``. It exposes the same ``Served`` / ``run`` /
``check_sample`` that ``tools/sweep_rate.py`` and ``tools/readings.py`` drive.

Three things differ, all from the recurrent state:

* warm-up sends the system-prompt shapes THREE times. The first prompt that
  diverges from the bare system prompt finds its pages and no state snapshot,
  so it is prefilled whole and leaves the snapshot; only from the second on is
  a system-prompt request a suffix pass from the snapshot, which is the shape
  the window's requests take: one pass would leave a suffix bucket to compile
  inside the window. The third pass sends the same lengths behind a prefix
  nobody shares, so that a request whose snapshot is gone (evicted, or, in the
  rate sweep, a system prompt of another seed) finds its whole prefill
  compiled too.
* the window also carries the allocator's state counters (hits, misses,
  snapshots, evictions, bytes), and ``page_leaks`` counts what
  ``check_leaks()`` says of snapshots too.
* a traced run STOPS the profiler inside the window and parses its file only
  after the last reply (``Served.measure``, ``llm_serve``'s otherwise): the
  parse holds the interpreter's lock for seconds, and at this cell's request
  rate the clients and the engine's worker waiting behind it made the traced
  window another regime than the one the cell measures. The window also
  keeps the host-clock bounds of the traced part (``trace_t0`` /
  ``trace_t1``), by which ``selective_scan_roofline`` finds the program's own
  ``serving.cb.prefill`` spans of that part.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

import compare
import flops_jamba
import harness
import reference_jamba
import traffic
import weights_jamba

base = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "llm_serve.py"))

ENDPOINT_NAME = base.ENDPOINT_NAME
pick_sample, reduce_trace, send_one = base.pick_sample, base.reduce_trace, base.send_one


def model_config(ctx):
    import jax.numpy as jnp

    from fedml_tpu.train.llm.checkpoint_import import config_from_hf_keys

    return config_from_hf_keys(ctx.config, max_seq_len=ctx.workload["program"]["max_seq_len"],
                               dtype=jnp.bfloat16, remat=False, lora_rank=0)


def param_shapes(cfg) -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerLM

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                          jax.random.PRNGKey(0))
    return weights_jamba.shapes_of(tree)


def build_predictor(ctx, params, cfg):
    """The program's predictor for this cell (tests break it from here)."""
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    p = ctx.workload["program"]
    return LLMPredictor(params, cfg, base.char_tokenizer(cfg.vocab_size), default_max_new_tokens=32,
                        paged=True, num_slots=p["num_slots"], decode_chunk=p["decode_chunk"],
                        page_size=p["page_size"], num_pages=p.get("num_pages"),
                        state_snapshots=p["snapshot_budget_states"])


def make_gap_fn(cfg: dict, pad_to: int, max_rows: int, quant=None):
    """jitted (params, tokens[pad_to], rows[max_rows], served[max_rows]) ->
    (gap of the served token below the reference's best, the reference's
    best token) at each row."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, rows, served):
        lg = reference_jamba.logits_at(params, tokens, rows, cfg, quant)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(lg, axis=-1)

    return jax.jit(f)


def check_sample(ctx, params, sample, requests, quant=None) -> dict:
    """``llm_serve.check_sample`` against ``reference_jamba``: the widest and
    the mean gap of a served token below the reference's best; with ``quant``,
    the gaps of the token the lower precision puts first (the control)."""
    import jax.numpy as jnp

    chk = ctx.workload["check"]
    cfg = reference_jamba.norm_cfg(ctx.config)
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


STATE_COUNTERS = ("state_prefix_hits", "state_prefix_misses", "state_snapshot_evictions")


class Served(base.Served):
    """The deployed endpoint with everything warmed (see the module's header
    for the second pass); ``close`` is ``llm_serve``'s, ``measure`` is but for
    where a traced run parses its trace."""

    def __init__(self, ctx):  # noqa: D107 - replaces, does not extend: the parent's names the dense block
        import jax.numpy as jnp

        from fedml_tpu.serving.endpoint import EndpointManager

        self.ctx = ctx
        p, tr = ctx.workload["program"], ctx.traffic
        self.vocab = int(ctx.config["vocab_size"])
        self.timeout_s = float(p["client_timeout_s"])
        cfg = model_config(ctx)
        self.params = weights_jamba.make_params(param_shapes(cfg), ctx.seed, jnp.bfloat16)
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
                # the second pass: every system-prompt shape again, as a suffix pass from the snapshot
                warm += [warm[0] + rng.integers(1, self.vocab, n).tolist() for n in lens]
                # and whole, behind a prefix nobody shares: what a system-prompt request costs when
                # its snapshot is gone (evicted, or a system prompt never seen)
                warm += [rng.integers(1, self.vocab, sys_len + n).tolist() for n in lens]
            for prompt in warm:
                reply = send_one(self.ep, prompt, p["decode_chunk"] + 1, self.timeout_s)
                if len(reply["token_ids"]) != p["decode_chunk"] + 1:
                    raise harness.HarnessError(f"warm-up reply has {len(reply['token_ids'])} tokens")
        except BaseException:
            self.close()
            raise
        ctx.log(f"warmed {len(warm)} prompt shapes; compile+load {ctx.compile_log.seconds():.1f} s "
                f"in {len(ctx.compile_log.events)} programs; state {self.engine.stats()['state_snapshots']} snapshots")

    def measure(self, requests, seconds: float, traced: dict = None) -> dict:
        """``llm_serve.Served.measure`` with the trace's parse after the drain."""
        import jax

        ctx, p = self.ctx, self.ctx.workload["program"]
        client = base.Client(self.ep, int(p["client_threads"]), self.timeout_s)
        sampler = base.StatsSampler(self.engine)
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
            decode += [P + j for j in range(1, len(r["tokens"]))]
        leaks = self.engine._alloc.check_leaks()
        w["page_leaks"] += len(leaks["state_leaked"])
        w["flops"] = flops_jamba.serve_flops(ctx.config, prefill, decode)
        w["prefill_passes"] = prefill
        for k in STATE_COUNTERS:
            w[k] = stats1[k] - stats0[k]
        w["state_snapshots"], w["state_snapshot_bytes"] = stats1["state_snapshots"], stats1["state_snapshot_bytes"]
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
            f"page+snapshot leaks {window['page_leaks']}, state hits {window['state_prefix_hits']} "
            f"misses {window['state_prefix_misses']} snapshots held {window['state_snapshots']}")
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

"""Driver ``llm_serve``: open-loop chat traffic through ``Endpoint.predict``.

The endpoint is deployed in this process, as ``chip_smoke.py`` does:
``EndpointManager().deploy(name, lambda: predictor)`` puts the in-process
HTTP replica in front of ``LLMPredictor(paged=True, ...)``. A dispatcher
sends each request when it is due, whether or not earlier ones have finished
(open loop); a pool of client threads blocks in ``Endpoint.predict``. Every
request due in the window is measured from the time it was DUE to the full
reply as the client receives it (the endpoint does not stream); the run
drains them after the window closes and an unfinished one is failed.

From its own file the driver wraps ``engine.submit`` (to keep each
``RequestHandle`` beside the client request that caused it: TTFT, TPOT) and,
in a traced run only, ``engine._run_wave`` / ``engine._step_chunk`` (host
spans ``admit_wave`` / ``step_chunk`` in the profiler's trace). The program
is not changed.

Token ids reach the endpoint as text: the benchmark's tokenizer has one
character per token id (``chr(0x10000 + id)``, no merges), so a prompt's
encoding has exactly the wanted length and lands in its 16-token bucket.
"""

from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

import compare
import flops
import harness
import reference
import stats
import traffic
import weights

CHAR_BASE = 0x10000
ENDPOINT_NAME = "bench_llm"


def text_of(ids) -> str:
    return "".join(chr(CHAR_BASE + int(i)) for i in ids)


def char_tokenizer(vocab: int):
    from fedml_tpu.train.llm.tokenizer import BPETokenizer

    # no "</s>" special: random weights must never end a reply early
    return BPETokenizer({chr(CHAR_BASE + i): i for i in range(vocab)}, [], mode="metaspace")


def model_config(ctx):
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerConfig

    c, p = ctx.config, ctx.workload["program"]
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], max_seq_len=p["max_seq_len"], rope_theta=float(c["rope_theta"]),
        dtype=jnp.bfloat16, remat=False, lora_rank=0)


def param_shapes(cfg) -> dict:
    import jax
    import jax.numpy as jnp

    from fedml_tpu.models.transformer import TransformerLM

    tree = jax.eval_shape(lambda k: TransformerLM(cfg).init(k, jnp.zeros((1, 8), jnp.int32))["params"],
                          jax.random.PRNGKey(0))
    return weights.shapes_of(tree)


def build_predictor(ctx, params, cfg):
    """The program's predictor for this cell (tests break it from here)."""
    from fedml_tpu.serving.fedml_predictor import LLMPredictor

    p = ctx.workload["program"]
    return LLMPredictor(params, cfg, char_tokenizer(cfg.vocab_size), default_max_new_tokens=32,
                        paged=True, num_slots=p["num_slots"], decode_chunk=p["decode_chunk"],
                        page_size=p["page_size"], num_pages=p.get("num_pages"))


class Handles:
    """engine.submit wrapped: every RequestHandle beside its prompt."""

    def __init__(self, engine):
        self.by_prompt = {}
        self._lock = threading.Lock()
        inner = engine.submit

        def submit(prompt, max_new_tokens, **kw):
            t = time.perf_counter()
            handle = inner(prompt, max_new_tokens, **kw)
            with self._lock:
                self.by_prompt[tuple(int(x) for x in prompt)] = (t, handle)
            return handle

        engine.submit = submit

    def of(self, prompt):
        return self.by_prompt.get(tuple(prompt))


def add_trace_spans(engine) -> None:
    for attr, name in (("_run_wave", "admit_wave"), ("_step_chunk", "step_chunk")):
        inner = getattr(engine, attr)

        def wrapped(*a, _inner=inner, _name=name, **k):
            with harness.span(_name):
                return _inner(*a, **k)

        setattr(engine, attr, wrapped)


class Client:
    """The open-loop load generator: one dispatcher, a pool of blocked senders."""

    def __init__(self, ep, n_threads: int, timeout_s: float):
        self.ep, self.timeout_s = ep, timeout_s
        self.q: "queue.Queue" = queue.Queue()
        self.records = {}
        self._lock = threading.Lock()
        self.threads = [threading.Thread(target=self._worker, daemon=True, name=f"bench-client-{i}")
                        for i in range(n_threads)]
        for t in self.threads:
            t.start()

    def _worker(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            req, t_due = item
            rec = {"index": req["index"], "t_due": t_due, "t_send": time.perf_counter(),
                   "t_done": None, "tokens": None, "error": None}
            try:
                reply = self.ep.predict({"prompt": text_of(req["prompt"]),
                                         "max_new_tokens": req["max_new_tokens"],
                                         "temperature": req["temperature"]}, timeout_s=self.timeout_s)
                rec["tokens"] = [int(t) for t in reply["token_ids"]]
            except Exception as e:  # noqa: BLE001 - a failed request is counted, not raised
                rec["error"] = repr(e)
            rec["t_done"] = time.perf_counter()
            with self._lock:
                self.records[req["index"]] = rec

    def send_all(self, requests, t_start: float) -> None:
        """Dispatch each request at t_start + due_s, never earlier."""
        for req in requests:
            t_due = t_start + req["due_s"]
            with harness.span("client_wait"):
                while True:
                    left = t_due - time.perf_counter()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
            self.q.put((req, t_due))

    def drain(self, n: int, deadline: float) -> None:
        while time.perf_counter() < deadline:
            with self._lock:
                if len(self.records) >= n:
                    return
            time.sleep(0.02)

    def stop(self):
        for _ in self.threads:
            self.q.put(None)


def send_one(ep, prompt, max_new: int, timeout_s: float):
    return ep.predict({"prompt": text_of(prompt), "max_new_tokens": max_new, "temperature": 0.0},
                      timeout_s=timeout_s)


class StatsSampler(threading.Thread):
    def __init__(self, engine, period_s: float = 0.1):
        super().__init__(daemon=True, name="bench-stats")
        self.engine, self.period_s = engine, period_s
        self.samples = []
        self._stop = threading.Event()

    def run(self):
        while not self._stop.is_set():
            s = self.engine.stats()
            self.samples.append((time.perf_counter(), s["slots_active"], s["kv_tokens_live"], s["queue_depth"]))
            self._stop.wait(self.period_s)

    def stop(self):
        self._stop.set()


def make_gap_fn(cfg: dict, pad_to: int, max_rows: int, quant=None):
    """jitted (params, tokens[pad_to], rows[max_rows], served[max_rows]) ->
    (gap of the served token below the reference's best, the reference's
    best token) at each row."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, rows, served):
        lg = reference.logits_at(params, tokens, rows, cfg, quant)
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(lg, axis=-1)

    return jax.jit(f)


def pick_sample(records, requests, k: int, seed: int):
    """The longest finished request, then a draw from the seed; at least one
    with and one without the system prompt where both finished."""
    done = [r for r in records if r["tokens"]]
    if not done:
        return []
    size = lambda r: len(requests[r["index"]]["prompt"]) + len(r["tokens"])  # noqa: E731
    longest = max(done, key=size)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 5])
    rest = [r for r in done if r is not longest]
    rng.shuffle(rest)
    sample = [longest]
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
    """Reference over each sampled prompt with its served tokens -> the widest
    and the mean gap of a served token below the reference's best; with
    ``quant``, the gaps of the token the lower precision puts first (the control)."""
    import jax.numpy as jnp

    chk = ctx.workload["check"]
    cfg = reference.norm_cfg(ctx.config)
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


class Served:
    """The deployed endpoint with everything warmed: set-up once, then one
    window (a run) or several (the rate sweep)."""

    def __init__(self, ctx):
        import jax.numpy as jnp

        from fedml_tpu.serving.endpoint import EndpointManager

        self.ctx = ctx
        p, tr = ctx.workload["program"], ctx.traffic
        self.vocab = int(ctx.config["vocab_size"])
        self.timeout_s = float(p["client_timeout_s"])
        cfg = model_config(ctx)
        self.params = weights.make_params(param_shapes(cfg), ctx.seed, jnp.bfloat16)
        self.predictor = build_predictor(ctx, self.params, cfg)
        self.engine = self.predictor.engine
        self.handles = Handles(self.engine)
        if ctx.trace:
            add_trace_spans(self.engine)
        self.mgr = EndpointManager()
        self.ep = self.mgr.deploy(ENDPOINT_NAME, lambda: self.predictor)
        try:
            warm = traffic.warmup_prompts(tr, ctx.seed, self.vocab)
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
        """One open-loop window over ``requests``; drains them after it closes."""
        ctx, p = self.ctx, self.ctx.workload["program"]
        client = Client(self.ep, int(p["client_threads"]), self.timeout_s)
        sampler = StatsSampler(self.engine)
        stats0 = self.engine.stats()
        tracer_thread = None
        t_start = time.perf_counter()
        sampler.start()
        if traced is not None:
            def trace_part():
                time.sleep(float(ctx.workload.get("trace_start_share", 0.4)) * seconds)
                ctx.tracer.start()
                with harness.span("trace_window"):
                    time.sleep(float(ctx.workload.get("trace_seconds", 5.0)))
                traced["raw"] = ctx.tracer.stop()

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
        stats1 = self.engine.stats()
        records = [client.records.get(r["index"]) for r in requests]
        return self._window(requests, records, sampler.samples, stats0, stats1, t_start, t_close, t_end)

    def _window(self, requests, records, samples, stats0, stats1, t_start, t_close, t_end) -> dict:
        ctx, p, tr = self.ctx, self.ctx.workload["program"], self.ctx.traffic
        ok = [r for r in records if r is not None and r["tokens"] is not None
              and len(r["tokens"]) == requests[r["index"]]["max_new_tokens"]]
        ok_idx = {r["index"] for r in ok}
        t_last = max((r["t_done"] for r in ok), default=t_end)
        per_request = []
        for r in ok:
            h = self.handles.of(requests[r["index"]]["prompt"])
            if h is not None and h[1].ttft_s is not None:
                per_request.append({"index": r["index"], "ttft_s": (h[0] - r["t_due"]) + h[1].ttft_s,
                                    "tpot_s": h[1].tpot_s})
        in_window = [s for s in samples if t_start <= s[0] <= t_close]
        leaks = self.engine._alloc.check_leaks()
        page = int(p["page_size"])
        sys_len = int(tr.get("system_prompt_tokens", 0)) // page * page
        prefill, decode = [], []
        for r in ok:
            req = requests[r["index"]]
            P = len(req["prompt"])
            start = sys_len if req["system"] else 0
            prefill.append((P - start, start))
            decode += [P + j for j in range(1, len(r["tokens"]))]
        return {
            "t_start": t_start, "t_close": t_close, "t_last": t_last, "seconds": t_last - t_start,
            "requests": len(requests), "ok": len(ok), "ok_records": ok, "records": records,
            "failed": len(requests) - len(ok),
            "out_tokens": sum(len(r["tokens"]) for r in ok),
            "latencies_s": [(r["t_done"] - r["t_due"]) if (r is not None and r["index"] in ok_idx) else None
                            for r in records],
            "per_request": per_request,
            "lateness_s": [r["t_send"] - r["t_due"] for r in records if r is not None],
            "slots_total": int(p["num_slots"]),
            "slots_active": [s[1] for s in in_window], "kv_tokens_live": [s[2] for s in in_window],
            "queue_depth": [s[3] for s in in_window],
            "prefix_hits": stats1["kv_prefix_hits"] - stats0["kv_prefix_hits"],
            "prefix_misses": stats1["kv_prefix_misses"] - stats0["kv_prefix_misses"],
            "compiles": ctx.compile_log.between(t_start, t_last),
            "page_leaks": len(leaks["leaked"]) + len(leaks["bad_free"]),
            "decode_chunk": int(p["decode_chunk"]),
            "flops": flops.serve_flops(ctx.config, prefill, decode),
        }

    def close(self):
        """Stop the endpoint and free the program's state; the weights (the
        benchmark's own) stay for the reference."""
        if self.engine is None:
            return
        self.mgr.undeploy(ENDPOINT_NAME)
        self.engine.shutdown()
        self.predictor.engine = None
        self.engine._cache = None
        self.engine._params = None
        self.predictor._params = None
        self.engine = self.predictor = self.ep = self.mgr = None
        gc.collect()


def reduce_trace(ctx, raw) -> dict:
    red = ctx.tracer.reduce
    lo, hi = red.device_extent(raw)
    spans = [s for s in raw.host_spans if s.name == "trace_window"]
    if spans and spans[0].start_ns <= lo and hi <= spans[0].end_ns + 1e9:
        lo, hi = spans[0].start_ns, spans[0].end_ns  # the host's window: the clocks agree
    trace = red.reduce(raw, window=(lo, hi))
    trace.update(raw=raw, lo=lo, hi=hi,
                 chunks=sum(1 for s in raw.host_spans if s.name == "step_chunk"
                            and s.start_ns >= lo and s.end_ns <= hi))
    return trace


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
            f"page leaks {window['page_leaks']}")
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
            "serve_latency_p95_ms": _p95_ms(window["latencies_s"]),
            "serve_out_tokens_per_s": window["out_tokens"] / max(window["seconds"], 1e-9),
            "setup_s": window["t_start"] - ctx.t_process_start},
        "window": window, "trace": trace, "requests": requests, "sample": sample, "params": served.params,
    }


def _p95_ms(latencies) -> float:
    if not any(x is not None for x in latencies):
        return float("inf")
    return stats.latency_percentile_ms(latencies, 95.0)

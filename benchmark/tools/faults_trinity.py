#!/usr/bin/env python3
"""Planted faults under the cell's own comparison, on the chip at the cell's
own sizes: the program is built for ANOTHER config than the one the weights and
the plain reference were made for, serves three prompts (shorter than the
window, between 1 and 4 windows, over 4 windows) through the engine, and the
cell's ``check_sample`` says how far what it served lies from the reference.
Each fault has to read OVER one of the cell's limits; the unfaulted program,
run the same way first, under both.

    python3 benchmark/tools/faults_trinity.py --workload trinity_mini_longmix_over [--seed N]

  unwindowed   the window layers run with no horizon (``sliding_window`` = the whole row)
  full_rotary  the full layer run with rotary positions (``use_rope``)

One JSON line a case goes to ``chiprun_out/faults_<cell>.jsonl``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

NEW_TOKENS = 32  # a request's, at most the mix's longest reply


def serve(ctx, params, cfg, prompts, new_tokens, window_pages):
    """``window_pages``: the window page group of the UNFAULTED program (a fault that widens the
    window would size its own group for the wider window, past the chip)."""
    from fedml_tpu.serving.continuous_batching import PagedContinuousBatchingEngine

    p = ctx.workload["program"]
    eng = PagedContinuousBatchingEngine(params, cfg, num_slots=p["num_slots"], chunk=p["decode_chunk"],
                                        page_size=p["page_size"], num_pages=p["num_pages"],
                                        num_window_pages=window_pages)
    try:
        handles = [eng.submit(prompt, new_tokens) for prompt in prompts]
        return [h.result(timeout=1200) for h in handles]
    finally:
        eng.shutdown()
        eng._cache = eng._params = None
        gc.collect()


def main(argv=None, root: str = ROOT, allow_cpu: bool = False) -> int:
    """``root`` / ``allow_cpu`` are for the tests' rehearsal at a tiny size."""
    import jax.numpy as jnp
    import numpy as np

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 707)
    args = ap.parse_args(argv)
    cell = harness.Cell(root, args.workload)
    harness.place_compile_cache(root)
    ctx = harness.Ctx(cell, args.seed, 1.0, False, T0, allow_cpu=allow_cpu)
    drv = cell.driver()
    cfg = drv.model_config(ctx)
    import weights_trinity

    params = weights_trinity.make_params(drv.param_shapes(cfg), args.seed, jnp.bfloat16)
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, 8])
    vocab, window = int(ctx.config["vocab_size"]), int(ctx.config["sliding_window"])
    new_tokens = min(NEW_TOKENS, max(int(v) for v in ctx.traffic["max_new_tokens"]["values"]))
    lengths = (window // 2, 2 * window + window // 8, int(ctx.workload["program"]["max_seq_len"]) - new_tokens - 1)
    prompts = [rng.integers(1, vocab, n).tolist() for n in lengths]
    requests = [{"prompt": p} for p in prompts]
    from fedml_tpu.serving.paged_kv import window_bound

    prog = ctx.workload["program"]
    bound = lambda w: window_bound(w, prog["decode_chunk"], prog["page_size"])  # noqa: E731
    # the unfaulted program's group; at least a bound of the WIDEST window for each of the prompts and one more
    window_pages = max((prog["num_slots"] + 1) * bound(window), (len(prompts) + 1) * bound(cfg.max_seq_len)) + 1
    cases = (("none", {}), ("unwindowed", {"sliding_window": cfg.max_seq_len}), ("full_rotary", {"use_rope": True}))
    limits = ctx.workload["limits"]
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    bad = 0
    with open(os.path.join(root, "chiprun_out", f"faults_{args.workload}.jsonl"), "a") as f:
        for name, changes in cases:
            served = serve(ctx, params, dataclasses.replace(cfg, **changes), prompts, new_tokens, window_pages)
            sample = [{"index": i, "tokens": toks} for i, toks in enumerate(served)]
            chk = drv.check_sample(ctx, params, sample, requests)
            over = [k for k in ("widest_logit_gap", "mean_logit_gap") if chk[k] > limits[k]]
            ok = bool(over) if changes else not over
            bad += not ok
            row = {"fault": name, "seed": args.seed, "prompt_tokens": list(lengths), "over": over, "as_expected": ok,
                   **{k: chk[k] for k in ("widest_logit_gap", "mean_logit_gap", "tokens", "tokens_not_reference_best")}}
            ctx.log(json.dumps(row))
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

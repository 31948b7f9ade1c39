#!/usr/bin/env python3
"""Find the knee once: one process, one set-up, a few offered rates in turn.

    python3 benchmark/tools/sweep_rate.py --workload <cell> --rates 4,6,8,10,12 [--seconds 20]

At each rate: requests ok, latency p50/p95 from due time, output tokens/s, the
backlog (queue depth + active slots) in the first and last third of the window
and the time the drain took after the window closed. The highest rate with no
growing backlog is the knee; the cell's rate is 0.8 of it, written into the
traffic file by hand.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
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
import stats  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2**31 + 606)
    args = ap.parse_args()
    cell = harness.Cell(ROOT, args.workload)
    harness.place_compile_cache(ROOT)
    ctx = harness.Ctx(cell, args.seed, args.seconds, False, T0)
    drv = cell.driver()
    served = drv.Served(ctx)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    try:
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            mix = dict(ctx.traffic, rate_per_s=rate)
            reqs = traffic.open_loop_requests(mix, args.seed + i, args.seconds, served.vocab)["requests"]
            w = served.measure(reqs, args.seconds)
            lat = [x for x in w["latencies_s"] if x is not None] or [float("nan")]
            if w["compiles"]:
                ctx.log(f"COMPILED IN THE WINDOW at rate {rate}: {[(c[1], round(c[2], 3)) for c in w['compiles']]}")
            n = len(w["queue_depth"])
            backlog = [q + a for q, a in zip(w["queue_depth"], w["slots_active"])]
            third = max(1, n // 3)
            row = {
                "rate": rate, "requests": w["requests"], "ok": w["ok"],
                "p50_ms": 1e3 * stats.percentile(lat, 50), "p95_ms": 1e3 * stats.percentile(lat, 95),
                "out_tokens_per_s": w["out_tokens"] / w["seconds"],
                "backlog_first_third": sum(backlog[:third]) / third,
                "backlog_last_third": sum(backlog[-third:]) / third,
                "queue_max": max(w["queue_depth"] or [0]),
                "slots_mean": sum(w["slots_active"]) / max(1, n),
                "drain_s": w["t_last"] - w["t_close"],
                "ttft_p95_ms": 1e3 * stats.percentile([r["ttft_s"] for r in w["per_request"]] or [float("nan")], 95),
                "tpot_p50_ms": 1e3 * stats.percentile([r["tpot_s"] for r in w["per_request"] if r["tpot_s"]] or [float("nan")], 50),
                "lateness_p95_ms": 1e3 * stats.percentile(w["lateness_s"], 95),
                "compiles": len(w["compiles"]), "failed": w["failed"],
            }
            print(json.dumps(row), flush=True)
            with open(os.path.join(ROOT, "chiprun_out", f"sweep_{args.workload}.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
    finally:
        served.close()
    print("memory_peak_bytes", harness.memory_peak_bytes(cell.chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())

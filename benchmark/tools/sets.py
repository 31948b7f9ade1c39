#!/usr/bin/env python3
"""Two sets of runs of one cell with the same seeds, as the driver's check
makes them, and the spread of every end-to-end metric (the distance between
the quartiles over the median). This parent never touches JAX: each run is a
process of its own that holds the chip alone.

    python3 benchmark/tools/sets.py --workload <cell> [--runs 6] [--sets 2] [--traced 1]
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from stats import spread  # noqa: E402  (the benchmark's own arithmetic; no JAX)


def one_run(workload, seed, seconds, trace, log):
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", workload,
           "--seed", str(seed), "--trace", str(trace)]
    if seconds:
        cmd += ["--seconds", str(seconds)]
    t = time.time()
    r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.time() - t
    log.write(r.stderr[-6000:] + "\n")
    lines = r.stdout.strip().splitlines()
    if r.returncode != 0 or not lines:
        print(f"run failed rc={r.returncode}: {r.stderr[-2000:]}")
        return None
    out = json.loads(lines[-1])
    out["_seed"], out["_wall_s"], out["_trace"] = seed, wall, trace
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=0, help="traced runs after the sets, on seeds of their own")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2**31 + 4242)
    args = ap.parse_args()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", f"sets_{args.workload}.jsonl")
    seeds = [args.seed0 + 1009 * i for i in range(args.runs)]
    sets = []
    with open(out_path, "a") as f, open(out_path + ".log", "a") as log:
        for s in range(args.sets):
            rows = []
            for seed in seeds:
                out = one_run(args.workload, seed, args.seconds, 0, log)
                if out is None:
                    return 1
                out["_set"] = s
                f.write(json.dumps(out) + "\n")
                f.flush()
                rows.append(out)
                print(f"set {s} seed {seed} correct {out['correct']} wall {out['_wall_s']:.0f}s "
                      + " ".join(f"{k}={v['value']:.6g}" for k, v in out["metrics"].items()), flush=True)
            sets.append(rows)
        for i in range(args.traced):
            out = one_run(args.workload, args.seed0 + 500009 * (i + 1), args.seconds, 1, log)
            if out is None:
                return 1
            f.write(json.dumps(out) + "\n")
            print(f"traced seed {out['_seed']} correct {out['correct']} wall {out['_wall_s']:.0f}s "
                  + " ".join(f"{k}={v['value']:.6g}" for k, v in out["metrics"].items()), flush=True)
    names = list(sets[0][0]["metrics"])
    for name in names:
        per_set = [[r["metrics"][name]["value"] for r in rows] for rows in sets]
        sp = [spread(v) for v in per_set if len(v) >= 2]
        # the driver leaves each side's first run (the one that compiles) out of setup_s
        meds = [statistics.median(v[1:] if name == "setup_s" else v) for v in per_set]
        print(f"{name}: medians {['%.6g' % m for m in meds]} spreads {['%.4f' % x for x in sp]} "
              f"widest {max(sp) if sp else float('nan'):.4f} -> bound about {5 * max(sp) if sp else float('nan'):.4f}")
    ok = all(r["correct"] for rows in sets for r in rows)
    print("all correct:", ok, "peak bytes:", max(r["device"]["memory_peak_bytes"] for rows in sets for r in rows))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

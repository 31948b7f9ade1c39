#!/usr/bin/env python3
"""The starvation ledger's readings of one cell, traced and untraced runs in
ONE process (set-up's compiles shared through the cache and the jit caches).

    python3 benchmark/tools/starvation.py --workload <cell> --runs <seed>:<trace>[,<seed>:<trace>...]
                                          [--seconds S] [--time-calls]

For each run: the driver's own ``run(ctx)``, then every reader of
``benchmark/idle_by_span.py`` through its computing function (``read`` is the
harness's and speaks in traced runs only), the pieces' ``unseen_ns`` sum (the
upper bound of the chip's idle time), and the ten longest starvation
intervals with the worker's spans under each. A traced run also logs
``idle_by_span.report`` (stderr). One JSON line a run goes to
``chiprun_out/starvation_<cell>.jsonl``; a summary line a run to stdout.

``--time-calls`` times the ledger's own calls alone, before any engine exists:
a ``mark`` while a program runs on the chip (the queue busy), and a ``mark``
that cuts a piece.
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
import idle_by_span  # noqa: E402

LEDGER = ("serve_device_starved_pct", "serve_starved_pct.no_work", "serve_starved_pct.collect",
          "serve_starved_pct.launch", "serve_starved_pct.land", "serve_worker_off_cpu_pct", "serve_lock_wait_pct",
          "serve_idle_in_starved_pct", "serve_idle_unnamed_pct")
ALSO = ("serve_loop_admit_pct", "serve_loop_host_pct", "serve_decode_batch_mean")


def intervals(run: dict, top: int = 10):
    """The longest starvation intervals of the seconds looked at: their pieces
    by phase, the unseen head, and the worker's deepest spans under them."""
    worker, _ = idle_by_span.worker_spans(run, 0.0)
    if not worker:
        return []
    segs = idle_by_span.flatten(worker)
    starts = [s[0] for s in segs]
    groups = []
    for p in idle_by_span.pieces(run):
        if p["first"] or not groups:
            groups.append({"lo_s": p["lo_s"], "hi_s": p["hi_s"], "unseen_ms": 1e3 * p["unseen_s"], "phases": {}})
        g = groups[-1]
        g["hi_s"] = p["hi_s"]
        g["phases"][p["phase"]] = g["phases"].get(p["phase"], 0.0) + 1e3 * (p["hi_s"] - p["lo_s"])
    lo0 = idle_by_span.bounds(run)[0]
    out = []
    for g in sorted(groups, key=lambda g: g["lo_s"] - g["hi_s"])[:top]:
        under = idle_by_span.overlap_by_name((g["lo_s"] * 1e9, g["hi_s"] * 1e9), segs, starts)
        out.append({"at_s": round(g["lo_s"] - lo0, 3), "ms": round(1e3 * (g["hi_s"] - g["lo_s"]), 2),
                    "unseen_ms": round(g["unseen_ms"], 2), "phases_ms": {k: round(v, 2) for k, v in g["phases"].items()},
                    "worker_ms": {k: round(v / 1e6, 2) for k, v in sorted(under.items(), key=lambda kv: -kv[1])}})
    return out


def _value(cell, name: str, run: dict):
    return harness.load_module(os.path.join(cell.bench_dir, "metrics", name + ".py")).value(run)


def one_run(cell, seed: int, trace: bool, seconds: float, allow_cpu: bool = False) -> dict:
    from fedml_tpu.core import telemetry as tel

    tel.reset()  # one run's records: the registry caps them, the readers cache one snapshot a run
    ctx = harness.Ctx(cell, seed, seconds, trace, T0, allow_cpu)
    ctx.log(f"cell {cell.name} seed {seed} seconds {seconds} trace {int(trace)} device {ctx.device}")
    run = cell.driver().run(ctx)
    run["ctx"] = ctx
    row = {"workload": cell.name, "seed": seed, "trace": int(trace), "correct": bool(run["verdict"].correct),
           "failed": int(run["failed"]), "attempted": int(run["attempted"]),
           "memory_peak_bytes": int(run["memory_peak_bytes"]),
           "compiles_in_window": len(run["window"]["compiles"]),
           "end_to_end": {k: float(v) for k, v in run["end_to_end"].items()},
           "compared": run["verdict"].rows}
    for name in LEDGER + ALSO:
        row[name] = _value(cell, name, run)
    lo, hi = idle_by_span.bounds(run)
    pcs = idle_by_span.pieces(run)
    row["seconds_looked_at"] = hi - lo
    row["starvations"] = sum(p["first"] for p in pcs)
    row["pieces"] = len(pcs)
    row["unseen_pct"] = 100.0 * sum(p["unseen_s"] for p in pcs) / (hi - lo)
    its = idle_by_span.iterations(run)
    row["iterations"] = len(its)
    row["iteration_ms_mean"] = 1e3 * sum(s["dur_s"] for s in its) / max(len(its), 1)
    row["cpu_pct"] = 100.0 * sum(s["attrs"]["cpu_ns"] for s in its) / 1e9 / max(sum(s["dur_s"] for s in its), 1e-9)
    row["blocked_pct"] = 100.0 * sum(s["attrs"]["blocked_ns"] for s in its) / 1e9 / max(sum(s["dur_s"] for s in its), 1e-9)
    row["longest_starvations"] = intervals(run)
    if run.get("trace"):
        t = run["trace"]
        row.update(device_idle_pct=t["idle_pct"], busy_s=t["busy_s"], window_s=t["window_s"], idle_gaps=t["idle_gaps"])
        rep = idle_by_span.report(run)
        if rep is not None:
            row["idle_by_span"] = {k: rep[k] for k in ("idle_s", "by_name", "in_starved", "longest")}
    lat = [x for x in run["window"].get("lateness_s", []) if x is not None]
    if lat:
        row["gen_lateness_p95_ms"] = 1e3 * sorted(lat)[min(len(lat) - 1, int(0.95 * len(lat)))]
    run["verdict"].print_stderr()
    return row


def time_calls(n: int = 20000, loops: int = 1500) -> dict:
    """ns a call of the ledger's own calls, alone: ``mark`` while a program of
    about a second runs on the chip (the look reads busy), ``mark`` that cuts
    a piece (the look before read ready), ``launched`` that closes an interval."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.core import telemetry as tel
    from fedml_tpu.serving.continuous_batching import LAND, _DeviceLedger

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    spin = jax.jit(lambda a: jax.lax.fori_loop(0, loops, lambda i, b: (b @ a) * 1e-4, a))  # 137 GFLOP a round
    spin(x).block_until_ready()
    led = _DeviceLedger(())
    out = spin(x)
    led.launched(out)
    t = time.perf_counter_ns()
    for _ in range(n):
        led.mark(LAND)
    busy = (time.perf_counter_ns() - t) / n
    still_busy = not out.is_ready()
    out.block_until_ready()
    led.mark(LAND)  # opens
    t = time.perf_counter_ns()
    for _ in range(n):
        led.mark(LAND)
    cut = (time.perf_counter_ns() - t) / n
    t = time.perf_counter_ns()
    for _ in range(n):
        led.launched(out)
        led.mark(LAND)
    reopen = (time.perf_counter_ns() - t) / n
    t = time.perf_counter_ns()
    for _ in range(n):
        time.thread_time_ns()
    cpu = (time.perf_counter_ns() - t) / n
    tel.reset()
    return {"mark_busy_ns": busy, "still_busy_after_the_loop": still_busy, "mark_that_cuts_a_piece_ns": cut,
            "launched_then_mark_that_opens_ns": reopen, "thread_time_ns_call_ns": cpu, "calls": n,
            "device": jax.devices()[0].device_kind}


def main(argv=None, root: str = ROOT, allow_cpu: bool = False) -> int:
    """``root`` / ``allow_cpu``: the tests' rehearsal on a tiny cell, as ``run.main``'s."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", default="", help="seed:trace pairs, comma-separated")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--time-calls", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = harness.Cell(root, args.workload)
    harness.place_compile_cache(root)
    seconds = args.seconds or harness.load_json(os.path.join(root, "BENCHMARK.json"))["run_seconds"]
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    with open(os.path.join(out_dir, f"starvation_{args.workload}.jsonl"), "a") as f:
        if args.time_calls:
            harness.require_device(cell.chips, allow_cpu)
            row = {"time_calls": time_calls() if not allow_cpu else time_calls(200, 2)}
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
        for spec in [s for s in args.runs.split(",") if s]:
            seed, trace = (int(x) for x in spec.split(":"))
            row = one_run(cell, seed, bool(trace), seconds, allow_cpu)
            f.write(json.dumps(row) + "\n")
            f.flush()
            ok = ok and row["correct"]
            brief = {k: row[k] for k in ("seed", "trace", "correct", "failed") + LEDGER + ALSO
                     + ("unseen_pct", "starvations", "iterations", "iteration_ms_mean", "cpu_pct", "blocked_pct")}
            brief.update(row["end_to_end"], device_idle_pct=row.get("device_idle_pct"),
                         gen_lateness_p95_ms=row.get("gen_lateness_p95_ms"))
            print(json.dumps(brief), flush=True)
            for g in row["longest_starvations"]:
                print("  starved", json.dumps(g), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # daemon threads of the endpoint must not outlive the summary

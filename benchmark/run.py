#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it holds the chip, loads, warms up every shape the cell's
traffic uses (set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints the contract's JSON object
as the LAST line of stdout. Everything else goes to stderr. With no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None, root: str = ROOT, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (root, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness

    seconds = args.seconds
    if seconds is None:
        seconds = harness.load_json(os.path.join(root, "BENCHMARK.json"))["run_seconds"]
    try:
        result = harness.run_cell(root, args.workload, args.seed, seconds, bool(args.trace),
                                  T_PROCESS_START, allow_cpu=allow_cpu)
    except harness.HarnessError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # daemon threads of the endpoint must not outlive the result line

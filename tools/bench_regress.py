#!/usr/bin/env python3
"""Bench regression sentinel over the measured-artifact trajectory.

``bench.py`` banks one ``BENCH_MEASURED_*.json``
per successful ladder run; round-numbered ``BENCH_r0*.json``
baselines — and until now nothing ever *read* the trajectory, so a decaying
rounds/hr or a TTFT tail doubling between runs was invisible. Runs are
stage-isolated, so key sets differ per artifact; for every headline key the
tool therefore compares its newest occurrence on the trajectory against the
most recent PRIOR occurrence (falling back to the ``BENCH_r0*.json`` parsed
baselines for keys measured only once), prints a per-key delta table, and
exits nonzero when any headline regressed by more than ``--threshold``
(default 10%) in its "worse" direction. The ladder's generic ``value``
headline is qualified by its ``metric`` name so short-window and full-ladder
headlines never cross-compare.

Usage::

    python tools/bench_regress.py [--repo DIR] [--threshold 0.10] [--json]

Exit codes: 0 = no regression (or nothing to compare yet), 1 = at least one
headline regressed, 2 = usage error.
"""

from __future__ import annotations

import argparse
import fnmatch
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

# Headline keys and the direction in which a move is an IMPROVEMENT.
# Patterns are fnmatch globs over dot-flattened artifact paths; anything the
# table does not name is informational only (shape strings, platform notes,
# stage sub-docs) and never trips the sentinel.
HEADLINES: Dict[str, str] = {
    "value:*": "higher",                     # ladder headline, metric-qualified
    "*.value:*": "higher",                   # same, nested (short_window etc.)
    "mfu": "higher",
    "fedavg_rounds_per_hr": "higher",
    "decode_tokens_per_sec": "higher",
    "decode_tokens_per_sec_int8": "higher",
    "int8_decode_speedup": "higher",
    "resnet56_steps_per_sec": "higher",
    "resnet56_mfu": "higher",
    "async_rounds_per_hr.*": "higher",       # per-cohort dict
    "async_flatness_ratio": "higher",
    "agg_clients_per_sec.*": "higher",       # per-engine/K nested dict
    "agg_sharded_clients_per_sec": "higher",
    "agg_wall_s": "lower",
    "ckpt_enqueue_ms": "lower",
    "placement_speedup.*": "higher",
    "link_bw_error_pct": "lower",
    "probe_overhead_pct": "lower",
    "pipeline_overlap_frac": "higher",       # ISSUE 15 stage executor
    "pipeline_speedup": "higher",
    "slo_overhead_pct": "lower",             # ISSUE 14 evaluator guard
    "llm_mfu": "higher",                     # ISSUE 17 devperf registry MFU
    "devperf_overhead_pct": "lower",         # ISSUE 17 registry cost guard
    "modelwatch_overhead_pct": "lower",      # ISSUE 18 fold-stats cost guard
    "fleet_scale_quantile_err_pct": "lower",  # ISSUE 19 sketch accuracy
    "fleet_telemetry_bytes_per_client": "lower",  # ISSUE 19 memory bound
    "secagg_overhead_pct": "lower",          # ISSUE 20 masking+DP cost guard
    "dp_epsilon_spent": "lower",             # ISSUE 20 budget per bench run
    "_llm_pallas.tokens_per_sec": "higher",
    "_llm_pallas.mfu": "higher",
}


def flatten(doc: Any, prefix: str = "") -> Dict[str, float]:
    """Dot-flattened numeric leaves of an artifact (bool excluded).

    A dict carrying both ``metric`` and a numeric ``value`` is a ladder
    headline: its value flattens to ``value:<metric>`` so runs that measured
    DIFFERENT ladder metrics never cross-compare.
    """
    out: Dict[str, float] = {}
    if isinstance(doc, dict):
        metric = doc.get("metric")
        for k, v in doc.items():
            if (k == "value" and isinstance(metric, str)
                    and isinstance(v, (int, float)) and not isinstance(v, bool)):
                out[f"{prefix}value:{metric}"] = float(v)
            else:
                out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(doc, (int, float)) and not isinstance(doc, bool):
        out[prefix[:-1]] = float(doc)
    return out


def direction_of(key: str) -> Optional[str]:
    for pat, d in HEADLINES.items():
        if fnmatch.fnmatch(key, pat):
            return d
    return None


def load_measured(repo: str) -> List[Tuple[str, Dict[str, float]]]:
    """(path, flat) for every measured artifact, NEWEST first (the stamp in
    the filename is the watcher's capture time and sorts lexically)."""
    paths = sorted(glob.glob(os.path.join(repo, "BENCH_MEASURED_*.json")),
                   reverse=True)
    out = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as f:
                out.append((p, flatten(json.load(f))))
        except (OSError, ValueError) as e:
            print(f"bench_regress: unreadable artifact {p}: {e}", file=sys.stderr)
    return out


def load_baselines(repo: str) -> List[Tuple[str, str, float]]:
    """(path, metric, value) from each ``BENCH_r0*.json`` whose capture
    parsed a headline (failed captures carry ``parsed: null``)."""
    out = []
    for p in sorted(glob.glob(os.path.join(repo, "BENCH_r0*.json"))):
        try:
            with open(p, encoding="utf-8") as f:
                parsed = (json.load(f) or {}).get("parsed")
        except (OSError, ValueError):
            continue
        if parsed and parsed.get("metric") and parsed.get("value") is not None:
            out.append((p, str(parsed["metric"]), float(parsed["value"])))
    return out


def compare(repo: str, threshold: float) -> Dict[str, Any]:
    measured = load_measured(repo)
    # key -> [(path, value), ...] newest-first; parsed baselines ride at the
    # tail so a key measured only once still gets a reference point
    series: Dict[str, List[Tuple[str, float]]] = {}
    for p, flat in measured:
        for key, v in flat.items():
            if direction_of(key) is not None:
                series.setdefault(key, []).append((p, v))
    for p, m, v in reversed(load_baselines(repo)):
        for key in (f"value:{m}", m):
            if direction_of(key) is not None:
                series.setdefault(key, []).append((p, v))
                break
    rows: List[Dict[str, Any]] = []
    for key in sorted(series):
        occ = series[key]
        if len(occ) < 2 or occ[1][1] == 0:
            continue
        (new_p, new), (old_p, old) = occ[0], occ[1]
        delta = (new - old) / abs(old)
        direction = direction_of(key)
        worse = -delta if direction == "higher" else delta
        rows.append({
            "key": key,
            "new": new,
            "old": old,
            "at": os.path.basename(new_p),
            "ref": os.path.basename(old_p),
            "delta_pct": round(delta * 100, 2),
            "direction": direction,
            "regressed": worse > threshold,
        })
    return {
        "newest": os.path.basename(measured[0][0]) if measured else None,
        "threshold_pct": threshold * 100,
        "compared": len(rows),
        "regressions": [r for r in rows if r["regressed"]],
        "rows": rows,
    }


def render_table(report: Dict[str, Any]) -> str:
    lines = []
    if not report["newest"]:
        return "bench_regress: no BENCH_MEASURED_*.json artifacts to compare"
    if not report["rows"]:
        return (f"bench_regress: {report['newest']}: no headline key has a "
                "prior occurrence or baseline yet — nothing to compare")
    w = max(len(r["key"]) for r in report["rows"])
    lines.append(f"bench_regress: trajectory through {report['newest']} "
                 f"(threshold {report['threshold_pct']:.0f}%)")
    lines.append(f"  {'key'.ljust(w)}  {'new':>12}  {'prior':>12}  "
                 f"{'delta':>8}  verdict  (newest <- reference)")
    for r in report["rows"]:
        verdict = "REGRESS" if r["regressed"] else "ok"
        arrow = "+" if r["delta_pct"] >= 0 else ""
        lines.append(
            f"  {r['key'].ljust(w)}  {r['new']:>12.4g}  {r['old']:>12.4g}  "
            f"{arrow}{r['delta_pct']:>6.1f}%  {verdict:7}  "
            f"({r['at']} <- {r['ref']})")
    n = len(report["regressions"])
    lines.append(f"  => {n} regression(s) over threshold"
                 if n else "  => no regressions over threshold")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="repo root holding BENCH_*.json")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="fractional regression that trips the sentinel")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report instead of a table")
    args = ap.parse_args(argv)
    if args.threshold <= 0:
        ap.error("--threshold must be > 0")
    report = compare(args.repo, args.threshold)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render_table(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())

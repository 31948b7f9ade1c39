"""Median over finished requests of RequestHandle.tpot_s."""

import stats


def read(run):
    xs = [r["tpot_s"] for r in run["window"]["per_request"] if r["tpot_s"] is not None]
    return 1e3 * stats.percentile(xs, 50.0) if xs else None

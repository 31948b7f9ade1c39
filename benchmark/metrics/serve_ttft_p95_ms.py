"""p95 over finished requests of (due -> engine.submit) + RequestHandle.ttft_s."""

import stats


def read(run):
    xs = [r["ttft_s"] for r in run["window"]["per_request"]]
    return 1e3 * stats.percentile(xs, 95.0) if xs else None

"""p95 of (actual send - due): how late the load generator ran."""

import stats


def read(run):
    xs = run["window"]["lateness_s"]
    return 1e3 * stats.percentile(xs, 95.0) if xs else None

"""Requests admitted in the window that found prefix pages, over all admitted."""


def read(run):
    w = run["window"]
    n = w["prefix_hits"] + w["prefix_misses"]
    return 100.0 * w["prefix_hits"] / n if n else None

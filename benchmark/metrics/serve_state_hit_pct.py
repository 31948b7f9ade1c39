"""Admissions of the window whose recurrent layers started from a state
snapshot of the prefix cache, over all admissions (the allocator's
``state_prefix_hits`` / ``state_prefix_misses``: a miss starts them from the
zero state whether or not pages matched)."""


def read(run):
    w = run["window"]
    if "state_prefix_hits" not in w:
        return None
    n = w["state_prefix_hits"] + w["state_prefix_misses"]
    return 100.0 * w["state_prefix_hits"] / n if n else None

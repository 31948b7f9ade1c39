"""Pages the live requests map in the window page group over the pages their
window layers would hold with no horizon (``ceil(len / page_size)`` a live row),
both sampled from ``engine.stats()`` through the window
(``kv_window_pages_held`` / ``kv_window_pages_unbounded``), sum over sum. 100 is
a cache that keeps every token of every layer; a request of 16 k tokens at its
bound of 34 pages of 64 reads 13."""


def read(run):
    w = run["window"]
    held, unbounded = w.get("window_pages_held"), w.get("window_pages_unbounded")
    if not held or not unbounded or sum(unbounded) <= 0:
        return None
    return 100.0 * sum(held) / sum(unbounded)

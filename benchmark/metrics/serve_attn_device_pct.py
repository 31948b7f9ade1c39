"""Device seconds of the two attention kernels of the serving path (the paged
decode kernel ``paged_attention`` and the prefill's ``flash_attention_rows``,
found by their own names as their rooflines find them) over the busy seconds of
the traced window: how much of the chip's time attention is."""

KERNELS = ("paged_attention", "flash_attention_rows")


def read(run):
    t = run.get("trace")
    if not t or run["ctx"].peaks is None or t["busy_s"] <= 0:
        return None
    spent = sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNELS))
    return 100.0 * spent / t["busy_s"] if spent > 0 else None

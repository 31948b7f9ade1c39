"""Device seconds of the routed experts' matmuls (the grouped-matmul kernel,
found by its own name as ``moe_grouped_matmul_roofline`` finds it) over the busy
seconds of the traced window: how much of the chip's time the experts are."""

KERNEL = "grouped_matmul"


def read(run):
    t = run.get("trace")
    if not t or run["ctx"].peaks is None or t["busy_s"] <= 0:
        return None
    spent = sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))
    return 100.0 * spent / t["busy_s"] if spent > 0 else None

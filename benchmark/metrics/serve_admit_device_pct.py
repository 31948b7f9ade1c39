"""Device seconds of the admission programs (``jit_prefill*``,
``jit_paged_suffix_prefill``, ``jit_paged_gather``, ``jit_paged_admit``; the
profiler may append an id, so names are matched by prefix) over the busy
seconds of the traced window. A program whose jitted functions carry no stable
names gives nothing."""

ADMIT_PROGRAMS = ("jit_prefill", "jit_paged_suffix_prefill", "jit_paged_gather", "jit_paged_admit")


def value(module_seconds: dict, busy_s: float):
    """``module_seconds``: program name -> (executions, device seconds)."""
    admit = [sec for name, (_, sec) in module_seconds.items() if name.startswith(ADMIT_PROGRAMS)]
    return 100.0 * sum(admit) / busy_s if admit and busy_s > 0 else None


def read(run):
    t = run.get("trace")
    if not t or run["ctx"].peaks is None:
        return None
    mods = run["ctx"].tracer.reduce.module_seconds(t["raw"], t["lo"], t["hi"])
    return value(mods, t["busy_s"])

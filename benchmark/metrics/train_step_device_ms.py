"""Device busy time of the traced train() call over its steps."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("steps"):
        return None
    return 1e3 * t["busy_s"] / t["steps"]

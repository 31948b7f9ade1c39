"""1 - union of device-op intervals over the traced train() call."""


def read(run):
    t = run.get("trace")
    return None if not t else t["idle_pct"]

"""Mean of engine.stats()['slots_active'] sampled through the window, over the slots."""


def read(run):
    w = run["window"]
    if not w["slots_active"]:
        return None
    return 100.0 * sum(w["slots_active"]) / len(w["slots_active"]) / w["slots_total"]

"""The most-loaded held expert's (token, expert) pairs over the mean of the held
experts', over the window (prefill and decode together): the engine's
``stats()["moe_expert_load"]``, the program's own count of what the routing sent
to each expert it holds. 1 is an even load; a straggler among the experts reads
higher."""


def read(run):
    load = run["window"].get("moe_expert_load")
    if not load or sum(load) <= 0:
        return None
    return max(load) / (sum(load) / len(load))

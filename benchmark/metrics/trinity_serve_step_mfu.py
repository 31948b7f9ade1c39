"""Required FLOPs of the window's serving of a model with window and full
attention layers and routed experts (benchmark/flops_trinity.py: the attention's
five matrices, the dense layer, the shared expert and the router for every
prompt token computed and every output token, the head where a token is sampled,
attention over the keys each query SEES (a window layer's at most
``sliding_window``), and 2 x an expert's weights for each (token, expert) pair
the ROUTING made: the program's counter ``serving.moe.local_picks``, through
``engine.stats()``) over its seconds times the bf16 peak: the share of the
whole step."""


def read(run):
    peaks, w = run["ctx"].peaks, run["window"]
    if peaks is None or not w["ok"] or "sliding_window" not in run["ctx"].config or "flops" not in w:
        return None
    chips = run["ctx"].cell.chips
    return 100.0 * w["flops"] / (w["seconds"] * peaks["bf16_flops_per_s"] * chips)

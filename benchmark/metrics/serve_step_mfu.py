"""Required FLOPs of the window's serving (benchmark/flops.py: blocks for every
prompt token computed and every output token, the head where a token is
sampled, attention over the context) over its seconds times the bf16 peak."""


def read(run):
    peaks, w = run["ctx"].peaks, run["window"]
    if peaks is None or not w["ok"]:
        return None
    chips = run["ctx"].cell.chips
    return 100.0 * w["flops"] / (w["seconds"] * peaks["bf16_flops_per_s"] * chips)

"""Required FLOPs of the window's steps (benchmark/flops.py: forward 2N, input
gradients 2N, adapters, causal attention; no frozen dW, no recomputation) over
the window's seconds times the chip's bf16 peak."""


def read(run):
    peaks, w = run["ctx"].peaks, run["window"]
    if peaks is None:
        return None
    chips = run["ctx"].cell.chips
    return 100.0 * w["step_flops"] * w["steps"] / (w["seconds"] * peaks["bf16_flops_per_s"] * chips)

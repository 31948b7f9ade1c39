"""Required FLOPs of the window's serving of a hybrid model
(benchmark/flops_jamba.py: the blocks' matmuls, the scan and the convolution of
the Mamba layers for every prompt token computed and every output token,
attention over the context in the attention layers, the tied head where a token
is sampled) over its seconds times the bf16 peak."""


def read(run):
    peaks, w = run["ctx"].peaks, run["window"]
    if peaks is None or not w["ok"] or "prefill_passes" not in w:
        return None
    chips = run["ctx"].cell.chips
    return 100.0 * w["flops"] / (w["seconds"] * peaks["bf16_flops_per_s"] * chips)

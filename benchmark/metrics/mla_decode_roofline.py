"""Least time the chip could take for the latent decode kernel's calls of the
traced window over their summed device time. The calls are found in the trace
by the kernel's own name (``paged_latent_attention``: ``trace/reduce.py`` labels
every Mosaic call ``<name>[mosaic:..]``): one a layer a token-step, so
(decode chunks traced) x decode_chunk x layers calls. A call's least time is
the larger of its FLOPs over the bf16 peak and its bytes over the HBM bandwidth
(benchmark/flops_pangu.py: every head against the 576-wide row and the 512-wide
value of each live token, each latent read once: 242 FLOPs a byte, beside the
v5e's ridge of 240), at the mean of the tokens live in the window
(``engine.stats()``)."""

import flops_pangu

KERNEL = "paged_latent_attention"


def kernel_seconds(t) -> float:
    return sum(sec for name, sec in t["op_seconds"].items() if name.startswith(KERNEL))


def read(run):
    t, peaks = run.get("trace"), run["ctx"].peaks
    if not t or peaks is None or not t.get("chunks") or "kv_lora_rank" not in run["ctx"].config:
        return None
    cfg, w = run["ctx"].config, run["window"]
    spent = kernel_seconds(t)
    if spent <= 0.0 or not w["kv_tokens_live"]:
        return None
    live = sum(w["kv_tokens_live"]) / len(w["kv_tokens_live"])
    fl, by = flops_pangu.mla_decode_call_cost(cfg, live)
    calls = t["chunks"] * w["decode_chunk"] * cfg["num_hidden_layers"]
    least = calls * max(fl / peaks["bf16_flops_per_s"], by / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent

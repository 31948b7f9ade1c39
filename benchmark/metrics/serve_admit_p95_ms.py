"""p95 over the requests popped in the window of ``serving.request.admit``:
popped into a wave -> first token on the host (prefix match, page
reservation, prefill or gather + suffix prefill, pool scatter, host sync)."""

import program_spans as ps


def value(run):
    return ps.percentile_ms(ps.durations(run, "serving.request.admit"), 95.0)


read = ps.chip_only(value)

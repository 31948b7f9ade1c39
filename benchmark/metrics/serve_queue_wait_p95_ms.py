"""p95 over the requests enqueued in the window of ``serving.request.queue``:
enqueued in ``engine.submit`` -> popped into an admission wave."""

import program_spans as ps


def value(run):
    return ps.percentile_ms(ps.durations(run, "serving.request.queue"), 95.0)


read = ps.chip_only(value)

"""p95 per request, joined on ``request_id``, of ``serving.endpoint.predict``
minus ``serving.predict.wait``: what the entry itself takes around the
engine, which is the router, HTTP both ways, JSON and the tokenizer."""

import program_spans as ps


def value(run):
    whole = ps.by_request(run, "serving.endpoint.predict")
    waited = ps.by_request(run, "serving.predict.wait", in_window=False)
    return ps.percentile_ms([whole[r] - waited[r] for r in whole if r in waited], 95.0)


read = ps.chip_only(value)

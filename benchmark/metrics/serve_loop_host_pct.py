"""Share of the worker loop's time that is host work of a decode chunk:
summed ``serving.cb.chunk.dispatch`` (host arrays -> the call returns) and
``serving.cb.chunk.post`` (mirrors, token loop, finishes) over summed
``serving.engine.iteration``. ``.sync``, the wait for the device, is not in it."""

import program_spans as ps


def value(run):
    host = ps.total(run, "serving.cb.chunk.dispatch") + ps.total(run, "serving.cb.chunk.post")
    return ps.share_pct(host, ps.total(run, "serving.engine.iteration"))


read = ps.chip_only(value)

"""Share of the worker loop's time spent admitting: summed
``serving.paged.admit_wave`` over summed ``serving.engine.iteration``
(passes of the loop that started in the window)."""

import program_spans as ps


def value(run):
    return ps.share_pct(ps.total(run, "serving.paged.admit_wave"), ps.total(run, "serving.engine.iteration"))


read = ps.chip_only(value)

"""``serve_device_starved_pct``'s pieces whose ``phase`` is ``no_work``: the chip
had nothing queued while the worker had nothing to launch (the wait of
``serving.engine.idle``, the backpressure sleep). Over the
SAME seconds as the sum, so the four phases add up to it."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.starved_pct(run, "no_work")


read = ps.chip_only(value)

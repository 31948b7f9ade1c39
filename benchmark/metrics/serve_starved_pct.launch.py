"""``serve_device_starved_pct``'s pieces whose ``phase`` is ``launch``: the chip
had nothing queued while the worker was inside a launch (``serving.cb.prefill``,
``serving.paged.transfer``, the window slide + uploads + ``.dispatch``). Over the
SAME seconds as the sum, so the four phases add up to it."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.starved_pct(run, "launch")


read = ps.chip_only(value)

"""``serve_device_starved_pct``'s pieces whose ``phase`` is ``collect``: the chip
had nothing queued while the worker was collecting a wave (``_collect_wave``, the
loop's top). Over the
SAME seconds as the sum, so the four phases add up to it."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.starved_pct(run, "collect")


read = ps.chip_only(value)

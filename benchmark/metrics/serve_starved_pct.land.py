"""``serve_device_starved_pct``'s pieces whose ``phase`` is ``land``: the chip
had nothing queued while the worker was landing results (``.sync`` / ``.post`` /
``first_token_wait`` / ``serving.paged.admit``). Over the
SAME seconds as the sum, so the four phases add up to it."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.starved_pct(run, "land")


read = ps.chip_only(value)

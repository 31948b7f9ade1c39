"""Traced device idle seconds that fall inside a ``serving.device.starved`` piece
(its ``unseen_ns`` head included) over traced idle seconds: does the program
see what the chip sees. Nothing where idle is under 1 % of the traced part,
the driver keeps no ``trace_t0``, or the anchor fails its check."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.idle_in_starved_pct(run)


read = ps.chip_only(value)

"""Share of the seconds looked at (the traced part where the driver keeps
``trace_t0``, else the window) in which the chip had nothing queued by the
engine's own account: the summed ``serving.device.starved`` pieces. The same
quantity as ``device_idle_pct.serve`` from inside the program, and a lower
bound of it (``idle_by_span``); ``value`` takes any run, traced or not."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.starved_pct(run)


read = ps.chip_only(value)

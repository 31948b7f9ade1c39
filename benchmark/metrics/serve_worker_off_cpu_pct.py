"""Share of the worker's passes (``serving.engine.iteration``) in which its thread
was neither on the CPU nor blocked in a fetch that waits for the chip:
sum(wall - ``cpu_ns`` - ``blocked_ns``) over sum(wall). Descheduled, or waiting
for the GIL or a lock."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.off_cpu_pct(run)


read = ps.chip_only(value)

"""Share of the worker's passes spent ACQUIRING the engine lock and the
allocator's: sum(``lock_wait_ns``) over sum(wall) of ``serving.engine.iteration``."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.lock_wait_pct(run)


read = ps.chip_only(value)

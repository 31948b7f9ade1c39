"""Traced device idle seconds under which the worker's deepest span is the
iteration itself or nothing, over traced idle seconds: what the program's
spans still do not name. Nothing where ``serve_idle_in_starved_pct`` is."""

import idle_by_span
import program_spans as ps


def value(run):
    return idle_by_span.idle_unnamed_pct(run)


read = ps.chip_only(value)

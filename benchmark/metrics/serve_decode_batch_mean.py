"""Mean live slots of a decode chunk: the ``slots`` attribute of the
``serving.cb.chunk`` spans that started in the window."""

import program_spans as ps


def value(run):
    slots = [s["attrs"]["slots"] for s in ps.spans(run, "serving.cb.chunk") if "slots" in s["attrs"]]
    return sum(slots) / len(slots) if slots else None


read = ps.chip_only(value)

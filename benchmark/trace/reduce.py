"""Device trace -> numbers. Three jobs and no more.

(a) the union of the device-op intervals inside a window -> busy seconds and
    idle share, averaged over the device planes;
(b) device time by op name (self time: an op that encloses others, such as a
    ``while``, keeps only what its children leave) -> the top of the list, so
    that the Mosaic calls and the jitted programs are found by name;
(c) the longest idle gaps, each named by the benchmark's own host span
    (``jax.profiler.TraceAnnotation("bench:<name>")``) open at its middle.

Reads the profiler's ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
nothing else. The reduction itself works on plain tuples, so a test can feed
it a hand-made event list.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_HEAD = re.compile(r"%?([\w.\-]+) = (.*?) (custom-call|fusion)\(")
_PARAM = re.compile(r"%params__([A-Za-z0-9_]+?)__(?:\.\d+)?[,) ]")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def mosaic_kind(text: str) -> str:
    """Which flash-attention kernel a Mosaic call is, by what it returns: the
    forward returns the output and a [.., T, 1] row statistic, dK/dV returns
    two results of one shape, dQ one."""
    m = _HEAD.match(text)
    shapes = _SHAPE.findall(m.group(2)) if m else []
    if any(sh.endswith(",1") for sh in shapes):
        return "fwd"
    if len(shapes) == 2 and shapes[0] == shapes[1]:
        return "dkv"
    return "dq"


def short_label(text: str) -> str:
    """A device op's name for people: the HLO instruction's own name, with the
    weight it reads (layers starred together) or the Mosaic kernel's kind. The
    profiler gives the whole instruction text as the name."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    if not m:
        return text[:80]
    short = m.group(1)
    if MOSAIC in text:
        return f"{re.sub(r'[.]\d+$', '', short)}[mosaic:{mosaic_kind(text)}]"
    p = _PARAM.search(text)
    if p:
        path = [x for x in p.group(1).split("____") if x not in ("kernel", "embedding", "scale")]
        hint = ".".join(re.sub(r"^layer_\d+$", "layer_*", x) for x in path)
        return f"{re.sub(r'[.]\d+$', '', short)}[{hint}]"
    return short


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    device_ops: Dict[str, List[Event]]      # device plane -> op events
    device_modules: Dict[str, List[Event]]  # device plane -> program executions
    host_spans: List[Event]                 # the benchmark's own annotations


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, cpu_rehearsal: bool = False) -> Trace:
    """``cpu_rehearsal`` (tests only): with no TPU plane, the CPU client's
    executor threads stand in for a device so that a traced run can be driven
    end to end; what it yields is never a device number."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(short_label(e.name), e.start_ns, e.duration_ns)
                                       for e in line.events]
                elif line.name == MODULES_LINE:
                    mods[plane.name] = [Event(e.name, e.start_ns, e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name[len(SPAN_PREFIX):], e.start_ns, e.duration_ns))
                if cpu_rehearsal and line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.setdefault("cpu", []).extend(
                        Event(e.name, e.start_ns, e.duration_ns) for e in line.events
                        if e.duration_ns > 0 and not e.name.startswith("ThreadpoolListener"))
    return Trace(ops, mods, spans)


def merged_intervals(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Union of [start, end) clipped to [lo, hi), as sorted disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((max(ev.start_ns, lo), min(ev.end_ns, hi)) for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event], lo: float, hi: float) -> Dict[str, float]:
    """ns by op name inside [lo, hi); an enclosing op loses its children's time."""
    total: Dict[str, float] = collections.defaultdict(float)
    stack: List[Event] = []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        if ev.end_ns <= lo or ev.start_ns >= hi:
            continue
        while stack and stack[-1].end_ns <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= stack[-1].end_ns:  # enclosed: a child, not an overlap
            total[stack[-1].name] -= ev.dur_ns
        total[ev.name] += ev.dur_ns
        stack.append(ev)
    return dict(total)


def span_at(spans: Sequence[Event], t_ns: float) -> str:
    """The innermost (shortest) of the benchmark's spans open at ``t_ns``."""
    open_ = [s for s in spans if s.start_ns <= t_ns < s.end_ns]
    return min(open_, key=lambda s: s.dur_ns).name if open_ else "no_span"


def device_extent(trace: Trace) -> Tuple[float, float]:
    evs = [e for lst in trace.device_ops.values() for e in lst]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None, top: int = 10) -> dict:
    """``window`` (lo_ns, hi_ns) defaults to the extent of the device ops."""
    lo, hi = window if window is not None else device_extent(trace)
    if hi <= lo:
        raise ValueError("traced window has no length")
    busy, by_name = [], collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane, events in sorted(trace.device_ops.items()):
        merged = merged_intervals(events, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_times(events, lo, hi).items():
            by_name[name] += ns
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not busy:
        raise ValueError("the trace holds no device plane")
    n_dev = len(busy)
    busy_s = sum(busy) / n_dev / 1e9
    window_s = (hi - lo) / 1e9
    gap_by_span: Dict[str, float] = collections.defaultdict(float)
    for s, e in gaps:
        gap_by_span[span_at(trace.host_spans, (s + e) / 2.0)] += (e - s) / n_dev / 1e9
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "op_seconds": {k: v / n_dev / 1e9 for k, v in by_name.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[span_at(trace.host_spans, (s + e) / 2.0), (e - s) / 1e9] for s, e in longest],
        "idle_by_span_s": dict(gap_by_span),
        "n_devices": n_dev,
    }


def module_seconds(trace: Trace, lo: float, hi: float) -> Dict[str, Tuple[int, float]]:
    """program name -> (executions, device seconds) inside [lo, hi), first plane."""
    out: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for plane in sorted(trace.device_modules)[:1]:
        for ev in trace.device_modules[plane]:
            if ev.start_ns >= lo and ev.end_ns <= hi:
                out[ev.name][0] += 1
                out[ev.name][1] += ev.dur_ns / 1e9
    return {k: (int(v[0]), v[1]) for k, v in out.items()}

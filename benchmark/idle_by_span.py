"""The program's starvation ledger and its worker's timeline, read after a
run, and (in a traced run) laid over the device trace on ONE clock.

Three things, all from records the program keeps itself
(``fedml_tpu/serving/continuous_batching._DeviceLedger``, through
``program_spans.snapshot``):

(a) ``serving.device.starved`` pieces: when the chip had nothing queued by the
    engine's own account, each piece in one ``phase`` of the worker
    (``no_work`` / ``collect`` / ``launch`` / ``land``). ``starved_pct`` is
    their share of the seconds looked at: the traced part where the driver
    keeps ``trace_t0`` / ``trace_t1`` on its window, else the whole window.
    The pieces are clipped to those seconds, so a share cannot pass 100.
(b) the worker's own account of a pass (attributes of
    ``serving.engine.iteration``): ``off_cpu_pct``, ``lock_wait_pct``.
(c) in a traced run whose driver kept ``trace_t0``: the device's idle gaps
    (the reducer's own ``merged_intervals`` over the device ops of the traced
    part) INTERSECTED with the worker's timeline (the deepest of its spans open
    at each instant) and with the starved pieces. The profiler's times are
    relative to its session's start, the registry's are ``perf_counter`` ones:
    the anchor is ``bench:trace_window``'s start against ``trace_t0``, read on
    the line before that span was entered, and it checks itself by the other
    end (``trace_t1`` against the span's end). ``report`` logs idle seconds by
    the worker's span and the ten longest gaps with the spans under each,
    once a traced run, through ``ctx.log``.

Every function gives ``None`` (or nothing) on a program without these records:
the parent of the PR that added them, a registry that is off.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import program_spans as ps

STARVED = "serving.device.starved"
ITERATION = "serving.engine.iteration"
PHASES = ("no_work", "collect", "launch", "land")  # the program's ``STARVED_PHASES``; its parent has no such name
#: records the worker's thread makes that are not what it was doing (``record_span``: a request's
#: queue / admit / decode intervals, the ledger's own pieces)
NOT_ACTIVITY = ("serving.request.", "serving.device.")
NOTHING = "(no span)"
ANCHOR_TOLERANCE_NS = 2e6
MIN_IDLE_SHARE = 0.01     # of the traced part: under it the two coverage shares say nothing
REPORT_KEY = "idle_by_span_report"

Interval = Tuple[float, float]


# ---- (a), (b): the ledger and the worker's account, any run ------------------------------------


def bounds(run: dict) -> Interval:
    """The seconds looked at, on the ``time.perf_counter()`` clock."""
    w = run["window"]
    if "trace_t0" in w and "trace_t1" in w:
        return w["trace_t0"], w["trace_t1"]
    return ps.window_bounds(run)


def _records(run: dict, name: str) -> List[dict]:
    return ps.spans(run, name, in_window=False)


def iterations(run: dict) -> List[dict]:
    """The worker's passes that touch the seconds looked at and carry its account."""
    lo, hi = bounds(run)
    return [s for s in _records(run, ITERATION)
            if s["start_s"] <= hi and s["start_s"] + s["dur_s"] >= lo and "starved_ns" in s["attrs"]]


def has_ledger(run: dict) -> bool:
    """The program keeps the ledger: its iterations carry the worker's account."""
    return bool(iterations(run))


def pieces(run: dict) -> List[dict]:
    """Starved pieces that touch the seconds looked at, clipped to them:
    {lo_s, hi_s, phase, first, unseen_s}; the unseen head is not clipped."""
    lo, hi = bounds(run)
    out = []
    for s in _records(run, STARVED):
        a, b = max(s["start_s"], lo), min(s["start_s"] + s["dur_s"], hi)
        if b >= a:  # a piece of no length (a doubt seen at a launch's return) counts by its unseen head
            out.append({"lo_s": a, "hi_s": b, "phase": s["attrs"].get("phase"), "first": bool(s["attrs"].get("first")),
                        "unseen_s": s["attrs"].get("unseen_ns", 0) / 1e9 if s["start_s"] >= lo else 0.0})
    return out


def starved_pct(run: dict, phase: Optional[str] = None) -> Optional[float]:
    if not has_ledger(run):
        return None
    lo, hi = bounds(run)
    total = sum(p["hi_s"] - p["lo_s"] for p in pieces(run) if phase in (None, p["phase"]))
    return ps.share_pct(total, hi - lo)


def off_cpu_pct(run: dict) -> Optional[float]:
    """Share of the passes' wall time the worker neither computed nor waited for the chip."""
    its = iterations(run)
    wall = sum(s["dur_s"] for s in its)
    off = sum(max(0.0, s["dur_s"] - (s["attrs"]["cpu_ns"] + s["attrs"]["blocked_ns"]) / 1e9) for s in its)
    return ps.share_pct(off, wall) if its else None


def lock_wait_pct(run: dict) -> Optional[float]:
    its = iterations(run)
    return ps.share_pct(sum(s["attrs"]["lock_wait_ns"] for s in its) / 1e9, sum(s["dur_s"] for s in its)) if its else None


# ---- (c): one clock with the device trace -------------------------------------------------------


def anchor_ns(trace_window: Optional[Tuple[float, float]], trace_t0: Optional[float],
              trace_t1: Optional[float]) -> Optional[float]:
    """Profiler ns = ``perf_counter`` ns + this. ``trace_window``: (start_ns,
    end_ns) of ``bench:trace_window`` on the profiler's clock; ``trace_t0`` /
    ``trace_t1``: ``perf_counter()`` read just before it was entered and just
    after it was left. ``None`` without them, or where the two ends disagree
    by over 2 ms (a thread that lost the CPU between the reading and the span)."""
    if trace_window is None or trace_t0 is None or trace_t1 is None:
        return None
    at_start = trace_window[0] - trace_t0 * 1e9
    at_end = trace_window[1] - trace_t1 * 1e9
    return at_start if abs(at_start - at_end) <= ANCHOR_TOLERANCE_NS else None


def flatten(spans: Sequence[Tuple[float, float, int, str]]) -> List[Tuple[float, float, str]]:
    """One thread's nested spans (t0, t1, depth, name) -> disjoint segments
    (t0, t1, name of the DEEPEST span open there), in time order. Instants no
    span covers get no segment."""
    edges = []
    for t0, t1, depth, name in spans:
        if t1 > t0:
            edges.append((t0, 1, depth, name))
            edges.append((t1, 0, depth, name))
    edges.sort(key=lambda e: (e[0], e[1]))  # at one instant a span closes before the next opens
    open_: Dict[int, str] = {}
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, opens, depth, name in edges:
        if open_ and prev is not None and t > prev:
            out.append((prev, t, open_[max(open_)]))
        if opens:
            open_[depth] = name
        else:
            open_.pop(depth, None)
        prev = t
    return out


def overlap_by_name(gap: Interval, segments: Sequence[Tuple[float, float, str]], starts: Sequence[float]) -> Dict[str, float]:
    """Length of ``gap`` under each segment's name; what no segment covers is ``NOTHING``."""
    lo, hi = gap
    out: Dict[str, float] = {}
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(segments) and segments[i][0] < hi:
        a, b = max(segments[i][0], lo), min(segments[i][1], hi)
        if b > a:
            out[segments[i][2]] = out.get(segments[i][2], 0.0) + (b - a)
            covered += b - a
        i += 1
    if hi - lo - covered > 1e-3:  # ns: not a float's rounding
        out[NOTHING] = hi - lo - covered
    return out


def attribute(gaps: Sequence[Interval], worker: Sequence[Tuple[float, float, int, str]],
              starved: Sequence[Tuple[float, float, str]], top: int = 10) -> dict:
    """Device idle ``gaps`` against the worker's spans and the starved pieces
    (``(t0, t1, phase)``, unseen heads included by the caller), all on one
    clock in ns -> idle seconds by the worker's deepest span, by starved phase,
    and the ``top`` longest gaps with what lies under each."""
    segs = flatten(worker)
    seg_starts = [s[0] for s in segs]
    pcs = sorted(starved)
    pc_starts = [p[0] for p in pcs]
    by_name: Dict[str, float] = {}
    in_starved: Dict[str, float] = {}
    for gap in gaps:
        for name, ns in overlap_by_name(gap, segs, seg_starts).items():
            by_name[name] = by_name.get(name, 0.0) + ns / 1e9
        for phase, ns in overlap_by_name(gap, pcs, pc_starts).items():
            if phase != NOTHING:
                in_starved[phase] = in_starved.get(phase, 0.0) + ns / 1e9
    longest = []
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        under = overlap_by_name(gap, segs, seg_starts)
        seen = overlap_by_name(gap, pcs, pc_starts)
        longest.append({"start_ns": gap[0], "seconds": (gap[1] - gap[0]) / 1e9,
                        "worker": {k: v / 1e9 for k, v in sorted(under.items(), key=lambda kv: -kv[1])},
                        "starved": {k: v / 1e9 for k, v in seen.items() if k != NOTHING}})
    return {"idle_s": sum(b - a for a, b in gaps) / 1e9, "by_name": by_name, "in_starved": in_starved,
            "longest": longest}


def worker_spans(run: dict, offset_ns: float) -> Tuple[list, list]:
    """(the worker's nested spans, the starved pieces with their unseen heads), profiler ns."""
    snap = ps.snapshot(run)
    if snap is None:
        return [], []
    epoch = snap["epoch_perf_ns"] + offset_ns
    tids = {r["tid"] for r in snap["spans"] if r["name"] == ITERATION}
    worker, starved = [], []
    for r in snap["spans"]:
        if r["tid"] not in tids:
            continue
        t0 = epoch + r["t0_ns"]
        if r["name"] == STARVED:
            attrs = r.get("attrs") or {}
            starved.append((t0 - attrs.get("unseen_ns", 0), t0 + r["dur_ns"], str(attrs.get("phase"))))
        elif not r["name"].startswith(NOT_ACTIVITY):
            worker.append((t0, t0 + r["dur_ns"], r["depth"], r["name"]))
    return worker, starved


def _trace_window(raw) -> Optional[Tuple[float, float]]:
    found = [s for s in raw.host_spans if s.name == "trace_window"]
    return (found[0].start_ns, found[0].end_ns) if found else None


def report(run: dict) -> Optional[dict]:
    """``attribute`` over the traced part of ``run`` (cached on it; logged
    once). ``None``: not traced, no ``trace_t0`` on the window, an anchor that
    fails its check, or a program without an iteration span."""
    if REPORT_KEY in run:
        return run[REPORT_KEY]
    run[REPORT_KEY] = out = _report(run)
    ctx = run.get("ctx")
    if out is not None and ctx is not None:
        for line in lines(out):
            ctx.log(line)
    return out


def _report(run: dict) -> Optional[dict]:
    t, w = run.get("trace"), run["window"]
    if not t:
        return None
    offset = anchor_ns(_trace_window(t["raw"]), w.get("trace_t0"), w.get("trace_t1"))
    if offset is None:
        return None
    worker, starved = worker_spans(run, offset)
    if not worker:
        return None
    lo, hi = t["lo"], t["hi"]
    first_plane = min(t["raw"].device_ops)  # the cells that keep the anchor run on one chip
    busy = run["ctx"].tracer.reduce.merged_intervals(t["raw"].device_ops[first_plane], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    out = attribute(gaps, worker, starved)
    out.update(window_s=(hi - lo) / 1e9, lo_ns=lo, offset_ns=offset, has_ledger=has_ledger(run))
    return out


def lines(out: dict) -> List[str]:
    """The report for people: the builder's and the next planner's reading."""
    idle = out["idle_s"]
    head = (f"idle_by_span: device idle {idle:.4f} s of the traced {out['window_s']:.2f} s; "
            f"inside the program's starved pieces {sum(out['in_starved'].values()):.4f} s "
            f"{ {k: round(v, 4) for k, v in sorted(out['in_starved'].items())} }")
    rows = [head, "idle_by_span: idle seconds by the worker's deepest span: " + ", ".join(
        f"{name} {sec:.4f}" for name, sec in sorted(out["by_name"].items(), key=lambda kv: -kv[1]))]
    for g in out["longest"]:
        under = ", ".join(f"{k} {v * 1e3:.1f}" for k, v in g["worker"].items())
        seen = ", ".join(f"{k} {v * 1e3:.1f}" for k, v in g["starved"].items()) or "none"
        rows.append(f"idle_by_span: gap {g['seconds'] * 1e3:.1f} ms at {(g['start_ns'] - out['lo_ns']) / 1e9:.3f} s of the traced part: "
                    f"worker ms [{under}]; starved ms [{seen}]")
    return rows


def _enough_idle(run: dict) -> Optional[dict]:
    """The report where the two coverage shares mean something: a program with
    the ledger, idle at least ``MIN_IDLE_SHARE`` of the traced part."""
    out = report(run)
    return out if out is not None and out["has_ledger"] and out["idle_s"] >= MIN_IDLE_SHARE * out["window_s"] else None


def idle_in_starved_pct(run: dict) -> Optional[float]:
    """Traced idle seconds inside a starved piece (its unseen head included)
    over traced idle seconds: does the program see what the chip sees."""
    out = _enough_idle(run)
    return None if out is None else ps.share_pct(sum(out["in_starved"].values()), out["idle_s"])


def idle_unnamed_pct(run: dict) -> Optional[float]:
    """Traced idle seconds under which the worker's deepest span is the
    iteration itself or nothing, over traced idle seconds."""
    out = _enough_idle(run)
    return None if out is None else ps.share_pct(
        out["by_name"].get(ITERATION, 0.0) + out["by_name"].get(NOTHING, 0.0), out["idle_s"])

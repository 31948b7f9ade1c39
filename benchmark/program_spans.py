"""The program's own spans, read after a run: records of the default
telemetry registry (``fedml_tpu.core.telemetry``), by name, whose START lies
inside the measured window.

The window's bounds are ``time.perf_counter()`` readings of the driver
(``window.t_start..t_close`` of the chat driver, ``window.t0..t1`` of the
train driver); the registry's spans are on the same clock, offset by the
``epoch_perf_ns`` its snapshot gives. Warm-up and the drain fall outside.

A program whose registry has no ``epoch_perf_ns`` (one from before the spans
these metrics read existed), or whose registry is off (``FEDML_TELEMETRY=0``),
gives nothing: every reader built on this returns ``None`` then.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import stats

SNAPSHOT_KEY = "program_span_snapshot"  # cached on the run: one copy for all readers


def snapshot(run: dict) -> Optional[dict]:
    if SNAPSHOT_KEY not in run:
        try:
            from fedml_tpu.core import telemetry as tel
            snap = tel.snapshot()
        except Exception:  # noqa: BLE001 - a program without the registry has no spans to read
            snap = None
        run[SNAPSHOT_KEY] = snap if snap and "epoch_perf_ns" in snap else None
    return run[SNAPSHOT_KEY]


def window_bounds(run: dict) -> Tuple[float, float]:
    w = run["window"]
    return (w["t_start"], w["t_close"]) if "t_close" in w else (w["t0"], w["t1"])


def spans(run: dict, name: str, in_window: bool = True) -> List[dict]:
    """Records named ``name`` as {start_s, dur_s, attrs}; ``start_s`` on the
    ``time.perf_counter()`` clock. With ``in_window`` only those that started
    inside the measured window."""
    snap = snapshot(run)
    if snap is None:
        return []
    lo, hi = window_bounds(run)
    epoch = snap["epoch_perf_ns"]
    out = []
    for r in snap["spans"]:
        if r["name"] != name:
            continue
        start_s = (epoch + r["t0_ns"]) / 1e9
        if in_window and not lo <= start_s <= hi:
            continue
        out.append({"start_s": start_s, "dur_s": r["dur_ns"] / 1e9, "attrs": r.get("attrs") or {}})
    return out


def durations(run: dict, name: str) -> List[float]:
    return [s["dur_s"] for s in spans(run, name)]


def total(run: dict, name: str) -> float:
    return sum(durations(run, name))


def percentile_ms(xs: List[float], q: float) -> Optional[float]:
    return 1e3 * stats.percentile(xs, q) if xs else None


def share_pct(part_s: float, whole_s: float) -> Optional[float]:
    return 100.0 * part_s / whole_s if whole_s > 0 else None


def by_request(run: dict, name: str, in_window: bool = True) -> Dict[str, float]:
    """request_id -> summed seconds of its spans named ``name``."""
    out: Dict[str, float] = {}
    for s in spans(run, name, in_window):
        rid = s["attrs"].get("request_id")
        if rid is not None:
            out[rid] = out.get(rid, 0.0) + s["dur_s"]
    return out


def chip_only(value):
    """``read`` of a span metric from its computing function. Times are
    device-run numbers: like the roofline and MFU readers, a span reader says
    nothing where there is no chip (``ctx.peaks is None``); the computing
    function is what the tests call."""
    return lambda run: value(run) if run["ctx"].peaks is not None else None

"""Device-performance observability: the compiled-program cost registry.

``bench.py`` computes MFU once per window and throws the compile-time facts
away; this module keeps them live. :func:`instrument` wraps an
already-jitted step: the FIRST call lowers and compiles it ahead-of-time
(one trace — the same one the jit dispatcher would have spent, so
instrumented steps stay zero-recompile) and captures the executable's
``cost_analysis()`` FLOPs / bytes-accessed plus its memory analysis; every
later call dispatches the cached executable directly. Callers then fold
MEASURED wall time in via :func:`observe_step` / :func:`observe_window`
(per-call wall-timing of an async-dispatched program would measure dispatch
latency, not device time — the fold sites are the places that already block
on results: the trainer's window fetch, the serving chunk's token sync).

Each fold updates the program's achieved FLOPs/s, its MFU against the
per-device-kind peak table (``core/distributed/device_specs.py``), and its
roofline point (operational intensity vs the device's ridge →
compute-bound / bandwidth-bound verdict), and emits:

- counters ``program.flops.<label>`` / ``program.steps.<label>`` →
  ``fedml_program_flops_total{program=}`` / ``fedml_program_steps_total{program=}``;
- tsdb gauges ``devperf.mfu.<label>`` (the SLO engine's ``mfu_collapse``
  alert keys on the glob) — recorded only while a tsdb store is installed;
- ride-along prom gauges ``fedml_device_mfu{program=}`` /
  ``fedml_device_flops_per_sec{program=}`` via :func:`prom_gauges`.

:class:`HbmSampler` is the low-overhead memory side: a daemon thread reads
every local device's ``memory_stats()`` on a fixed cadence into live +
high-water gauges (``fedml_device_hbm_bytes{device=}`` /
``fedml_device_hbm_high_water_bytes{device=}``) and the tsdb series
``devperf.hbm_high_water_frac`` that the ``hbm_high_water`` SLO watches.

Everything self-accounts its own cost into ``overhead_ns`` so the
``bench.py --stage devperf_overhead`` guard can bill the registry against
the loop it watches. ``FEDML_DEVPERF=0`` disables the whole layer
(:func:`instrument` returns the fn unchanged, folds and the sampler no-op).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..distributed import device_specs
from . import prom, tsdb
from .core import get_telemetry

log = logging.getLogger(__name__)

__all__ = [
    "CompiledProgramRegistry",
    "HbmSampler",
    "enabled",
    "get_registry",
    "instrument",
    "observe_step",
    "observe_window",
    "prom_gauges",
    "reset",
    "snapshot",
    "start_hbm_sampler",
    "statusz_snapshot",
    "stop_hbm_sampler",
]

_ENV_DISABLE = "FEDML_DEVPERF"
_ENV_HBM_INTERVAL = "FEDML_DEVPERF_HBM_INTERVAL_S"

FLOPS_SOURCE_ANALYTIC = "caller_analytic"
FLOPS_SOURCE_XLA = "cost_analysis"

VERDICT_COMPUTE = "compute-bound"
VERDICT_BANDWIDTH = "bandwidth-bound"

# fedml_program_* counter families: bounded cardinality (one value per
# instrumented step label — a handful per process, fixed at wiring time)
prom.register_prefix_family(
    "program.flops.", ("program",),
    "device FLOPs executed per instrumented compiled program")
prom.register_prefix_family(
    "program.steps.", ("program",),
    "measured step count per instrumented compiled program")


def enabled() -> bool:
    return os.environ.get(_ENV_DISABLE, "1") != "0"


class ProgramRecord:
    """Mutable per-program row; all mutation happens under the registry
    lock, readers get dict copies via :meth:`as_dict`."""

    __slots__ = (
        "label", "n_devices", "device_kind", "captured", "aot",
        "flops_xla", "flops_hint", "flops_per_token_hint", "flops_source",
        "bytes_accessed", "memory", "peak_flops_per_sec",
        "op_intensity", "ridge", "roofline_verdict",
        "calls", "steps", "tokens", "device_seconds",
        "last_step_wall_s", "last_flops_per_sec", "last_mfu",
    )

    def __init__(self, label: str, n_devices: int,
                 flops_hint: Optional[float],
                 flops_per_token_hint: Optional[float]):
        self.label = label
        self.n_devices = max(1, int(n_devices))
        self.device_kind = ""
        self.captured = False
        self.aot = False
        self.flops_xla: Optional[float] = None
        self.flops_hint = flops_hint
        self.flops_per_token_hint = flops_per_token_hint
        self.flops_source: Optional[str] = None
        self.bytes_accessed: Optional[float] = None
        self.memory: Dict[str, int] = {}
        self.peak_flops_per_sec: Optional[float] = None
        self.op_intensity: Optional[float] = None
        self.ridge: Optional[float] = None
        self.roofline_verdict: Optional[str] = None
        self.calls = 0
        self.steps = 0
        self.tokens = 0
        self.device_seconds = 0.0
        self.last_step_wall_s: Optional[float] = None
        self.last_flops_per_sec: Optional[float] = None
        self.last_mfu: Optional[float] = None

    def step_flops(self, tokens_per_step: Optional[float]) -> Optional[float]:
        """FLOPs per step: caller-provided model FLOPs win over XLA's
        hardware FLOPs (MFU is defined on model FLOPs; cost_analysis also
        counts recompute and masked-out work)."""
        if self.flops_per_token_hint is not None and tokens_per_step:
            return self.flops_per_token_hint * tokens_per_step
        if self.flops_hint is not None:
            return self.flops_hint
        return self.flops_xla

    def as_dict(self) -> Dict[str, Any]:
        return {
            "label": self.label,
            "n_devices": self.n_devices,
            "device_kind": self.device_kind,
            "captured": self.captured,
            "aot": self.aot,
            "flops_xla": self.flops_xla,
            "flops_hint": self.flops_hint,
            "flops_per_token_hint": self.flops_per_token_hint,
            "flops_source": self.flops_source,
            "bytes_accessed": self.bytes_accessed,
            "memory": dict(self.memory),
            "peak_flops_per_sec": self.peak_flops_per_sec,
            "op_intensity": self.op_intensity,
            "ridge_flops_per_byte": self.ridge,
            "roofline_verdict": self.roofline_verdict,
            "calls": self.calls,
            "steps": self.steps,
            "tokens": self.tokens,
            "device_seconds": self.device_seconds,
            "last_step_wall_s": self.last_step_wall_s,
            "achieved_flops_per_sec": self.last_flops_per_sec,
            "mfu": self.last_mfu,
        }


class CompiledProgramRegistry:
    """Per-process program table + HBM watermarks + self-accounted cost.

    Leaf lock: nothing is called while ``_lock`` is held except record
    mutation — telemetry/tsdb emission happens in the module-level fold
    functions AFTER the lock is released.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, ProgramRecord] = {}
        self._hbm: Dict[str, Dict[str, Optional[float]]] = {}
        self.overhead_ns = 0

    # --- registration / capture ------------------------------------------
    def register(self, label: str, *, n_devices: int = 1,
                 flops_hint: Optional[float] = None,
                 flops_per_token_hint: Optional[float] = None) -> ProgramRecord:
        with self._lock:
            rec = self._programs.get(label)
            if rec is None:
                rec = ProgramRecord(label, n_devices, flops_hint,
                                    flops_per_token_hint)
                self._programs[label] = rec
            else:
                rec.n_devices = max(1, int(n_devices))
                if flops_hint is not None:
                    rec.flops_hint = flops_hint
                if flops_per_token_hint is not None:
                    rec.flops_per_token_hint = flops_per_token_hint
            return rec

    def note_capture(self, label: str, *, device_kind: str,
                     flops_xla: Optional[float],
                     bytes_accessed: Optional[float],
                     memory: Optional[Dict[str, int]],
                     aot: bool) -> None:
        peak = device_specs.peak_flops_per_sec(device_kind)
        ridge = device_specs.roofline_ridge_flops_per_byte(device_kind)
        with self._lock:
            rec = self._programs.get(label)
            if rec is None:
                return
            rec.captured = True
            rec.aot = aot
            rec.device_kind = device_kind
            rec.flops_xla = flops_xla
            rec.bytes_accessed = bytes_accessed
            rec.memory = dict(memory or {})
            # unknown device kind: no peak -> no MFU, no roofline verdict
            rec.peak_flops_per_sec = (
                None if peak is None else peak * rec.n_devices)
            if rec.flops_per_token_hint is not None or rec.flops_hint is not None:
                rec.flops_source = FLOPS_SOURCE_ANALYTIC
            elif flops_xla is not None:
                rec.flops_source = FLOPS_SOURCE_XLA
            if flops_xla and bytes_accessed:
                rec.op_intensity = flops_xla / bytes_accessed
                rec.ridge = ridge
                if ridge is not None:
                    rec.roofline_verdict = (
                        VERDICT_COMPUTE if rec.op_intensity >= ridge
                        else VERDICT_BANDWIDTH)

    # --- measurement folds -----------------------------------------------
    def fold(self, label: str, wall_s: float, steps: int,
             tokens: Optional[int]) -> Optional[Tuple[Optional[float],
                                                      Optional[float],
                                                      Optional[float]]]:
        """Fold a measured wall-time window into the program's rates;
        returns ``(flops_folded, mfu, achieved_flops_per_sec)`` (entries
        None when the program has no FLOP count), or None for unknown
        labels / degenerate windows."""
        if wall_s <= 0 or steps <= 0:
            return None
        with self._lock:
            rec = self._programs.get(label)
            if rec is None:
                return None
            tokens_per_step = (tokens / steps) if tokens else None
            step_flops = rec.step_flops(tokens_per_step)
            rec.calls += 1
            rec.steps += int(steps)
            rec.tokens += int(tokens or 0)
            rec.device_seconds += float(wall_s)
            rec.last_step_wall_s = wall_s / steps
            if step_flops is None:
                return (None, None, None)
            flops = step_flops * steps
            achieved = flops / wall_s
            mfu = None
            if rec.peak_flops_per_sec:
                mfu = achieved / rec.peak_flops_per_sec
                rec.last_mfu = mfu
            rec.last_flops_per_sec = achieved
            return (flops, mfu, achieved)

    def note_hbm(self, device: str, stats: Dict[str, Optional[float]]) -> None:
        with self._lock:
            self._hbm[device] = dict(stats)

    def add_overhead(self, ns: int) -> None:
        with self._lock:
            self.overhead_ns += int(ns)

    # --- read surfaces ----------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            programs = {k: r.as_dict() for k, r in self._programs.items()}
            hbm = {k: dict(v) for k, v in self._hbm.items()}
            overhead_ns = self.overhead_ns
        return {
            "programs": programs,
            "hbm": hbm,
            "overhead_ms": round(overhead_ns / 1e6, 3),
        }


# --- process-wide singletons --------------------------------------------------
_REGISTRY = CompiledProgramRegistry()
_SAMPLER: Optional["HbmSampler"] = None
_sampler_lock = threading.Lock()


def get_registry() -> CompiledProgramRegistry:
    return _REGISTRY


def _device_kind() -> str:
    try:
        import jax

        return str(getattr(jax.local_devices()[0], "device_kind", ""))
    except Exception:  # noqa: BLE001 - no backend is a valid devperf state
        return ""


def _extract_cost(compiled) -> Tuple[Optional[float], Optional[float]]:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        return (flops if flops > 0 else None, nbytes if nbytes > 0 else None)
    except Exception:  # noqa: BLE001 - cost analysis is best-effort per backend
        return (None, None)


def _extract_memory(compiled) -> Dict[str, int]:
    out: Dict[str, int] = {}
    try:
        ma = compiled.memory_analysis()
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, key, None)
            if v is not None:
                out[key] = int(v)
    except Exception:  # noqa: BLE001 - memory analysis is best-effort per backend
        pass
    return out


def instrument(fn: Callable, label: str, *, n_devices: int = 1,
               flops_hint: Optional[float] = None,
               flops_per_token_hint: Optional[float] = None) -> Callable:
    """Wrap a jitted callable for registry capture; returns ``fn`` unchanged
    when devperf is disabled.

    First call: AOT ``fn.lower(*args).compile()`` — the single trace the jit
    dispatcher would have performed anyway, so ``tel.compile_count`` stays at
    1 — then capture cost/memory analysis and dispatch the executable. Later
    calls dispatch the cached executable directly. One wrapper serves ONE
    signature: a compile failure, or a later call whose shapes, dtypes or
    shardings differ from the captured executable's, raises to the caller —
    re-dispatching through ``jit`` would be a second, unrecorded compile and
    would mask a sharding mismatch.
    """
    if not enabled():
        return fn
    reg = get_registry()
    reg.register(label, n_devices=n_devices, flops_hint=flops_hint,
                 flops_per_token_hint=flops_per_token_hint)
    return _Instrumented(fn, label, reg)


class _Instrumented:
    """The callable :func:`instrument` returns. ``compiled`` is the captured
    executable (``None`` before the first call) — ``compiled.as_text()`` is
    the optimized HLO of what actually runs."""

    def __init__(self, fn: Callable, label: str, reg: CompiledProgramRegistry):
        self._fn = fn
        self._label = label
        self._reg = reg
        self.compiled = None
        self.__name__ = f"devperf_{label}"

    def __call__(self, *args):
        if self.compiled is None:
            self.compiled = self._fn.lower(*args).compile()
            t0 = time.perf_counter_ns()
            flops, nbytes = _extract_cost(self.compiled)
            self._reg.note_capture(
                self._label, device_kind=_device_kind(), flops_xla=flops,
                bytes_accessed=nbytes, memory=_extract_memory(self.compiled),
                aot=True)
            self._reg.add_overhead(time.perf_counter_ns() - t0)
        return self.compiled(*args)


def observe_step(label: str, wall_s: float, *, steps: int = 1,
                 tokens: Optional[int] = None) -> Optional[float]:
    """Fold a measured wall-time for ``steps`` executions of ``label`` into
    the registry and the metric surfaces; returns the resulting MFU (None
    when unknown program / no FLOP count / disabled)."""
    if not enabled():
        return None
    t0 = time.perf_counter_ns()
    reg = get_registry()
    out = reg.fold(label, wall_s, steps, tokens)
    mfu = None
    if out is not None:
        flops, mfu, _achieved = out
        t = get_telemetry()
        t.counter("program.steps." + label).add(int(steps))
        if flops is not None:
            t.counter("program.flops." + label).add(float(flops))
        if mfu is not None:
            store = tsdb.active()
            if store is not None:
                store.record_gauge("devperf.mfu." + label, float(mfu))
    reg.add_overhead(time.perf_counter_ns() - t0)
    return mfu


def observe_window(label: str, wall_s: float, steps: int, *,
                   tokens: Optional[int] = None) -> Optional[float]:
    """Window form of :func:`observe_step`: a whole measured train/decode
    window of ``steps`` executions (the trainer's ``llm.train`` span)."""
    return observe_step(label, wall_s, steps=steps, tokens=tokens)


# --- HBM sampler --------------------------------------------------------------

def _device_memory_stats() -> List[Tuple[str, Dict[str, Optional[float]]]]:
    """(device_label, stats) per local device; ``bytes_limit`` comes from the
    device-kind datasheet table when the runtime's ``memory_stats()`` has
    none (CPU devices report nothing)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 - no backend: nothing to sample
        return []
    out: List[Tuple[str, Dict[str, Optional[float]]]] = []
    for d in devices:
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - CPU devices may not implement it
            st = {}
        limit = st.get("bytes_limit")
        if limit is None:
            limit = device_specs.device_hbm_bytes(
                getattr(d, "device_kind", ""))
        out.append((f"{getattr(d, 'platform', 'dev')}:{getattr(d, 'id', len(out))}", {
            "bytes_in_use": st.get("bytes_in_use"),
            "peak_bytes_in_use": st.get("peak_bytes_in_use"),
            "bytes_limit": limit,
        }))
    return out


class HbmSampler:
    """Fixed-cadence device-memory sampler thread (live + high-water).

    ``stats_fn`` is injectable for tests and chaos drills; the default reads
    every local JAX device's ``memory_stats()``. ``start``/``stop`` are
    idempotent and ``stop`` joins the thread (no leak), tolerating at most
    one sleep interval of drain.
    """

    def __init__(self, interval_s: Optional[float] = None,
                 stats_fn: Optional[Callable[[], List[Tuple[str, Dict[str, Optional[float]]]]]] = None,
                 registry: Optional[CompiledProgramRegistry] = None):
        self.interval_s = float(interval_s if interval_s is not None
                                else os.environ.get(_ENV_HBM_INTERVAL, "1.0"))
        self._stats_fn = stats_fn or _device_memory_stats
        self._reg = registry or get_registry()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.samples = 0

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def start(self) -> None:
        if self.running:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="devperf-hbm", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=self.interval_s + 5.0)
            self._thread = None

    def sample_once(self) -> int:
        """One synchronous sweep (the thread's body; callable directly from
        tests and the bench stage). Returns devices sampled."""
        t0 = time.perf_counter_ns()
        stats = self._stats_fn()
        high_frac: Optional[float] = None
        for device, st in stats:
            self._reg.note_hbm(device, st)
            peak, limit = st.get("peak_bytes_in_use"), st.get("bytes_limit")
            if peak is not None and limit:
                frac = float(peak) / float(limit)
                high_frac = frac if high_frac is None else max(high_frac, frac)
        if high_frac is not None:
            store = tsdb.active()
            if store is not None:
                store.record_gauge("devperf.hbm_high_water_frac",
                                   float(high_frac))
        self.samples += 1
        self._reg.add_overhead(time.perf_counter_ns() - t0)
        return len(stats)

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 - the sampler must survive backend hiccups
                log.debug("devperf: hbm sample failed", exc_info=True)
            time.sleep(self.interval_s)  # fedlint: disable=bare-sleep fixed-cadence sampler pacing, not a retry/poll of remote state; stop() joins and tolerates one interval of drain


def start_hbm_sampler(interval_s: Optional[float] = None) -> Optional[HbmSampler]:
    """Start (or reuse) the process-wide HBM sampler; None when disabled."""
    if not enabled():
        return None
    global _SAMPLER
    with _sampler_lock:
        if _SAMPLER is None:
            _SAMPLER = HbmSampler(interval_s=interval_s)
        sampler = _SAMPLER
    sampler.start()
    return sampler


def stop_hbm_sampler() -> None:
    global _SAMPLER
    with _sampler_lock:
        sampler = _SAMPLER
        _SAMPLER = None
    if sampler is not None:
        sampler.stop()


# --- surfaces -----------------------------------------------------------------

def snapshot() -> Dict[str, Any]:
    """The registry's full JSON-safe state (mlops trace dumps, perf_report)."""
    snap = _REGISTRY.snapshot()
    with _sampler_lock:
        sampler = _SAMPLER
    snap["sampler"] = {
        "running": bool(sampler is not None and sampler.running),
        "samples": int(sampler.samples) if sampler is not None else 0,
        "interval_s": sampler.interval_s if sampler is not None else None,
    }
    snap["enabled"] = enabled()
    return snap


def statusz_snapshot() -> Dict[str, Any]:
    """The `/statusz` ``devperf`` section; empty when nothing was captured
    (so idle processes don't grow a vacant section)."""
    if not enabled():
        return {}
    snap = snapshot()
    if not snap["programs"] and not snap["hbm"]:
        return {}
    return snap


def prom_gauges() -> List[tuple]:
    """``fedml_device_*`` ride-along gauges for ``prom.render``."""
    if not enabled():
        return []
    snap = _REGISTRY.snapshot()
    out: List[tuple] = []
    for label in sorted(snap["programs"]):
        p = snap["programs"][label]
        if p.get("mfu") is not None:
            out.append(("device_mfu", {"program": label}, float(p["mfu"])))
        if p.get("achieved_flops_per_sec") is not None:
            out.append(("device_flops_per_sec", {"program": label},
                        float(p["achieved_flops_per_sec"])))
    for device in sorted(snap["hbm"]):
        h = snap["hbm"][device]
        if h.get("bytes_in_use") is not None:
            out.append(("device_hbm_bytes", {"device": device},
                        float(h["bytes_in_use"])))
        if h.get("peak_bytes_in_use") is not None:
            out.append(("device_hbm_high_water_bytes", {"device": device},
                        float(h["peak_bytes_in_use"])))
    return out


def reset() -> None:
    """Tests: stop the sampler and drop every captured program/watermark."""
    global _REGISTRY
    stop_hbm_sampler()
    _REGISTRY = CompiledProgramRegistry()

"""JAX-aware telemetry hooks.

Importing this module imports no jax — the hooks exploit properties of *call
sites*:

- ``track_compiles`` wraps a function so a counter bumps when the body runs
  under tracing. Inside ``jax.jit`` the Python body executes only on (re)trace,
  so the counter advances per compile, not per call — the same trick
  ``BucketedAggregator.accum_traces`` uses (tests/test_bucketed_agg.py pins it).
  The wrapper carries the label as its ``__name__``, so the jitted program
  reaches the profiler's trace and the HLO as ``jit_<label>``.
- ``record_transfer`` is called from the ``utils/pytree.py`` flat-vector comm
  boundary with the byte count of each host<->device hop.
- the registry's second span sink: ``core._profiler_annotation`` is pointed at
  ``jax.profiler.TraceAnnotation``, resolved on the first enabled span.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

from . import core as _core
from .core import Telemetry, get_telemetry

COMPILE_COUNTER_PREFIX = "jax.compiles."
H2D_BYTES = "comm.host_to_device_bytes"
D2H_BYTES = "comm.device_to_host_bytes"
H2D_TRANSFERS = "comm.host_to_device_transfers"
D2H_TRANSFERS = "comm.device_to_host_transfers"


def track_compiles(fn: Callable, name: Optional[str] = None, telemetry: Optional[Telemetry] = None) -> Callable:
    """Wrap ``fn`` so ``counter("jax.compiles.<name>")`` counts its jit traces.

    Use on the function handed to ``jax.jit`` (or already inside a jitted
    caller): the increment is a Python side effect, so it fires at trace time
    only. Outside jit it counts plain calls — wrap only jit-bound bodies.
    """
    label = name or getattr(fn, "__name__", repr(fn))

    @functools.wraps(fn)
    def wrapped(*args: Any, **kwargs: Any):
        (telemetry or get_telemetry()).counter(COMPILE_COUNTER_PREFIX + label).add(1)
        return fn(*args, **kwargs)

    # jax.jit names the program after the function it is handed
    wrapped.__name__ = wrapped.__qualname__ = label
    return wrapped


def _profiler_annotation(name: str):
    """First enabled span of the process: bind the profiler's annotation
    class in place of this resolver, so later spans call it directly."""
    from jax.profiler import TraceAnnotation

    _core._profiler_annotation = TraceAnnotation
    return TraceAnnotation(name)


_core._profiler_annotation = _profiler_annotation


def compile_count(name: str, telemetry: Optional[Telemetry] = None) -> int:
    """Current trace count for a ``track_compiles``-wrapped function."""
    return (telemetry or get_telemetry()).counter(COMPILE_COUNTER_PREFIX + name).value


def record_transfer(direction: str, nbytes: int, telemetry: Optional[Telemetry] = None) -> None:
    """Account one device transfer at the comm boundary.

    ``direction`` is ``"host_to_device"`` (upload: client deltas landing on
    chip) or ``"device_to_host"`` (download: global model leaving the chip).
    """
    if direction == "host_to_device":
        bytes_key, hops_key = H2D_BYTES, H2D_TRANSFERS
    elif direction == "device_to_host":
        bytes_key, hops_key = D2H_BYTES, D2H_TRANSFERS
    else:
        raise ValueError(f"unknown transfer direction: {direction!r}")
    t = telemetry or get_telemetry()
    t.counter(bytes_key).add(int(nbytes))
    t.counter(hops_key).add(1)

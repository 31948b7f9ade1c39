"""Per-device-kind accelerator datasheet: peak FLOPs, HBM capacity, HBM
bandwidth.

The single home for the chip constants that used to live as private copies
in ``bench.py`` (``_PEAK_BF16_TFLOPS`` / ``_HBM_BYTES_BY_DEVICE_KIND``) and
that the devperf registry (``core/telemetry/devperf.py``) and the placement
cost model (``core/engine/placement_search.py``) now share. All lookups
match by SUBSTRING against the runtime's ``device_kind`` string
(lowercased) — TPU runtimes report kinds like ``"TPU v5 lite"`` or
``"TPU v5e"`` depending on generation and stack version, so exact-match
tables silently miss.

Pure Python on purpose: no jax import, so the bench orchestrator process
(which never imports jax/fedml_tpu device code) and host-side tools can
read the tables for free. Callers that need the *attached* device's kind
read it themselves and pass the string in.

Granularity note (inherited from bench's memplan table): capacities and
bandwidths are per JAX *device*, not per chip — v2/v3 expose each core as
a device (half the chip's HBM and HBM bandwidth); v4+ megacore and the
single-core v5e/v6e chips expose whole-chip numbers.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

# Dense peak TFLOPS at bf16; f32 ≈ bf16/2 on every TPU generation here.
PEAK_BF16_TFLOPS = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,   # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,   # trillium
    "v6e": 918.0,
}

# Datasheet HBM per device; ordered so the most specific substring wins
# ("v5 lite" and "v5litepod" before the bare "v5..." generations would
# otherwise shadow them).
HBM_BYTES_BY_DEVICE_KIND: list[tuple[str, int]] = [
    ("v5 lite", 16 * 2**30),   # v5e, 16 GiB/chip, 1 core/chip
    ("v5litepod", 16 * 2**30),
    ("v5e", 16 * 2**30),
    ("v5p", 95 * 2**30),       # 95 GiB/chip
    ("v6 lite", 32 * 2**30),   # v6e / trillium
    ("v6e", 32 * 2**30),
    ("v4", 32 * 2**30),        # megacore: device == chip
    ("v3", 16 * 2**30),        # 32 GiB/chip, 2 devices/chip
    ("v2", 8 * 2**30),
]

# Datasheet HBM bandwidth per device (bytes/s) — the roofline ridge point's
# denominator. Same ordering discipline as the capacity table.
HBM_BANDWIDTH_BYTES_PER_S: list[tuple[str, float]] = [
    ("v5 lite", 819e9),
    ("v5litepod", 819e9),
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6 lite", 1640e9),
    ("v6e", 1640e9),
    ("v4", 1228e9),
    ("v3", 450e9),             # 900 GB/s/chip, 2 devices/chip
    ("v2", 350e9),             # 700 GB/s/chip, 2 devices/chip
]


def _unknown(device_kind: str, what: str) -> None:
    """A device that is not in the tables has no peak: callers report no MFU
    or roofline for it (``None``) instead of one from an invented number. A
    TPU that is not in the tables is an error — the table needs a row with
    its source before anything is measured on that chip."""
    if "tpu" in str(device_kind).lower():
        raise ValueError(
            f"no {what} recorded for TPU device_kind {device_kind!r}; add it to "
            "core/distributed/device_specs.py with its datasheet source")
    return None


def peak_tflops(device_kind: str, dtype_bits: int = 16) -> Optional[float]:
    """Dense peak TFLOPS for a ``device_kind`` string at the given matmul
    width; substring match. ``None`` for a non-TPU kind that is not in the
    table (CPU), ``ValueError`` for an unlisted TPU."""
    kind = str(device_kind).lower()
    for key, bf16 in PEAK_BF16_TFLOPS.items():
        if key in kind:
            return bf16 if dtype_bits == 16 else bf16 / 2.0
    return _unknown(device_kind, "peak FLOP/s")


def peak_flops_per_sec(device_kind: str, dtype_bits: int = 16) -> Optional[float]:
    tflops = peak_tflops(device_kind, dtype_bits)
    return None if tflops is None else tflops * 1e12


def device_hbm_bytes(device_kind: str) -> Optional[int]:
    """Datasheet HBM capacity per device; ``None`` when unrecognized (the
    caller decides whether missing capacity is fatal — bench's memplan
    falls through to a direct allocation probe)."""
    kind = str(device_kind).lower()
    for sub, cap in HBM_BYTES_BY_DEVICE_KIND:
        if sub in kind:
            return cap
    return None


def hbm_bandwidth_bytes_per_sec(device_kind: str) -> Optional[float]:
    kind = str(device_kind).lower()
    for sub, bw in HBM_BANDWIDTH_BYTES_PER_S:
        if sub in kind:
            return bw
    return _unknown(device_kind, "HBM bandwidth")


def roofline_ridge_flops_per_byte(device_kind: str,
                                  dtype_bits: int = 16) -> Optional[float]:
    """Operational intensity (FLOPs/byte) at which the roofline's compute
    ceiling meets its bandwidth slope: programs above it are compute-bound,
    below it bandwidth-bound. ``None`` when the device has no recorded peak."""
    peak = peak_flops_per_sec(device_kind, dtype_bits)
    bandwidth = hbm_bandwidth_bytes_per_sec(device_kind)
    if peak is None or bandwidth is None:
        return None
    return peak / bandwidth


def local_chip_count() -> int:
    """TPU chips attached to THIS host, counted from their device nodes
    (``/dev/accel<n>``, or ``/dev/vfio/<n>`` on hosts that expose chips
    through VFIO). Deliberately not ``jax.devices()``: initialising JAX takes
    the chips, so a launcher (scheduler agent, replica controller) that
    counted through JAX would starve the process it is about to start."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    return sum(os.path.basename(p).isdigit() for p in glob.glob("/dev/vfio/*"))

"""Device selection (reference: python/fedml/device/device.py:42 +
ml/engine/ml_engine_adapter.py:176-229).

In the reference this maps (platform, gpu ids, engine) to torch/tf/jax
devices; here JAX is the engine so the job is simpler: pick the accelerator
and expose mesh construction for sharded paths (see
fedml_tpu.parallel.mesh). The CPU is a device you ask for, never one you
are handed because the accelerator went missing."""

from __future__ import annotations

import logging
from typing import Any, Optional

import jax

log = logging.getLogger(__name__)


def cpu_selected() -> bool:
    """True when the process pinned JAX to the CPU on purpose
    (``JAX_PLATFORMS=cpu`` or the ``jax_platforms`` config) — what the tests
    and ``chip_smoke.py --dry-run-cpu`` do."""
    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def get_device(args: Optional[Any] = None):
    """Return the compute device for this process.

    ``using_gpu`` (default true) asks for an accelerator; when none is
    attached this RAISES rather than quietly handing back the CPU — unless
    the CPU was selected explicitly (:func:`cpu_selected`), in which case the
    CPU devices are the pool. ``using_gpu: false`` always means the CPU."""
    using_gpu = bool(getattr(args, "using_gpu", True)) if args is not None else True
    if not using_gpu:
        pool = jax.devices("cpu")
    else:
        devices = jax.devices()
        pool = [d for d in devices if d.platform != "cpu"]
        if not pool:
            if not cpu_selected():
                raise RuntimeError(
                    "an accelerator was requested (using_gpu: true) but JAX found "
                    f"only platform {devices[0].platform!r}; set JAX_PLATFORMS=cpu "
                    "(or using_gpu: false) to run on the CPU on purpose")
            pool = devices
    gpu_id = int(getattr(args, "gpu_id", 0) or 0) if args is not None else 0
    dev = pool[gpu_id % len(pool)]
    log.info("device = %s", dev)
    return dev


def get_local_device_count() -> int:
    return jax.local_device_count()

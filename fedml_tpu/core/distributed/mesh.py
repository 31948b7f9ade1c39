"""Server-side mesh plumbing: spec parsing, mesh construction, topology
registry, and shard-byte accounting.

This module (plus ``core/aggregation/sharded.py``) is the ONLY place in the
server data plane allowed to touch ``jax.sharding`` — enforced by
``tools/check_sharding.py``. Everything else sees meshes through three
narrow surfaces:

- :func:`configure_server_mesh` / :func:`server_mesh`: resolve
  ``args.server_mesh`` / ``FEDML_SERVER_MESH`` ("auto", "fsdp:8",
  "dp:2,fsdp:4") into a named :class:`jax.sharding.Mesh` over the local
  devices, or ``None`` when unset or the spec resolves to one device —
  callers then keep the single-device path, so the sp CPU tier-1 path is
  byte-identical with no mesh configured. A spec that asks for more devices
  than are visible raises.
- :func:`note_mesh` / :func:`current_topologies`: a plain-dict topology
  registry (axis names/sizes, device kinds) that the flight recorder and
  ``/statusz`` read without importing jax.
- :func:`record_shard_bytes` / :func:`prom_gauges`: per-device resident
  shard bytes (``fedml_server_shard_bytes{device=}``) and per-device HBM
  high-water (``fedml_device_hbm_peak_bytes{device=}``, where the platform
  reports ``memory_stats``) for ``/metrics``.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

SERVER_MESH_ENV = "FEDML_SERVER_MESH"

_lock = threading.Lock()
# spec configured programmatically (configure_server_mesh(args)); the env var
# is consulted as the fallback on every read so subprocess benches can steer
# the engine without an args object
_configured_spec: Optional[str] = None
# spec string -> Mesh; meshes are tiny but construction touches jax.devices()
_mesh_cache: Dict[str, Any] = {}
# name -> plain-dict topology (flight recorder / statusz read this)
_topologies: Dict[str, Dict[str, Any]] = {}
# owner -> {device_str: resident shard bytes}
_shard_bytes: Dict[str, Dict[str, int]] = {}


def parse_mesh_spec(spec: str) -> List[Tuple[str, int]]:
    """``"fsdp:8"`` / ``"dp:2,fsdp:4"`` -> ordered ``[(axis, size), ...]``.

    ``"auto"`` (or an axis size of ``auto``/``-1``) means "all local
    devices" and is resolved by :func:`server_mesh` against the live device
    count, so the same spec string works on a v5e-8 and a forced 8-way CPU
    host.
    """
    spec = str(spec).strip().lower()
    if not spec:
        raise ValueError("empty mesh spec")
    if spec == "auto":
        return [("fsdp", -1)]
    axes: List[Tuple[str, int]] = []
    for part in spec.split(","):
        part = part.strip()
        if ":" not in part:
            raise ValueError(f"mesh spec axis {part!r} needs name:size (e.g. fsdp:8)")
        name, _, size_s = part.partition(":")
        name = name.strip()
        size_s = size_s.strip()
        if not name:
            raise ValueError(f"mesh spec axis {part!r} has an empty axis name")
        size = -1 if size_s in ("auto", "-1", "*") else int(size_s)
        if size == 0 or size < -1:
            raise ValueError(f"mesh spec axis {part!r} has invalid size {size_s!r}")
        axes.append((name, size))
    if sum(1 for _, s in axes if s == -1) > 1:
        raise ValueError(f"mesh spec {spec!r} has more than one auto-sized axis")
    return axes


def configure_server_mesh(args: Any = None, spec: Optional[str] = None) -> Optional[str]:
    """Install the process-default server mesh spec from ``args.server_mesh``
    (or an explicit ``spec``); returns the installed spec or ``None``.

    ``bucketed.get_engine`` keys its registry on this, so configuring a mesh
    after engines were handed out yields *new* engines — no stale jit caches.
    """
    global _configured_spec
    if spec is None and args is not None:
        spec = getattr(args, "server_mesh", None)
    if spec is not None:
        spec = str(spec).strip() or None
    with _lock:
        _configured_spec = spec
    return spec


def configured_spec() -> Optional[str]:
    """The active server-mesh spec: programmatic config wins, then the
    ``FEDML_SERVER_MESH`` env var, then ``None`` (single-device path)."""
    with _lock:
        if _configured_spec is not None:
            return _configured_spec
    env = os.environ.get(SERVER_MESH_ENV, "").strip()
    return env or None


def server_mesh(spec: Optional[str] = None):
    """Build (or fetch the cached) server Mesh for ``spec`` — defaulting to
    :func:`configured_spec` — or ``None`` when no spec is set or it resolves
    to a single device (callers then keep the unsharded path). Raises
    ``ValueError`` on a spec the visible devices cannot satisfy: an operator
    who asked for ``fsdp:8`` must not get one device and a log line."""
    if spec is None:
        spec = configured_spec()
    if spec is None:
        return None
    with _lock:
        if spec in _mesh_cache:
            return _mesh_cache[spec]
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    axes = parse_mesh_spec(spec)
    fixed = 1
    for _, s in axes:
        if s != -1:
            fixed *= s
    resolved: List[Tuple[str, int]] = []
    for name, s in axes:
        if s == -1:
            s = max(1, len(devices) // fixed)
        resolved.append((name, s))
    total = int(np.prod([s for _, s in resolved]))
    if total > len(devices):
        raise ValueError(
            f"server mesh spec {spec!r} needs {total} devices but only "
            f"{len(devices)} are visible")
    if total <= 1:
        mesh = None  # "auto" on a one-device host: the unsharded path IS the spec
    else:
        grid = np.asarray(devices[:total]).reshape([s for _, s in resolved])
        mesh = Mesh(grid, axis_names=tuple(n for n, _ in resolved))
        note_mesh("server", mesh)
    with _lock:
        _mesh_cache[spec] = mesh
    return mesh


def mesh_topology(mesh) -> Dict[str, Any]:
    """A Mesh as plain JSON-safe data (for crash dumps / statusz)."""
    devices = list(mesh.devices.flat)
    kinds = sorted({getattr(d, "device_kind", "unknown") for d in devices})
    return {
        "axis_names": list(mesh.axis_names),
        "axis_sizes": [int(mesh.shape[a]) for a in mesh.axis_names],
        "n_devices": len(devices),
        "device_kinds": kinds,
        "platform": getattr(devices[0], "platform", "unknown") if devices else "none",
    }


def note_mesh(name: str, mesh) -> None:
    """Register a mesh's topology under ``name`` so crash dumps and
    ``/statusz`` can report it without holding the Mesh object."""
    topo = mesh_topology(mesh)
    with _lock:
        _topologies[str(name)] = topo


def current_topologies() -> Dict[str, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _topologies.items()}


def record_shard_bytes(owner: str, per_device: Dict[str, int]) -> None:
    """Book the resident shard bytes an owner (e.g. the sharded aggregator's
    accumulator + params + optimizer state) keeps per device."""
    with _lock:
        _shard_bytes[str(owner)] = {str(k): int(v) for k, v in per_device.items()}


def shard_bytes_by_device() -> Dict[str, int]:
    """Total booked shard bytes per device across all owners."""
    out: Dict[str, int] = {}
    with _lock:
        for per_device in _shard_bytes.values():
            for dev, nbytes in per_device.items():
                out[dev] = out.get(dev, 0) + nbytes
    return out


def device_hbm_peak_bytes() -> Dict[str, int]:
    """Per-device ``peak_bytes_in_use`` where the platform reports it
    (TPU/GPU; CPU devices usually return nothing). Only queried when a mesh
    was registered, so processes that never shard never import jax here."""
    if not current_topologies():
        return {}
    try:
        import jax

        out: Dict[str, int] = {}
        for d in jax.devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 - platform-dependent API
                stats = None
            if stats and "peak_bytes_in_use" in stats:
                out[str(d)] = int(stats["peak_bytes_in_use"])
        return out
    except Exception:  # noqa: BLE001 - gauges must never take down a scrape
        return {}


def prom_gauges() -> List[Tuple[str, Optional[Dict[str, str]], float]]:
    """``(name, labels, value)`` gauge triples for ``/metrics``."""
    gauges: List[Tuple[str, Optional[Dict[str, str]], float]] = []
    for dev, nbytes in sorted(shard_bytes_by_device().items()):
        gauges.append(("server_shard_bytes", {"device": dev}, float(nbytes)))
    for dev, nbytes in sorted(device_hbm_peak_bytes().items()):
        gauges.append(("device_hbm_peak_bytes", {"device": dev}, float(nbytes)))
    return gauges


def statusz_snapshot() -> Dict[str, Any]:
    """The ``sharding`` section for ``/statusz``: empty dict when no mesh has
    ever been registered (section is then omitted)."""
    topos = current_topologies()
    if not topos:
        return {}
    return {
        "configured_spec": configured_spec(),
        "meshes": topos,
        "shard_bytes_by_device": shard_bytes_by_device(),
    }


def reset_mesh_state() -> None:
    """Test hook: drop configured spec, mesh cache, topologies, and gauges."""
    global _configured_spec
    with _lock:
        _configured_spec = None
        _mesh_cache.clear()
        _topologies.clear()
        _shard_bytes.clear()

"""The data-driven harness: finds a cell's files by the names in
``BENCHMARK.json`` and drives one run of it.

A cell (``workloads`` entry) names a configuration and a traffic mix. Files,
all found by name, none listed in code:

  workloads/<cell>.json     the driver, the program's settings, the limits
  configs/<config>.json     the published sizes as run
  traffic/<traffic>.json    parameters of a traffic kind (traffic.py)
  drivers/<driver>.py       ``run(ctx) -> dict``: set-up, warm-up, window, check
  metrics/<metric>.py       ``read(run) -> float | None``: one per-layer metric

A later PR adds a cell, a configuration, a mix or a metric by adding files
and ``BENCHMARK.json`` entries; it edits nothing that is here.
"""

from __future__ import annotations

import collections
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIRNAME = ".bench_out"          # traces and scratch, inside the checkout, gitignored
CACHE_DIRNAME = ".jax_cache"        # the one compile cache of the checkout
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class HarnessError(Exception):
    pass


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import one file by path (metric and driver names may hold dots)."""
    if not os.path.exists(path):
        raise HarnessError(f"no such file: {path}")
    mod_name = name or "bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry with the files it names, under ``root``."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmark")
        self.benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.benchmark["workloads"]}
        if workload not in entries:
            raise HarnessError(f"no cell {workload!r} in BENCHMARK.json (have {sorted(entries)})")
        self.entry = entries[workload]
        self.name = workload
        cfg_entry = {c["name"]: c for c in self.benchmark["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.workload = load_json(os.path.join(self.bench_dir, "workloads", workload + ".json"))
        self.traffic = load_json(os.path.join(self.bench_dir, "traffic", self.entry["traffic"] + ".json"))
        self.chips = int(self.entry["chips"])

    def driver(self):
        return load_module(os.path.join(self.bench_dir, "drivers", self.workload["driver"] + ".py"))

    def _in_cell(self, metric: dict, reported: set) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        return "moves" not in metric or metric["moves"] in reported

    def end_to_end(self) -> List[dict]:
        return [m for m in self.benchmark["end_to_end"] if self._in_cell(m, set())]

    def per_layer(self) -> List[dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.benchmark["per_layer"] if self._in_cell(m, reported)]

    def metric_reader(self, name: str):
        return load_module(os.path.join(self.bench_dir, "metrics", name + ".py")).read

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))["device_kinds"]
        if device_kind not in table:
            raise HarnessError(f"device kind {device_kind!r} is not in benchmark/peaks.json")
        return table[device_kind]


class CompileLog:
    """Every backend compile of the process, with the time it ended (a
    persistent-cache hit fires the event too: its seconds are the load)."""

    def __init__(self):
        import jax.monitoring

        self.events: List[tuple] = []  # (t_end perf_counter, fun_name, secs)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == BACKEND_COMPILE_EVENT:
            self.events.append((time.perf_counter(), str(kw.get("fun_name", "?")), float(secs)))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]

    def seconds(self) -> float:
        return sum(e[2] for e in self.events)


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    at ``<checkout>/.jax_cache`` (where the program's own
    ``utils/compile_cache.py`` points too); in this process every program is
    cached, however small or quick."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(root, CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_device(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it; raises unless it is a TPU with at least
    ``chips`` chips (``allow_cpu`` is for the tests' rehearsals only)."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not allow_cpu:
        raise HarnessError(f"no accelerator: JAX reports platform {platform!r}")
    if len(devs) < chips:
        raise HarnessError(f"cell needs {chips} chips, JAX reports {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class Tracer:
    """jax.profiler around a part of the window; the trace is reduced and
    then deleted (a run keeps nothing large on disk)."""

    def __init__(self, out_dir: str, cpu_rehearsal: bool = False):
        self.cpu_rehearsal = cpu_rehearsal
        self.dir = os.path.join(out_dir, "trace")
        self.reduce = load_module(os.path.join(BENCH_DIR, "trace", "reduce.py"))

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        trace = self.reduce.load_xplane(self.reduce.find_xplane(self.dir), self.cpu_rehearsal)
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def span(name: str):
    """The benchmark's own host span, written into the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


class Ctx:
    """What a driver gets: the cell's files, the arguments, the clocks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process_start: float, allow_cpu: bool = False, out_dir: Optional[str] = None):
        self.cell = cell
        self.config, self.workload, self.traffic = cell.config, cell.workload, cell.traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.t_process_start = t_process_start
        self.allow_cpu = allow_cpu
        self.out_dir = out_dir or os.path.join(cell.root, OUT_DIRNAME)
        os.makedirs(self.out_dir, exist_ok=True)
        self.compile_log = CompileLog()
        self.device = require_device(cell.chips, allow_cpu)
        self.peaks = None if self.device["platform"] != "tpu" else cell.peaks(self.device["kind"])
        self.tracer = Tracer(self.out_dir, self.device["platform"] != "tpu") if trace else None

    def log(self, msg: str) -> None:
        print(f"[bench +{time.perf_counter() - self.t_process_start:7.2f}s] {msg}", file=sys.stderr, flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             t_process_start: float, allow_cpu: bool = False) -> dict:
    """One run of one cell -> the result object of the contract."""
    cell = Cell(root, workload)
    place_compile_cache(root)
    ctx = Ctx(cell, seed, seconds, trace, t_process_start, allow_cpu)
    ctx.log(f"cell {workload} seed {seed} seconds {seconds} trace {int(trace)} device {ctx.device}")
    run = cell.driver().run(ctx)
    run["ctx"] = ctx
    metrics: Dict[str, dict] = collections.OrderedDict()
    if trace:
        for m in cell.per_layer():
            value = cell.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": float(run["end_to_end"][m["name"]]), "unit": m["unit"]}
    device = dict(ctx.device, memory_peak_bytes=int(run["memory_peak_bytes"]))
    result = collections.OrderedDict(
        correct=bool(run["verdict"].correct), attempted=int(run["attempted"]),
        failed=int(run["failed"]), metrics=metrics, device=device)
    if trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
    result["compared"] = run["verdict"].rows
    run["verdict"].print_stderr()
    return result

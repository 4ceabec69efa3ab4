"""One run of one cell: discovery by name, the device, set-up and window
clocks, host spans, the trace, and the result line.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration's sizes; its
  ``rows`` names the row generator ``bench/rows/<rows>.py`` and its
  ``reference`` the plain reference ``bench/refs/<reference>.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix; its ``driver``
  names ``bench/drivers/<driver>.py``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or None where the run has nothing to read.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def process_start() -> float:
    """``time.monotonic()`` at which this process started (from /proc,
    to 10 ms); the import time of this module where /proc is missing."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.monotonic() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_IMPORTED = time.monotonic()


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              path.resolve().with_suffix("").as_posix())
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench: Path = BENCH

    @property
    def driver(self):
        return load_module(self.bench / "drivers" / f"{self.traffic['driver']}.py")

    @property
    def rows(self):
        return load_module(self.bench / "rows" / f"{self.config['rows']}.py")

    @property
    def reference(self):
        return load_module(self.bench / "refs" / f"{self.config['reference']}.py")

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric}.py")

    def metrics(self, traced: bool) -> list[dict]:
        """The metrics this cell reports: end-to-end ones untraced,
        per-layer ones traced, each where its ``workloads`` names the
        cell or it has none."""
        pool = self.per_layer if traced else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` of ``<root>/BENCHMARK.json``, its files
    found under ``<root>/bench``."""
    root = Path(root)
    spec = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(root / cfg_entry["file"]),
                traffic_name=w["traffic"],
                traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                end_to_end=spec["end_to_end"], per_layer=spec["per_layer"],
                bench=root / "bench")


def pair_cell(config: str, traffic: str, root: Path = ROOT) -> Cell:
    """A one-chip cell of ``bench/configs/<config>.json`` under
    ``bench/traffic/<traffic>.json`` that ``BENCHMARK.json`` need not
    list (knee sweeps, tests); it reports no metrics."""
    bench = Path(root) / "bench"
    return Cell(name=f"{traffic}:{config}", chips=1,
                config=load_json(bench / "configs" / f"{config}.json"),
                traffic_name=traffic,
                traffic=load_json(bench / "traffic" / f"{traffic}.json"),
                end_to_end=[], per_layer=[], bench=bench)


def require_chips(chips: int):
    """The devices, or exit non-zero: never a CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; JAX sees {len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache``), keeping every program however
    quick its compile."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class Stalls:
    """What the host did besides the window's work: the interpreter's
    garbage collections, and the CPU time of all the process's threads."""

    def __init__(self):
        self.t0, self.cpu0 = time.monotonic(), time.process_time()
        self.pauses: list[float] = []
        self._gc_t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.pauses.append(time.perf_counter() - self._gc_t)

    def close(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        wall = time.monotonic() - self.t0
        out = {"gc_collections": len(self.pauses),
               "gc_pause_max_ms": 1e3 * max(self.pauses, default=0.0),
               "gc_pause_total_ms": 1e3 * sum(self.pauses),
               "process_cpu_per_wall": (time.process_time() - self.cpu0) / wall}
        return out


class MetricMissing(RuntimeError):
    """A metric that the cell lists read nothing."""


class Run:
    """State of one run, filled by the driver and read by the metric
    readers."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float,
                 trace: bool, devices, t_start: float | None = None):
        self.cell, self.seed, self.seconds, self.traced = cell, seed, seconds, trace
        self.devices = devices
        self.t_start = process_start() if t_start is None else t_start
        self.config, self.traffic = cell.config, cell.traffic
        self.setup_s: float | None = None
        self.window_s: float | None = None
        self.attempted = 0
        self.failed = 0
        self.counts: dict[str, float] = {}
        self.samples: dict[str, Any] = {}
        self.checks: dict[str, dict] = {}
        self.trace = None
        self.memory_peak_bytes: int | None = None
        # Programs looked up per phase, and of those found in the
        # persistent cache; the rest were compiled.
        self.compiles = {ph: {"programs": 0, "cache_hits": 0}
                         for ph in ("setup", "window", "after")}
        self._phase = "setup"
        self._trace_dir = None
        self._window_span = None
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    # -- clocks --------------------------------------------------------
    def _on_event(self, event: str, *_, **__) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[self._phase]["programs"] += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.compiles[self._phase]["cache_hits"] += 1

    def window_opens(self) -> float:
        """End of set-up, start of the window.  A traced run starts the
        profiler before this, so that its start is set-up, and opens the
        WINDOW span here."""
        if self.traced:
            import jax

            from harness import trace

            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # Host spans and JAX's own dispatch events, but no event per
            # Python call: that tracer would slow the host loop manyfold.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir, profiler_options=options)
            self._window_span = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._window_span.__enter__()
        # Set-up's garbage is collected now and what survives it is kept
        # out of later collections, so that a collection of the window's
        # own garbage does not rescan it.
        gc.collect()
        gc.freeze()
        self._stalls = Stalls()
        now = time.monotonic()
        self.setup_s = now - self.t_start
        self._phase = "window"
        return now

    @property
    def tracing(self) -> bool:
        return self._window_span is not None

    def trace_stops(self) -> None:
        """Close the traced part of the window (a no-op untraced)."""
        if self._window_span is None:
            return
        import jax

        self._window_span.__exit__(None, None, None)
        self._window_span = None
        jax.profiler.stop_trace()

    def window_closes(self, t_open: float, t_close: float | None = None) -> None:
        """Record the window, read the memory peak, reduce the trace."""
        t_close = time.monotonic() if t_close is None else t_close
        self.window_s = t_close - t_open
        self._phase = "after"
        self.counts.update(self._stalls.close())
        gc.unfreeze()
        self.trace_stops()
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)
        if self._trace_dir is not None:
            from harness import trace

            try:
                self.trace = trace.reduce_file(trace.find_xplane(self._trace_dir))
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
                self._trace_dir = None

    # -- spans -----------------------------------------------------------
    def span(self, name: str):
        """A host span in the trace while the run traces, else nothing."""
        if self._window_span is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    # -- checks ----------------------------------------------------------
    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values()))

    # -- result ----------------------------------------------------------
    def result(self) -> dict:
        metrics = {}
        missing = []
        for m in self.cell.metrics(self.traced):
            value = self.cell.reader(m["name"]).read(self)
            if value is None:
                missing.append(m["name"])
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if missing:
            # A metric the cell lists that finds nothing to read (a module
            # renamed or fused away, a span gone) fails the run.
            raise MetricMissing(f"{self.cell.name}: nothing to read for {missing}")
        d = self.devices[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(self.devices),
                  "memory_peak_bytes": int(self.memory_peak_bytes or 0)}
        out: dict[str, Any] = {
            "correct": self.correct, "attempted": int(self.attempted),
            "failed": int(self.failed), "metrics": metrics, "device": device,
            "compiles": dict(self.compiles),
        }
        if self.trace is not None:
            device["busy_s"] = self.trace.busy_s
            device["window_s"] = self.trace.window_s
            out["breakdown"] = self.trace.breakdown()
        out["checks"] = self.checks
        return out

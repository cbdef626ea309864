"""What every cell shares: finding a cell's files by name, the device,
compile counting, host spans, the traced window and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own that this module finds by the
name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: one deployment (its ``file`` entry);
* ``bench/traffic/<traffic>.json``: one traffic mix, naming its driver;
* ``bench/drivers/<driver>.py``: a loop kind, ``run(cell) -> Outcome``;
* ``bench/metrics/<metric>.py``: one per-layer metric, ``read(ctx)``
  (or one reader for every ``<metric>.<kind>``);
* ``bench/peaks.json``: published peaks keyed by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a missing file)."""


# --------------------------------------------------------------------------
# cells, by name
# --------------------------------------------------------------------------

def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def driver(self):
        name = self.traffic["driver"]
        return _load_module(os.path.join(BENCH, "drivers", f"{name}.py"),
                            f"bench_driver_{name}")


def load_cell(name: str, root: str = ROOT) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(BENCH, "traffic",
                                      f"{w['traffic']}.json"))
    applies = lambda m: name in m.get("workloads", [name])
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def metric_reader(name: str):
    """``metrics/<name>.py``, or else the reader of the name's stem before
    its first dot (``device_idle_share.train`` -> ``device_idle_share``),
    so that one reader can serve a quantity split by kind of cell."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", f"{name.split('.')[0]}.py")
    return _load_module(path, f"bench_metric_{name.replace('.', '_')}")


def peaks(device_kind: str) -> dict:
    table = _read_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def configure_jax() -> str:
    """JAX's persistent compilation cache at a fixed place inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program, so that only a cell's first run in a checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(count: int) -> list:
    """The first ``count`` TPU chips; no fallback to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is on platform "
                         f"{devs[0].platform!r}")
    if len(devs) < count:
        raise BenchError(f"{count} TPU chips needed, {len(devs)} found")
    return devs[:count]


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class CompileCounter:
    """Counts backend compiles (and their seconds) process-wide."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# --------------------------------------------------------------------------
# host spans and the traced window
# --------------------------------------------------------------------------

class Spans:
    """Named host intervals kept in memory, written into the profiler's
    trace as annotations when one is being taken."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: dict[str, list] = {}
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        t1 = time.perf_counter()
        with self._lock:
            self.spans.setdefault(name, []).append((t0, t1))

    def add(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self.spans.setdefault(name, []).append((t0, t1))


class Window:
    """The measured window.  With ``trace`` the profiler records it into a
    temporary directory; ``xplane`` is the file once it has stopped."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.t0 = self.t1 = None
        self._dir = None
        self.xplane = None

    def __enter__(self):
        if self.trace:
            import jax
            self._dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir.name, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def close(self):
        """End the window (idempotent); stops the profiler."""
        if self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        if self.trace:
            import glob
            import jax
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            found = glob.glob(os.path.join(self._dir.name, "**",
                                           "*.xplane.pb"), recursive=True)
            self.xplane = found[0] if found else None

    def __exit__(self, *exc):
        self.close()

    def cleanup(self):
        if self._dir is not None:
            self._dir.cleanup()
            self._dir = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# --------------------------------------------------------------------------
# what a driver hands back
# --------------------------------------------------------------------------

@dataclass
class Check:
    """One number compared, with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: dict            # metric name -> value
    checks: list                # [Check]
    device: dict
    setup_s: float
    notes: list = field(default_factory=list)   # first lines of the run
    layer_ctx: dict = field(default_factory=dict)
    window: Window | None = None


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation; +inf values
    (shed requests) count as the largest."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or v[lo] == v[hi]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)

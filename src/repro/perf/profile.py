"""Profiler trace capture + per-op compiled-cost harvesting.

Two thin, dependency-free views into what the kernels actually cost:

* :func:`trace` — a context manager around ``jax.profiler`` trace
  capture.  Everything executed inside lands in a TensorBoard/Perfetto
  trace directory (``benchmarks/run.py --profile`` wraps one benchmark
  section in it and uploads the directory from CI).
* :func:`op_costs` — lower + compile a callable and harvest the
  compiler's own cost model: flops, bytes accessed, and (where the
  backend reports it) optimal seconds.  This is the *static* cost view
  that pairs with a measured wall time to give achieved-vs-attainable
  (:mod:`benchmarks.roofline` uses its own analytic model instead, so
  the roofline gate cannot drift when XLA's cost tables change; the two
  are cross-checkable in the profile report).
"""
from __future__ import annotations

import contextlib
import json
import os

import jax

__all__ = ["trace", "op_costs", "profile_ops", "write_report"]


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a ``jax.profiler`` trace of the enclosed block into
    ``logdir`` (created if missing).  Yields the directory; view with
    TensorBoard's profile plugin or Perfetto.

    Keep the enclosed block BOUNDED — a handful of dispatches, not a
    bench run: the profiler buffers every event in host memory until
    ``stop_trace``, so minutes of hot-loop dispatches (e.g. the tuner's
    grid race) exhaust RAM instead of producing a trace.
    :func:`profile_ops` with ``logdir`` is the safe packaged form."""
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def op_costs(fn, *args, static_argnames=()) -> dict:
    """Compile ``fn(*args)`` and return the compiler's cost view:
    ``{"flops", "bytes", "peak_memory", "optimal_seconds"}`` (0.0 where
    the backend does not report a term).  ``fn`` is jitted here — pass
    the un-jitted body; already-jitted callables lower fine too."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnames=static_argnames)
    compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "optimal_seconds": float(cost.get("optimal_seconds", 0.0)),
    }
    try:
        mem = compiled.memory_analysis()
        out["peak_memory"] = float(
            getattr(mem, "temp_size_in_bytes", 0.0) or 0.0)
    except Exception:       # backends without memory analysis
        out["peak_memory"] = 0.0
    return out


def profile_ops(named: dict, *, logdir: str | None = None) -> dict:
    """Harvest :func:`op_costs` for ``{name: (fn, args)}``; when
    ``logdir`` is given, also execute each op once under a profiler
    trace (one trace for the whole set — per-op spans are visible inside
    it).  Returns ``{name: costs}``."""
    report = {name: op_costs(fn, *args) for name, (fn, args) in named.items()}
    if logdir is not None:
        with trace(logdir):
            for fn, args in named.values():
                jax.block_until_ready(jax.jit(fn)(*args)
                                      if not hasattr(fn, "lower")
                                      else fn(*args))
    return report


def write_report(report: dict, path: str) -> str:
    """Serialize a :func:`profile_ops` report to JSON (the CI artifact)."""
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    return path

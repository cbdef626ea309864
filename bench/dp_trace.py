"""What the data-parallel trainer writes into a profiler trace, read back
from the trace file alone, on the trace's own clock.

* Host spans ``dp.local``, ``dp.gather``, ``dp.sync`` and
  ``dp.broadcast``: one each per global batch (the last three at a sync
  only), around the dispatch of the local step, the copy of the shard
  deltas to the root device, the merge and apply there, and the copy of
  the merged forest back to every device.  On a v5e host a
  ``jax.device_put`` from one chip to another runs through host memory
  (``TransferFromDevice``, a host transpose, ``TransferToDevice``, all
  on host threads) and returns when it is done, so the gather and
  broadcast spans last the whole copy; no device plane records it.
* Runs of the sync program ``jit_dp_sync``: events of the root device's
  ``XLA Modules`` line (the first device plane).
* Scopes ``dp.merge`` and ``dp.apply``: the name stack of every op of
  that program (``tf_op``, read by ``program_trace.tf_ops``).

A profiler may drop part of a device's events in a long window (the
root device's plane then holds a ``Trace Buffers Dropped`` event), so
per-sync device times divide by the sync runs the trace holds.
"""
from __future__ import annotations

import functools

import program_trace

GATHER, BROADCAST = "dp.gather", "dp.broadcast"
SCOPES = ("dp.merge", "dp.apply")
SYNC_MODULE = "jit_dp_sync"
MODULES_LINE = "XLA Modules"


@functools.lru_cache(maxsize=None)
def host_spans(path: str) -> list:
    """(start_ns, end_ns, name) of every ``dp.*`` span of the host planes
    that starts inside the window."""
    from jax.profiler import ProfileData
    win = program_trace.window(path)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#", 1)[0]
                if name.startswith("dp.") and (
                        win is None or win[0] <= e.start_ns <= win[1]):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                name))
    return out


@functools.lru_cache(maxsize=None)
def sync_runs(path: str) -> int:
    """Runs of the sync program on the root device that start inside the
    window."""
    from jax.profiler import ProfileData
    win = program_trace.window(path)
    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    if not planes or win is None:
        return 0
    return sum(1 for line in planes[0].lines if line.name == MODULES_LINE
               for e in line.events
               if e.name.split("(", 1)[0] == SYNC_MODULE
               and win[0] <= e.start_ns <= win[1])


def scope_of(tf_op) -> str | None:
    for part in (tf_op or "").split("/"):
        if part in SCOPES:
            return part
    return None


@functools.lru_cache(maxsize=None)
def root_scopes(path: str) -> dict:
    """Seconds of the window's time on the root device by scope
    (``dp.merge``, ``dp.apply``), each instant to the innermost op
    running."""
    devices, win = program_trace._device_ops(path)
    if not devices:
        return {}
    ops = program_trace.tf_ops(path)
    scoped = [(s, e, scope_of(ops.get(name))) for s, e, name in devices[0]]
    return program_trace._innermost(
        [iv for iv in scoped if iv[2] is not None], *win)

"""From a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` file that ``jax.profiler`` writes, with nothing
but JAX.  A device is a plane named ``/device:TPU:<i>``; its ``XLA Ops``
line holds one event per operation that ran, named by its HLO
instruction (``%qo_update_leaves_pallas.1 = f32[...] custom-call(...)``).
An operation's *kernel name* is that instruction name without the
``%`` and the numeric suffix.

* busy: the union of the op intervals on a device inside the window,
  averaged over the devices used;
* kernel time: the summed durations of the ops with one kernel name,
  and their count, an op cut by the window's edge counting by the share
  of its duration inside it;
* idle gaps: the stretches of the window in which the device ran no op,
  named by the innermost host span (``harness.Spans``) around their
  midpoint; those under 10 us are the device's own gaps between ops.

The window is the ``bench.window`` annotation on the host, which shares
the trace's clock; the host spans are placed on that clock through it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
#: idle stretches shorter than this are the device's own op-to-op gaps
SHORT_GAP_NS = 10_000
BETWEEN_OPS = "device:between-ops"


def kernel_name(op: str) -> str:
    m = _NAME.match(op)
    return m.group(1) if m else op.split(" ", 1)[0]


def union_length(intervals) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """The (start, end) stretches of [lo, hi] no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class Reduction:
    window_s: float
    busy_s: float                          # averaged over devices
    kernel_s: dict                         # kernel name -> seconds (all devs)
    kernel_calls: dict                     # kernel name -> ops inside
    idle_by_host: dict = field(default_factory=dict)   # span -> seconds

    def kernel_seconds(self, name: str) -> float:
        return self.kernel_s.get(name, 0.0)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def window_of(pd):
    """(start_ns, end_ns) of the ``bench.window`` annotation, or None."""
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name == WINDOW_SPAN:
                    return s, e
    return None


def reduce(path: str, n_devices: int, spans=None, window=None) -> Reduction:
    """Reduce the trace at ``path``.  ``window``: the (t0, t1) of the
    window on the host's ``perf_counter`` clock, matched to the
    ``bench.window`` annotation to place ``spans`` (a ``harness.Spans``)
    on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)[:n_devices]
    ops = []
    for p in devices:
        per = [ev for line in p.lines if line.name == OPS_LINE
               for ev in _events(line)]
        ops.append(per)
    win = window_of(pd)
    if win is None:
        flat = [(s, e) for per in ops for _, s, e in per]
        win = (min(s for s, _ in flat), max(e for _, e in flat))
    lo, hi = win
    kernel_s, calls = defaultdict(float), defaultdict(float)
    busy, idle_by_host = [], defaultdict(float)
    host = _host_spans(spans, window, lo) if spans is not None else []
    for i, per in enumerate(ops):
        clipped = [(max(s, lo), min(e, hi)) for _, s, e in per
                   if e > lo and s < hi]
        busy.append(union_length(clipped))
        for name, s, e in per:
            if e > lo and s < hi:
                k = kernel_name(name)
                inside = min(e, hi) - max(s, lo)
                kernel_s[k] += inside * 1e-9
                calls[k] += inside / (e - s) if e > s else 1.0
        if i == 0:
            for a, b in gaps(clipped, lo, hi):
                label = BETWEEN_OPS if b - a < SHORT_GAP_NS \
                    else _label(host, (a + b) / 2)
                idle_by_host[label] += (b - a) * 1e-9
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=sum(busy) / len(busy) * 1e-9 if busy else 0.0,
                     kernel_s=dict(kernel_s), kernel_calls=dict(calls),
                     idle_by_host=dict(idle_by_host))


def _host_spans(spans, window, lo_ns):
    """Host spans as (start_ns, end_ns, name) on the trace's clock."""
    if window is None:
        return []
    off = lo_ns - window[0] * 1e9
    return [(a * 1e9 + off, b * 1e9 + off, name)
            for name, ivs in spans.spans.items() for a, b in ivs]


def _label(host, t: float) -> str:
    best = None
    for s, e, name in host:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host:no-span"

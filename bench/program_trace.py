"""What the program writes into a profiler trace, read back from the
trace file alone, on the trace's own clock.

* Stage scopes.  The forest step names its stages with
  ``jax.named_scope`` (``forest.test``, ``forest.route``,
  ``forest.absorb``, ``forest.attempt``, ``forest.drift``).  An op's name
  stack reaches the trace as the ``tf_op`` stat of the op's event
  metadata on its device plane (``jit(_learn)/forest.absorb/...``).
  ``jax.profiler.ProfileData`` does not expose event-metadata stats, so
  :func:`tf_ops` reads them with a walk of the XSpace protobuf, with
  nothing but the standard library.
* Host spans.  The program's ``jax.profiler.TraceAnnotation`` spans
  (``engine.*``, ``serve.*``) and the benchmark's own (``bench.*``) are
  events of the host plane's lines; a span's arguments, where the trace
  keeps them in its name, follow a ``#`` and are dropped.

Stage time is attributed instant by instant: while the device runs an
op, the instant belongs to the innermost op running (the latest
started), and so to that op's stage.  Control-flow ops (``conditional``,
``while``) cover their children on the same line, so a plain sum would
count the children twice; this way the stages and ``unscoped`` (ops
under no stage scope) add up to exactly the device's busy time.

Each file is read once per process, whichever reader asks first.
"""
from __future__ import annotations

import functools
import glob
import heapq
import os
import tempfile
from collections import defaultdict

STAGES = ("test", "route", "absorb", "attempt", "drift")
UNSCOPED = "unscoped"
WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "engine.", "serve.")
PUBLISH, FETCH = "engine.publish", "serve.freeze.fetch"
OPS_LINE = "XLA Ops"
#: idle stretches shorter than this are the device's own op-to-op gaps
SHORT_GAP_NS = 10_000
BETWEEN_OPS = "device:between-ops"
NO_SPAN = "host:no-span"


# --------------------------------------------------------------------------
# the XSpace protobuf, by its wire format
# --------------------------------------------------------------------------

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one message in ``buf[lo:hi]``: an int
    for a varint, a (start, end) span of ``buf`` for a length-delimited
    field, ``None`` for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entries(buf: bytes, spans):
    """The values of a protobuf map field (entries ``{1: key, 2: value}``)."""
    for span in spans:
        entry = dict(_fields(buf, *span))
        if 2 in entry:
            yield entry[2]


@functools.lru_cache(maxsize=None)
def tf_ops(path: str) -> dict:
    """Event name -> ``tf_op`` (the op's name stack) of every op on the
    device planes of the trace at ``path``.  A name that occurs with
    more than one ``tf_op`` (two programs may name an op alike) maps to
    ``None``, as does one without the stat."""
    with open(path, "rb") as f:
        buf = f.read()
    out: dict = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:                           # XSpace.planes
            continue
        parts = defaultdict(list)
        for k, v in _fields(buf, *plane):
            parts[k].append(v)
        if not any(_text(buf, s).startswith("/device:")
                   for s in parts[2]):           # XPlane.name
            continue
        stat_names = {}
        for meta in _map_entries(buf, parts[5]):  # XPlane.stat_metadata
            m = dict(_fields(buf, *meta))
            if 1 in m and 2 in m:
                stat_names[m[1]] = _text(buf, m[2])
        tf_op_id = next((k for k, v in stat_names.items() if v == "tf_op"),
                        None)
        for meta in _map_entries(buf, parts[4]):  # XPlane.event_metadata
            name, op = None, None
            for k, v in _fields(buf, *meta):
                if k == 2:                        # XEventMetadata.name
                    name = _text(buf, v)
                elif k == 5 and tf_op_id is not None:   # .stats
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) != tf_op_id:
                        continue
                    if 5 in stat:                 # XStat.str_value
                        op = _text(buf, stat[5])
                    elif 7 in stat:               # XStat.ref_value
                        op = stat_names.get(stat[7])
            if name is None:
                continue
            out[name] = op if out.get(name, op) == op else None
    return out


def stage_of(tf_op) -> str:
    """The stage named in an op's name stack, or ``unscoped``."""
    for part in (tf_op or "").split("/"):
        if part.startswith("forest.") and part[7:] in STAGES:
            return part[7:]
    return UNSCOPED


# --------------------------------------------------------------------------
# events on the trace's clock
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _profile(path: str):
    """(device op events per device plane, host spans) of the trace: the
    ops as (start_ns, end_ns, name), the spans as (start_ns, end_ns,
    name, thread) for every ``bench.*``, ``engine.*`` or ``serve.*``
    annotation."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append((plane.name, [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name.split("#", 1)[0]
                    if name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns, name,
                                      line.name))
    devices.sort()
    return [ops for _, ops in devices], spans


def host_spans(path: str) -> list:
    return _profile(path)[1]


def window(path: str):
    """(start_ns, end_ns) of the ``bench.window`` span, or None."""
    return next(((s, e) for s, e, name, _ in host_spans(path)
                 if name == WINDOW_SPAN), None)


def _device_ops(path: str):
    """The op events of each device that ran an op inside the window,
    and the window (the ops' extent where the trace has no
    ``bench.window`` span)."""
    devices, _ = _profile(path)
    win = window(path)
    if win is None:
        flat = [op for ops in devices for op in ops]
        if not flat:
            return [], None
        win = (min(s for s, _, _ in flat), max(e for _, e, _ in flat))
    used = [ops for ops in devices
            if any(e > win[0] and s < win[1] for s, e, _ in ops)]
    return used, win


def _innermost(intervals, lo: float, hi: float, rest=None) -> dict:
    """Seconds of ``[lo, hi]`` by label: each instant goes to the label
    of the latest-started ``(start, end, label)`` interval covering it
    (the shortest, between two that start together), an instant none
    covers to ``rest`` unless that is None."""
    out = defaultdict(float)
    todo = sorted(iv for iv in intervals if iv[1] > lo and iv[0] < hi)
    points = sorted({lo, hi} | {t for s, e, _ in todo for t in (s, e)
                                if lo < t < hi})
    active, k = [], 0
    for a, b in zip(points, points[1:]):
        while k < len(todo) and todo[k][0] <= a:
            s, e, label = todo[k]
            heapq.heappush(active, (-s, e, label))
            k += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        label = active[0][2] if active else rest
        if label is not None:
            out[label] += (b - a) * 1e-9
    return dict(out)


@functools.lru_cache(maxsize=None)
def device_stages(path: str) -> dict:
    """Seconds of the window's device time by stage, plus ``unscoped``,
    averaged over the devices that ran an op in the window.  Empty where
    no op of the window carries a stage scope (a program that names no
    stage)."""
    devices, win = _device_ops(path)
    ops = tf_ops(path)
    total = defaultdict(float)
    for dev in devices:
        per = _innermost([(s, e, stage_of(ops.get(name)))
                          for s, e, name in dev], *win)
        for stage, sec in per.items():
            total[stage] += sec / len(devices)
    if set(total) <= {UNSCOPED}:
        return {}
    return {k: total.get(k, 0.0) for k in STAGES + (UNSCOPED,)}


@functools.lru_cache(maxsize=None)
def idle_by_span(path: str) -> dict:
    """Seconds of the window in which the first device ran no op, each
    instant by the innermost host span around it (``host:no-span``
    outside every span); stretches under 10 us are the device's own gaps
    between ops."""
    devices, win = _device_ops(path)
    if not devices:
        return {}
    spans = [(s, e, name) for s, e, name, _ in host_spans(path)]
    out, t = defaultdict(float), win[0]
    for s, e, _ in sorted(devices[0]) + [(win[1], win[1], None)]:
        s = min(s, win[1])
        if s - t >= SHORT_GAP_NS:
            for label, sec in _innermost(spans, t, s, NO_SPAN).items():
                out[label] += sec
        elif s > t:
            out[BETWEEN_OPS] += (s - t) * 1e-9
        t = max(t, e)
        if t >= win[1]:
            break
    return dict(out)


# --------------------------------------------------------------------------
# publishes
# --------------------------------------------------------------------------

def _children(spans, parent):
    s0, e0, _, thread = parent
    return [sp for sp in spans if sp[3] == thread and s0 <= sp[0]
            and sp[1] <= e0 and sp is not parent]


def _publish_spans(path: str) -> list:
    """The ``engine.publish`` spans that start inside the window."""
    win = window(path)
    return [sp for sp in host_spans(path) if sp[2] == PUBLISH
            and (win is None or win[0] <= sp[0] <= win[1])]


def publishes(path: str) -> list:
    """Every publish of the window, as (seconds, seconds its
    ``serve.freeze.fetch`` child covers)."""
    spans = host_spans(path)
    return [((sp[1] - sp[0]) * 1e-9,
             sum(e - s for s, e, name, _ in _children(spans, sp)
                 if name == FETCH) * 1e-9)
            for sp in _publish_spans(path)]


def publish_tree(path: str) -> list:
    """The span tree of the window's median publish (by length): its
    spans in order of start, each as [path of names, seconds]."""
    pubs = sorted(_publish_spans(path), key=lambda sp: sp[1] - sp[0])
    if not pubs:
        return []
    root = pubs[(len(pubs) - 1) // 2]
    inner = sorted(_children(host_spans(path), root),
                   key=lambda sp: (sp[0], -sp[1]))
    out, stack = [[PUBLISH, (root[1] - root[0]) * 1e-9]], [root]
    for sp in inner:
        while stack[-1] is not root and stack[-1][1] <= sp[0]:
            stack.pop()
        stack.append(sp)
        out.append(["/".join(s[2] for s in stack), (sp[1] - sp[0]) * 1e-9])
    return out


def breakdown(path: str) -> dict:
    """Where the window's device time and a publish's host time went."""
    return {"device_stages": device_stages(path),
            "publish_host": publish_tree(path),
            "idle_by_span": idle_by_span(path)}


# --------------------------------------------------------------------------
# the trace of this run
# --------------------------------------------------------------------------

def trace_of(red):
    """The trace file the run's window was recorded into: the one in a
    ``bench_trace_*`` temporary directory (where ``harness.Window``
    records it, kept until the result line is printed) whose
    ``bench.window`` span has the length of the reduction ``red``.  None
    where no such file is found."""
    if red is None:
        return None
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                           "*.xplane.pb")
    found = sorted(glob.glob(pattern, recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in found:
        win = window(path)
        if win is not None and abs((win[1] - win[0]) * 1e-9
                                   - red.window_s) < 1e-6:
            return path
    return None


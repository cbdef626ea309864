"""The trace reduction on a small trace recorded on a TPU v5 lite: a
forest of 2 trees x 63 nodes, 4 train steps through the engine inside the
window annotation (``bench/testdata/small_train.*``)."""
from __future__ import annotations

import json
import os

import pytest

import harness
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
XPLANE = os.path.join(DATA, "small_train.xplane.pb")


def test_union_and_gaps():
    ivs = [(0, 2), (1, 3), (5, 6), (5.5, 5.7), (8, 9)]
    assert tr.union_length(ivs) == 5
    assert tr.gaps(ivs, 0, 10) == [(3, 5), (6, 8), (9, 10)]
    assert tr.gaps(ivs, 1.5, 5.2) == [(3, 5)]
    assert tr.union_length([]) == 0


def test_kernel_name():
    op = ("%qo_update_leaves_pallas.1 = f32[10,8,16384,128]{3,2,1,0} "
          "custom-call(s32[1,65536]{1,0} %copy-done.112)")
    assert tr.kernel_name(op) == "qo_update_leaves_pallas"
    assert tr.kernel_name("%fusion.84 = f32[65536]{0} fusion(x)") == "fusion"
    assert tr.kernel_name("%copy.2057 = s32[16] copy(s32[16] %a)") == "copy"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_train.spans.json")) as f:
        meta = json.load(f)
    spans = harness.Spans(False)
    for name, ivs in meta["spans"].items():
        for a, b in ivs:
            spans.add(name, a, b)
    return tr.reduce(XPLANE, 1, spans, tuple(meta["window"])), meta


def test_recorded_trace(recorded):
    red, meta = recorded
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(XPLANE)
    lo, hi = tr.window_of(pd)
    assert red.window_s == pytest.approx((hi - lo) * 1e-9)
    # the window annotation and the host clock agree on its length
    assert red.window_s == pytest.approx(meta["window"][1]
                                         - meta["window"][0], abs=1e-3)
    assert 0 < red.busy_s < red.window_s
    # every absorb ran once per step, and it is the largest kernel
    assert red.kernel_calls["qo_update_leaves_pallas"] == 4
    assert red.kernel_calls["qo_route_pallas"] >= 4
    # busy is at least any one kernel's time and at most all ops' time
    assert max(red.kernel_s.values()) <= red.busy_s + 1e-9
    assert red.busy_s <= sum(red.kernel_s.values()) + 1e-9
    # busy and idle fill the window
    idle = sum(red.idle_by_host.values())
    assert red.busy_s + idle == pytest.approx(red.window_s, rel=1e-6)
    assert "train_once" in red.idle_by_host


def test_recorded_busy_matches_a_direct_count(recorded):
    """Busy time, counted afresh: sweep the op intervals in time order."""
    red, _ = recorded
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(XPLANE)
    lo, hi = tr.window_of(pd)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    marks = []
    for line in dev.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                if t > s:
                    marks += [(s, 1), (t, -1)]
    depth, last, busy = 0, None, 0.0
    for t, d in sorted(marks, key=lambda m: (m[0], -m[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert red.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)


@pytest.mark.parametrize("name", ["device_idle_share.train",
                                  "device_idle_share.serve"])
def test_idle_share_reader_serves_each_kind(recorded, name):
    red, _ = recorded
    share = harness.metric_reader(name).read({"trace": red})
    assert share == pytest.approx(100.0 * (1.0 - red.busy_s / red.window_s))
    assert 0.0 < share < 100.0
    assert harness.metric_reader(name).read({}) is None

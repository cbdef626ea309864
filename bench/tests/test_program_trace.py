"""Reading what the program writes into a trace (``program_trace``): the
stage scope of each device op and the engine's host spans, on two traces
recorded on a TPU v5 lite through the engine, 2 trees x 63 nodes and 4
train steps in the window each.  ``scoped_train`` was recorded from a
program that names its stages and spans
(``bench/tests/record_scoped_train.py``); ``small_train`` from one that
names neither."""
from __future__ import annotations

import copy
import os
import shutil
import tempfile

import pytest

import harness
import program_trace as pt
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
SCOPED = os.path.join(DATA, "scoped_train.xplane.pb")
UNSCOPED = os.path.join(DATA, "small_train.xplane.pb")
STEPS = 4
NEW_METRICS = [f"stage_ms_per_step.{s}" for s in pt.STAGES] \
    + ["publish_host_ms"]


def _op_named(path: str, kernel: str) -> str:
    return next(name for name in pt.tf_ops(path)
                if tr.kernel_name(name) == kernel)


def test_innermost_credits_each_instant_once():
    ivs = [(0, 10, "a"), (2, 5, "b"), (4, 12, "c"), (20, 21, "a")]
    got = pt._innermost(ivs, 0, 30)
    assert got == pytest.approx({"a": 3e-9, "b": 2e-9, "c": 8e-9})
    assert sum(got.values()) == pytest.approx(
        tr.union_length([(s, e) for s, e, _ in ivs]) * 1e-9)
    # clipped to a stretch, an interval keeps the priority of its start
    assert pt._innermost(ivs, 3, 14, rest="none") == pytest.approx(
        {"b": 1e-9, "c": 8e-9, "none": 2e-9})


@pytest.mark.parametrize("path", [SCOPED, UNSCOPED],
                         ids=["scoped", "unscoped"])
def test_the_walk_reads_tf_op(path):
    ops = pt.tf_ops(path)
    op = ops[_op_named(path, "qo_update_leaves_pallas")]
    assert op.startswith("jit(_learn)/")
    assert "jit(qo_update_leaves_pallas)/pallas_call" in op
    assert sum(v is not None for v in ops.values()) > 100


def test_absorb_kernel_falls_under_its_stage():
    op = pt.tf_ops(SCOPED)[_op_named(SCOPED, "qo_update_leaves_pallas")]
    assert pt.stage_of(op) == "absorb"
    # XLA merges the training route into the prequential one, the same
    # kernel on the same trees and rows: one route kernel per step
    route = [v for k, v in pt.tf_ops(SCOPED).items()
             if tr.kernel_name(k) == "qo_route_pallas"]
    assert route and {pt.stage_of(v) for v in route} <= {"test", "route"}


@pytest.mark.parametrize("path", [SCOPED, UNSCOPED],
                         ids=["scoped", "unscoped"])
def test_stages_add_up_to_busy_time(path):
    busy = tr.reduce(path, 1).busy_s
    stages = pt.device_stages(path)
    if path == UNSCOPED:
        assert stages == {}
        return
    assert set(stages) == set(pt.STAGES) | {pt.UNSCOPED}
    assert all(0.0 <= v <= busy for v in stages.values())
    assert sum(stages.values()) == pytest.approx(busy, rel=1e-9)
    # the absorb kernel's stage holds at least the kernel's own time
    red = tr.reduce(path, 1)
    assert stages["absorb"] >= red.kernel_seconds("qo_update_leaves_pallas")


def test_publish_idle_is_labelled_by_a_serve_span():
    idle = pt.idle_by_span(SCOPED)
    red = tr.reduce(SCOPED, 1)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)
    serve = sum(v for k, v in idle.items() if k.startswith("serve."))
    assert serve > 0
    # the idle time inside the publish falls in its children, not in the
    # publish itself or outside every span
    inside = sum(v for k, v in idle.items()
                 if k.startswith(("serve.", "engine.")))
    loose = idle.get(pt.PUBLISH, 0.0) + idle.get(pt.NO_SPAN, 0.0)
    assert loose <= 0.1 * (inside + loose)


def test_publish_spans_and_tree():
    pubs = pt.publishes(SCOPED)
    assert len(pubs) == 1
    span, fetch = pubs[0]
    assert 0 < fetch < span
    tree = dict((path, sec) for path, sec in pt.publish_tree(SCOPED))
    assert tree[pt.PUBLISH] == pytest.approx(span)
    for child in ("serve.freeze", "serve.validate", "engine.swap"):
        assert f"{pt.PUBLISH}/{child}" in tree
    assert tree[f"{pt.PUBLISH}/serve.freeze/{pt.FETCH}"] \
        == pytest.approx(fetch)
    assert pt.publishes(UNSCOPED) == [] and pt.publish_tree(UNSCOPED) == []


@pytest.fixture
def in_tempdir(tmp_path, monkeypatch):
    """Place a recorded trace where ``harness.Window`` records a run's,
    and hand back the reduction the run's readers get."""
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    def place(path):
        dest = tmp_path / "bench_trace_x" / "plugins" / "profile" / "t"
        dest.mkdir(parents=True)
        shutil.copyfile(path, dest / "host.xplane.pb")
        return tr.reduce(str(dest / "host.xplane.pb"), 1)
    return place


def test_readers_read_the_scoped_trace(in_tempdir):
    red = in_tempdir(SCOPED)
    ctx = {"trace": red, "steps": STEPS}
    stages = pt.device_stages(pt.trace_of(red))
    for s in pt.STAGES:
        got = harness.metric_reader(f"stage_ms_per_step.{s}").read(ctx)
        assert got == pytest.approx(1e3 * stages[s] / STEPS)
    span, fetch = pt.publishes(SCOPED)[0]
    assert harness.metric_reader("publish_host_ms").read(ctx) \
        == pytest.approx(1e3 * (span - fetch))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_are_silent_on_an_unscoped_trace(in_tempdir, name):
    red = in_tempdir(UNSCOPED)
    assert harness.metric_reader(name).read({"trace": red,
                                             "steps": STEPS}) is None
    assert harness.metric_reader(name).read({}) is None


def test_readers_find_no_trace_of_another_window(in_tempdir):
    red = in_tempdir(SCOPED)
    other = tr.Reduction(window_s=red.window_s + 1.0, busy_s=0.0,
                         kernel_s={}, kernel_calls={})
    assert pt.trace_of(other) is None
    assert harness.metric_reader("publish_host_ms").read(
        {"trace": other, "steps": STEPS}) is None


@pytest.mark.parametrize("name,value", [
    ("device_idle_share.train", 86.9495862803398),
    ("qo_update_leaves_roofline", 2.163898082821821),
    ("query_ms_per_step", 0.009928500000000002)])
def test_accepted_metrics_on_the_unscoped_trace_are_unchanged(name, value):
    """The per-layer metrics the benchmark already had read what they
    read before the program named its stages and spans."""
    cfg = copy.deepcopy(harness.load_cell("friedman1.train").config)
    cfg["forest"].update(n_trees=2, max_nodes=63)
    ctx = {"trace": tr.reduce(UNSCOPED, 1), "steps": STEPS, "config": cfg,
           "peaks": harness.peaks("TPU v5 lite")}
    assert harness.metric_reader(name).read(ctx) == pytest.approx(
        value, rel=1e-12)

"""The serving engine driving the data-parallel trainer (DESIGN.md §4.1,
§5.6): ``ServingEngine.train_once`` learns one global batch through
``build_data_parallel_forest``'s ``update`` and publishes the replicated
forest at its own ``sync_every``.

Pinned here: every published snapshot is the freeze of the single-device
reference of the same protocol (``build_data_parallel_reference``),
bitwise; the exchange counters; the ``dp.*`` host spans in a profiler
trace and the ``dp.merge`` / ``dp.apply`` scopes of the sync's ops; and
the cadence rule.  Four devices come from the forced-host-device
subprocess idiom of tests/test_dp.py, run once for the module.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import engine as eng
from repro.core import forest as fr
from repro.core import hoeffding as ht
from repro.launch.mesh import make_mesh_auto
from repro.train import sharding as sh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
D, B, STEPS, SYNC_EVERY = 4, 256, 8, 2
DP_SPANS = ("dp.local", "dp.gather", "dp.sync", "dp.broadcast")

CHILD = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine as eng, forest as fr, hoeffding as ht
from repro.core import serve as sv
from repro.data import synth
from repro.train import sharding as sh
from repro.launch.mesh import make_mesh_auto

D, B, STEPS, SYNC_EVERY = {D}, {B}, {STEPS}, {SYNC_EVERY}
tree = ht.HTRConfig(n_features=4, max_nodes=31, n_bins=32, grace_period=100,
                    max_depth=6, r0=0.25, split_backend="jnp")
cfg = fr.ForestConfig(tree=tree, n_trees=4)
X, y = synth.piecewise_regression(D * B * (STEPS + 2), n_features=4, seed=7)
batch = lambda s: (X[s * D * B:(s + 1) * D * B], y[s * D * B:(s + 1) * D * B])

mesh = make_mesh_auto((D,), ("data",))
dp = sh.build_data_parallel_forest(cfg, mesh, "data", sync_every=1)
ref = sh.build_data_parallel_reference(cfg, D, sync_every=1)
key = jax.random.PRNGKey(5)
e = eng.ServingEngine(dp, dp.init(key), batch,
                      cfg=eng.EngineConfig(sync_every=SYNC_EVERY))
st_r = ref.init(key)
same = lambda a, b: all(np.array_equal(np.asarray(u), np.asarray(v))
                        for u, v in zip(jax.tree.leaves(a),
                                        jax.tree.leaves(b)))
fields = ("feature", "threshold", "child", "is_leaf", "leaf_mean", "vote_w",
          "depth", "step")
publishes = 0
for s in range(STEPS):
    e.train_once()
    st_r, aux = ref.update(st_r, *batch(s))
    assert aux is not None
    assert same(e._trainer.model(e._state), ref.model(st_r)), s
    if (s + 1) % SYNC_EVERY == 0:
        snap = e.snapshot_for_version(e.published_version)
        want = sv.freeze(ref.model(st_r), version=e.published_version,
                         step=s + 1)
        assert all(same(getattr(snap, f), getattr(want, f))
                   for f in fields), s
        # a snapshot of the replicated forest lives on one device
        assert all(len(getattr(snap, f).sharding.device_set) == 1
                   for f in fields if f != "depth"), s
        publishes += 1
t = e.submit(np.asarray(X[:40]))
assert e.serve_once() == 40 and t.status == "done"
np.testing.assert_array_equal(t.result, np.asarray(sv.predict_snapshot(
    e.snapshot_for_version(t.version), np.asarray(X[:40]))))
nodes = int(np.asarray(e._state["forest"]["trees"]["n_nodes"]).max())
devices = len(e._state["delta"]["ao_sum_x"].sharding.device_set)

jax.profiler.start_trace(sys.argv[1])
try:
    for s in range(STEPS, STEPS + SYNC_EVERY):
        e.train_once()
finally:
    jax.profiler.stop_trace()
print(json.dumps({{"publishes": publishes, "nodes": nodes,
                   "devices": devices, "metrics": {{
                       k: v for k, v in e.metrics().items()
                       if isinstance(v, (int, float))}}}}))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    """One four-device run of the engine over the DP trainer: its result
    line and the directory of the trace of its last steps."""
    trace = tmp_path_factory.mktemp("dp_trace")
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}")
    out = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(CHILD.format(D=D, B=B, STEPS=STEPS,
                                      SYNC_EVERY=SYNC_EVERY)), str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), trace


def test_every_publish_is_the_reference_forest_bitwise(child):
    res, _ = child
    assert res["publishes"] == STEPS // SYNC_EVERY
    assert res["devices"] == D
    assert res["nodes"] > 1                       # the trees grew
    m = res["metrics"]
    assert m["publishes"] == 1 + (STEPS + SYNC_EVERY) // SYNC_EVERY
    assert sum(m[k] for k in ("trainer_crashes", "recoveries",
                              "publish_failures", "rollbacks")) == 0


def test_exchange_counters_count_every_sync(child):
    res, _ = child
    m = res["metrics"]
    tree = ht.HTRConfig(n_features=4, max_nodes=31, n_bins=32)
    cfg = fr.ForestConfig(tree=tree, n_trees=4)
    shapes = jax.eval_shape(
        lambda: sh.init_data_parallel(cfg, jax.random.PRNGKey(0), D))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    per_sync = (D - 1) * (nbytes(shapes["delta"]) // D
                          + nbytes(shapes["forest"]))
    syncs = STEPS + SYNC_EVERY                 # sync_every=1: every step
    assert m["dp_syncs"] == syncs
    assert m["dp_exchange_bytes"] == syncs * per_sync
    assert m["dp_exchange_s"] > 0


def _spans(trace_dir) -> list:
    """(parent, name) of every ``engine.*`` / ``dp.*`` host span, the
    parent being the innermost such span around it on its thread."""
    path, = trace_dir.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = sorted((e.start_ns, -e.duration_ns, e.name.split("#")[0])
                         for e in line.events
                         if e.name.startswith(("engine.", "dp.")))
            stack = []
            for s, neg_d, name in evs:
                while stack and stack[-1][0] <= s:
                    stack.pop()
                out.append((stack[-1][1] if stack else None, name))
                stack.append((s - neg_d, name))
    return out


def test_dp_spans_sit_in_train_once(child):
    _, trace = child
    tree = _spans(trace)
    for name in DP_SPANS:
        assert sum(n == name for _, n in tree) == SYNC_EVERY, name
        assert {p for p, n in tree if n == name} == {"engine.train_once"}
    assert sum(n == "engine.publish" for _, n in tree) == 1


def _sync_op_names(backend: str) -> list:
    tree = ht.HTRConfig(n_features=4, max_nodes=15, n_bins=8,
                        split_backend=backend)
    cfg = fr.ForestConfig(tree=tree, n_trees=2, subspace=0.5)
    st = jax.eval_shape(
        lambda: sh.init_data_parallel(cfg, jax.random.PRNGKey(0), D))
    text = sh._dp_sync_jit(cfg).lower(st["forest"], st["delta"]) \
        .as_text(debug_info=True)
    return re.findall(r'loc\("(jit\(dp_sync\)/[^"]*)"', text)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_sync_ops_sit_under_merge_or_apply(backend):
    names = _sync_op_names(backend)
    assert names
    scopes = [[p for p in n.split("/") if p in ("dp.merge", "dp.apply")]
              for n in names]
    assert all(len(s) == 1 for s in scopes), names
    assert {s[0] for s in scopes} == {"dp.merge", "dp.apply"}
    if backend == "interpret":
        merges = [n for n in names if "qo_merge_pallas" in n]
        assert {n.split("/")[1] for n in merges} == {"dp.merge", "dp.apply"}


def _tiny_forest():
    tree = ht.HTRConfig(n_features=4, max_nodes=31, n_bins=32,
                        grace_period=100, max_depth=6, r0=0.25)
    return fr.ForestConfig(tree=tree, n_trees=4)


def _stream(step):
    rng = np.random.default_rng(step)
    X = rng.normal(0, 1, (B, 4)).astype(np.float32)
    return X, (2.0 * (X[:, 0] > 0)).astype(np.float32)


@pytest.mark.parametrize("trainer_every,engine_every", [(2, 3), (2, 1),
                                                        (4, 6)])
def test_publish_between_trainer_syncs_is_refused(trainer_every,
                                                  engine_every):
    cfg = _tiny_forest()
    dp = sh.build_data_parallel_forest(cfg, make_mesh_auto((1,), ("data",)),
                                       "data", sync_every=trainer_every)
    assert dp.sync_every == trainer_every
    with pytest.raises(ValueError, match="not a multiple"):
        eng.ServingEngine(dp, dp.init(jax.random.PRNGKey(0)), _stream,
                          cfg=eng.EngineConfig(sync_every=engine_every))


def test_engine_sync_may_span_several_trainer_syncs():
    cfg = _tiny_forest()
    dp = sh.build_data_parallel_forest(cfg, make_mesh_auto((1,), ("data",)),
                                       "data", sync_every=2)
    e = eng.ServingEngine(dp, dp.init(jax.random.PRNGKey(0)), _stream,
                          cfg=eng.EngineConfig(sync_every=4))
    for _ in range(8):
        e.train_once()
    m = e.metrics()
    assert (m["publishes"], m["dp_syncs"]) == (3, 4)
    assert m["dp_exchange_bytes"] == 0         # one device: nothing moves
    assert e.staleness()["published_step"] == 8


def test_one_device_engine_reports_no_exchange():
    cfg = _tiny_forest()
    e = eng.ServingEngine(cfg, fr.init_forest(cfg, jax.random.PRNGKey(0)),
                          _stream, cfg=eng.EngineConfig(sync_every=2))
    e.train_once()
    e.train_once()
    m = e.metrics()
    assert m["publishes"] == 2
    assert (m["dp_syncs"], m["dp_exchange_bytes"], m["dp_exchange_s"]) \
        == (0, 0, 0.0)

"""Continuous-serving engine fault-path tests (DESIGN.md §5.6).

Every test drives the engine through its deterministic single-step
methods (``train_once`` / ``serve_once``) so the fault timing is exact;
one threaded smoke test runs the deployment shape.  The invariant under
EVERY injected fault: all admitted requests are served from a validated
published snapshot, bit-identical to ``predict_snapshot`` on that
version, sheds are counted, and the engine recovers to publishing.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import Checkpointer
from repro.core import engine as eng
from repro.core import faults as fl
from repro.core import forest as fr
from repro.core import hoeffding as ht
from repro.core import serve as sv

F, B, N = 4, 64, 4096
TCFG = ht.HTRConfig(n_features=F, max_nodes=31, n_bins=16, grace_period=40,
                    max_depth=6, r0=0.3)
FCFG = fr.ForestConfig(tree=TCFG, n_trees=4)


def _data():
    rng = np.random.default_rng(7)
    X = rng.normal(0, 1, (N, F)).astype(np.float32)
    y = (2.0 * (X[:, 0] > 0) + 0.1 * rng.normal(0, 1, N)).astype(np.float32)
    return X, y


X_ALL, Y_ALL = _data()


def stream(step):
    """Deterministic, step-indexed (wraps) — crash recovery replays it."""
    i = (step * B) % (N - B)
    return jnp.asarray(X_ALL[i:i + B]), jnp.asarray(Y_ALL[i:i + B])


def make_engine(tmp_path=None, injector=None, **cfg_kw):
    cfg = eng.EngineConfig(**{"sync_every": 2, "max_queue_rows": 512,
                              "max_batch_rows": 256, **cfg_kw})
    ck = Checkpointer(str(tmp_path)) if tmp_path is not None else None
    state = fr.init_forest(FCFG, jax.random.PRNGKey(0))
    return eng.ServingEngine(FCFG, state, stream, cfg=cfg,
                             checkpointer=ck, injector=injector)


def _served_bit_identical(e, t):
    """The acceptance pin: a ticket's rows == a standalone
    predict_snapshot on the version that served it, bitwise."""
    assert t.status == "done" and t.version is not None
    snap = e.snapshot_for_version(t.version)
    ref = np.asarray(sv.predict_snapshot(snap, jnp.asarray(t.X)))
    np.testing.assert_array_equal(t.result, ref)


# -- publish / versioning --------------------------------------------------

def test_engine_publishes_on_cadence_with_monotone_versions():
    e = make_engine()
    assert e.published_version == 1          # never cold-starts
    seen = [e.published_version]
    for _ in range(6):
        e.train_once()
        if e.published_version != seen[-1]:
            seen.append(e.published_version)
    assert seen == [1, 2, 3, 4]              # sync_every=2 over 6 steps
    st = e.staleness()
    assert st["published_step"] == 6 and st["age_steps"] == 0
    assert not st["stale"]


def test_stale_publish_version_is_rejected():
    e = make_engine()
    e.train_once(), e.train_once()           # published v2
    old = sv.freeze(fr.init_forest(FCFG, jax.random.PRNGKey(1)),
                    version=1, step=0)       # not past v2
    assert not e.publish(old)
    assert e.published_version == 2
    assert e.metrics()["rollbacks"] == 1


# -- fault: trainer killed mid-sync-window ---------------------------------

def test_trainer_kill_mid_window_serving_uninterrupted(tmp_path):
    inj = fl.FaultInjector()
    e = make_engine(tmp_path, inj)
    for _ in range(4):
        e.train_once()                       # v3 published, ckpt at step 4
    v_before = e.published_version

    # kill the trainer MID-window (one step past the boundary)
    inj.arm("trainer.step", fl.Kill(), after=1)
    tickets = []
    for k in range(3):                       # steps 5 (ok), 6 (kill), 7
        tickets.append(e.submit(X_ALL[k * 10:k * 10 + 10]))
        e.train_once()
        while e.serve_once():
            pass
    assert inj.fired("trainer.step") == 1

    m = e.metrics()
    assert m["trainer_crashes"] == 1 and m["recoveries"] == 1
    # the swallowed exception stays readable, type and message
    assert m["last_trainer_error"].startswith("TrainerKilled: ")
    assert m["last_publish_error"] is None
    # zero failed requests: everything admitted was served, bit-identically
    assert all(t.status == "done" for t in tickets)
    for t in tickets:
        _served_bit_identical(e, t)
    # recovery re-published (a fresh version of the restored model) and
    # the cadence resumed: within one sync window a NEW training-fresh
    # snapshot is out
    assert e.published_version > v_before
    v_recov = e.published_version
    for _ in range(e.cfg.sync_every):
        e.train_once()
    assert e.published_version > v_recov
    assert e.metrics()["trainer_crashes"] == 1      # no repeat crash


def test_recovery_restores_from_checkpoint_step(tmp_path):
    inj = fl.FaultInjector()
    e = make_engine(tmp_path, inj)
    for _ in range(4):
        e.train_once()                       # last ckpt at step 4
    e.train_once()                           # step 5 (mid-window)
    assert e._trainer_step == 5
    inj.arm("trainer.step", fl.Kill())
    e.train_once()                           # dies -> restore
    assert e._trainer_step == 4              # rewound to the ckpt step
    assert int(np.asarray(e._published.snap.step)) == 4


def test_recovery_without_checkpointer_falls_back_to_memory():
    inj = fl.FaultInjector()
    e = make_engine(None, inj)
    for _ in range(3):
        e.train_once()
    step = e._trainer_step
    inj.arm("trainer.step", fl.Kill())
    e.train_once()
    m = e.metrics()
    assert m["trainer_crashes"] == 1 and m["recoveries"] == 1
    # the swallowed exception stays readable, type and message
    assert m["last_trainer_error"].startswith("TrainerKilled: ")
    assert m["last_publish_error"] is None
    assert e._trainer_step == step           # in-memory state kept
    assert e.published_version >= 2          # still re-published


# -- fault: corrupt publish -> rollback ------------------------------------

def test_corrupt_publish_rolls_back_to_last_good():
    inj = fl.FaultInjector()
    e = make_engine(None, inj)
    e.train_once(), e.train_once()           # v2 out
    v_good = e.published_version
    good_snap = e.snapshot_for_version(v_good)

    # NaN the vote weights in flight: invalid regardless of how far the
    # young trees have grown (threshold/BFS corruption is pinned by the
    # controlled-topology tests in test_serve.py)
    inj.arm("publish", fl.Corrupt(lambda s: dataclasses.replace(
        s, vote_w=s.vote_w.at[0].set(jnp.nan))))
    e.train_once(), e.train_once()           # boundary: corrupt publish
    assert inj.fired("publish") == 1
    m = e.metrics()
    assert m["publish_failures"] == 1 and m["rollbacks"] == 1
    # rollback = the reference never moved: still serving v_good, bitwise
    assert e.published_version == v_good
    t = e.submit(X_ALL[:50])
    e.serve_once()
    assert t.version == v_good
    np.testing.assert_array_equal(
        t.result, np.asarray(sv.predict_snapshot(good_snap,
                                                 jnp.asarray(t.X))))
    # the NEXT boundary publishes clean with a monotone version
    e.train_once(), e.train_once()
    assert e.published_version > v_good


def test_corrupt_vote_weights_and_child_range_rejected():
    e = make_engine()
    e.train_once(), e.train_once()
    snap = e.snapshot_for_version(e.published_version)
    bad_vote = dataclasses.replace(
        snap, vote_w=snap.vote_w.at[0].set(-1.0),
        version=jnp.int32(99), step=jnp.int32(99))
    assert not e.publish(bad_vote)
    bad_child = dataclasses.replace(
        snap, child=jnp.full_like(snap.child, snap.feature.shape[1]),
        version=jnp.int32(99), step=jnp.int32(99))
    assert not e.publish(bad_child)
    assert e.metrics()["rollbacks"] == 2
    err = e.metrics()["last_publish_error"]
    assert err.startswith("SnapshotValidationError: ") and "child" in err


# -- fault: dropped publishes -> staleness watchdog ------------------------

def test_dropped_publishes_trip_staleness_watchdog():
    inj = fl.FaultInjector()
    e = make_engine(None, inj, sync_every=2, staleness_factor=2.0)
    e.train_once(), e.train_once()           # v2 at step 2
    inj.arm("publish", fl.Drop(), times=4)   # lose the next 4 publishes
    for _ in range(8):
        e.train_once()
    m = e.metrics()
    assert m["publishes_dropped"] == 4
    st = e.staleness()
    assert st["published_step"] == 2 and st["age_steps"] == 8
    assert st["stale"] and m["stale_events"] > 0
    # the drop armed out: next boundary publishes again and the flag clears
    e.train_once(), e.train_once()
    assert not e.staleness()["stale"]
    assert e.published_version == 3          # monotone, no version holes


# -- admission control ------------------------------------------------------

def test_queue_overflow_sheds_exactly_the_excess():
    e = make_engine(None, None, max_queue_rows=512)
    tickets = [e.submit(X_ALL[:200]) for _ in range(4)]
    statuses = [t.status for t in tickets]
    assert statuses == ["queued", "queued", "shed", "shed"]
    m = e.metrics()
    assert m["admitted_rows"] == 400 and m["shed_rows"] == 400
    assert m["shed_requests"] == 2
    # shed tickets are resolved (never hang a caller), with no result
    assert tickets[2].wait(timeout=1) and tickets[2].result is None
    # draining reopens admission
    while e.serve_once():
        pass
    assert e.submit(X_ALL[:200]).status == "queued"
    assert e.metrics()["served_rows"] == 400


def test_packed_batch_splits_per_ticket_bit_identically():
    e = make_engine(None, None, max_batch_rows=256)
    sizes = (100, 37, 119)                    # packs into one 256-row batch
    tickets = [e.submit(X_ALL[i * 200:i * 200 + s])
               for i, s in enumerate(sizes)]
    assert e.serve_once() == sum(sizes)
    assert e.metrics()["serve_batches"] == 1  # ONE dispatch for all three
    for t in tickets:
        _served_bit_identical(e, t)


def test_inflight_requests_drain_on_the_pinned_version():
    """The hot-swap drain contract, exercised deterministically: tickets
    queued before a publish that are served after it still carry a
    consistent version and bit-identical results for that version."""
    e = make_engine()
    t_old = e.submit(X_ALL[:80])
    e.train_once(), e.train_once()           # hot-swap to v2 while queued
    e.serve_once()
    assert t_old.version == e.published_version    # served post-swap: v2
    _served_bit_identical(e, t_old)                # ...consistently


# -- threaded deployment shape ---------------------------------------------

def test_threaded_engine_serves_everything_admitted(tmp_path):
    inj = fl.FaultInjector()
    inj.arm("trainer.step", fl.Kill(), after=3)
    # the free-running trainer may publish many versions while tickets
    # wait: retain them all, so every ticket's version stays auditable
    e = make_engine(tmp_path, inj, sync_every=2, max_queue_rows=4096,
                    max_batch_rows=512, keep_versions=1 << 30)
    e.start()
    try:
        tickets = [e.submit(X_ALL[i % 32:(i % 32) + 48]) for i in range(20)]
        # let the injected kill actually land before shutting down (the
        # trainer thread paces itself; a fault that never fired proves
        # nothing)
        deadline = time.monotonic() + 120
        while (e.metrics()["recoveries"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        tickets += [e.submit(X_ALL[i % 32:(i % 32) + 48]) for i in range(20)]
        admitted = [t for t in tickets if t.status != "shed"]
        for t in admitted:
            assert t.wait(timeout=30), "admitted ticket never served"
    finally:
        e.stop(drain=True)
    m = e.metrics()
    assert m["trainer_crashes"] == 1 and m["recoveries"] == 1
    # the swallowed exception stays readable, type and message
    assert m["last_trainer_error"].startswith("TrainerKilled: ")
    assert m["last_publish_error"] is None
    assert all(t.status == "done" for t in admitted)
    assert m["served_requests"] == len(admitted)
    assert m["served_rows"] + m["shed_rows"] == sum(t.rows for t in tickets)
    for t in admitted:                       # zero torn reads, bitwise
        _served_bit_identical(e, t)


# -- snapshot identity round-trip ------------------------------------------

def test_version_and_step_round_trip_through_checkpoint(tmp_path):
    state = fr.init_forest(FCFG, jax.random.PRNGKey(0))
    state, _ = fr.update(FCFG, state, jnp.asarray(X_ALL[:B]),
                         jnp.asarray(Y_ALL[:B]))
    snap = sv.freeze(state, version=17, step=123)
    ck = Checkpointer(str(tmp_path))
    ck.save(123, snap, blocking=True)
    # the template carries DIFFERENT stamps: restore must bring back the
    # SAVED identity (leaves, not aux), so rollback audits can pin it
    template = sv.freeze(state, version=1, step=0)
    rest = ck.restore_latest(template)
    assert int(np.asarray(rest.version)) == 17
    assert int(np.asarray(rest.step)) == 123
    np.testing.assert_array_equal(
        np.asarray(sv.predict_snapshot(rest, jnp.asarray(X_ALL[:100]))),
        np.asarray(sv.predict_snapshot(snap, jnp.asarray(X_ALL[:100]))))

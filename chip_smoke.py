"""Chip smoke: the forest's train-and-serve path on a TPU, end to end.

    python chip_smoke.py [--seed N]      # one chip: the main path
    python chip_smoke.py --chips 4       # four chips: the data-parallel trainer

One chip (the default) drives an online-bagged forest (T = 16 trees of
M = 1023 nodes, F = 10 features, C = 64 QO bins) through
``core/engine.ServingEngine``: 64 stream batches of 4096 rows are learned
with the compiled Pallas kernels while ragged requests are served from
the published snapshots.  It checks, on the chip:

* kernels vs ``kernels/ref.py`` on one batch at these shapes;
* every served ticket bit-identical to ``predict_snapshot`` on the
  version that served it, and every snapshot's kernel routing equal to
  ``ref.forest_route_ref``;
* no engine failure of any kind, every ticket done, a replay of the same
  stream bit-identical and free of compiles;
* trees that grew, and a served prequential MSE within 5% of a
  ``split_backend="jnp"`` forest run on the same stream.

``--chips 4`` runs only the data-parallel stream trainer
(``train/sharding.build_data_parallel_forest``) on a 4-device mesh
against ``build_data_parallel_reference``, bitwise at every sync.

Every phase that fails exits non-zero; with no TPU it exits non-zero
before any work.  The last line of standard output is one JSON object
naming the device.  Numbers printed on earlier lines are one smoke
observation, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import engine as eg  # noqa: E402
from repro.core import forest as fr  # noqa: E402
from repro.core import hoeffding as ht  # noqa: E402
from repro.core import serve as sv  # noqa: E402
from repro.core import stats  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.qo_update_leaves import FOREST_ROWS, round_up  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

#: tests/test_qo_batched.py's tolerances: one kernel pass vs the oracle,
#: and two chained passes (incremental Chan merges)
TOL, TOL_CHAINED = 1e-4, 5e-4
#: served prequential MSE of the kernel forest vs the jnp forest
MSE_REL_TOL = 0.05
#: ragged request sizes, cycled one per stream batch
REQUEST_ROWS = (1, 100, 1000, 2048, 3000)
#: engine failure counters that must stay 0 (this run injects no fault)
FAILURE_COUNTERS = ("trainer_crashes", "publish_failures", "ckpt_failures",
                    "recoveries", "rollbacks", "publishes_dropped")


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


@dataclass(frozen=True)
class Sizes:
    trees: int = 16
    max_nodes: int = 1023
    features: int = 10
    bins: int = 64
    batch: int = 4096
    batches: int = 64
    sync_every: int = 4


def forest_config(sz: Sizes, split_backend: str) -> fr.ForestConfig:
    """The default observer (``qo``), decision (``hoeffding``) and
    attempt schedule (``grace``) at the smoke widths."""
    tree = ht.HTRConfig(n_features=sz.features, max_nodes=sz.max_nodes,
                        n_bins=sz.bins, split_backend=split_backend)
    return fr.ForestConfig(tree=tree, n_trees=sz.trees)


class CompileCounter:
    """Counts backend compiles (and their seconds) process-wide."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


# --------------------------------------------------------------------------
# phase: kernels vs kernels/ref.py at the smoke shapes
# --------------------------------------------------------------------------

def _sub_forest(tables, rows):
    return jax.tree.map(lambda a: a[rows], tables)


def _assert_close(name, got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want) / (tol + tol * np.abs(want)),
                       initial=0.0))
    check(err <= 1.0, f"{name}: kernel vs oracle off by {err:.3g}x the "
                      f"tolerance (atol = rtol = {tol})")
    return err


def check_kernels(sz: Sizes, backend: str, seed: int) -> None:
    """ops.forest_update / forest_best_splits on the folded T·M table axis
    vs ref.forest_update_ref / forest_query_ref.  The update oracle loops
    tables in Python, so it runs on a sample of leaves (hot, sparse and
    empty ones); tables are independent, so the sample checks the same
    math the full-shape kernel call ran.  The query oracle runs on every
    table."""
    rng = np.random.default_rng(seed)
    N, F, C, B = sz.trees * sz.max_nodes, sz.features, sz.bins, sz.batch
    radius = jnp.asarray(rng.uniform(0.05, 0.4, (N, F)), jnp.float32)
    origin = jnp.asarray(rng.normal(0, 0.5, (N, F)), jnp.float32)
    k = min(64, N // 4)
    hot = rng.choice(N, k, replace=False)
    sparse = rng.choice(np.setdiff1d(np.arange(N), hot), k, replace=False)

    def batch():
        leaf = np.where(rng.random(B) < 0.5, rng.choice(hot, B),
                        rng.integers(0, N, B)).astype(np.int32)
        X = rng.normal(0, 1, (B, F)).astype(np.float32)
        y = rng.normal(0, 2, B).astype(np.float32)
        w = rng.poisson(6.0, B).astype(np.float32)
        return leaf, X, y, w

    b1, b2 = batch(), batch()
    routed = np.union1d(b1[0], b2[0])
    unrouted = np.setdiff1d(np.arange(N), routed)
    empty = rng.choice(unrouted, min(8, len(unrouted)), replace=False)
    rows = np.concatenate([hot, sparse, empty])
    remap = np.full(N, -1, np.int32)
    remap[rows] = np.arange(len(rows), dtype=np.int32)

    tables = (stats.init((N, F, C)), jnp.zeros((N, F, C), jnp.float32))
    ref_tabs = _sub_forest(tables, rows)
    for i, (leaf, X, y, w) in enumerate((b1, b2)):
        tables = ops.forest_update(*tables, radius, origin, leaf, X, y, w,
                                   backend=backend)
        ref_tabs = ref.forest_update_ref(
            *ref_tabs, radius[rows], origin[rows],
            jnp.asarray(remap[leaf]), X, y, w)
        tol = TOL if i == 0 else TOL_CHAINED
        sub = _sub_forest(tables, rows)
        errs = [_assert_close(f"forest_update pass {i + 1} {k}",
                              sub[0][k], ref_tabs[0][k], tol)
                for k in ("n", "mean", "m2")]
        errs.append(_assert_close(f"forest_update pass {i + 1} sum_x",
                                  sub[1], ref_tabs[1], tol))
        say(f"kernel forest_update pass {i + 1} vs ref.forest_update_ref "
            f"on {len(rows)} leaves x {F} features: worst error "
            f"{max(errs):.3g} x the tolerance (atol = rtol = {tol})")
    check(not np.asarray(tables[0]["n"])[empty].any(),
          "forest_update: a leaf no row was routed to is not empty")

    for label, attempt in (
            ("full scan", rng.random(N) < 0.6),
            ("compacted", np.isin(np.arange(N), np.concatenate([hot, sparse])))):
        attempt = jnp.asarray(attempt)
        km, kt = ops.forest_best_splits(*tables, radius, origin, attempt,
                                        backend=backend)
        rm, rt = ref.forest_query_ref(*tables, attempt)
        km, kt, rm, rt = map(np.asarray, (km, kt, rm, rt))
        valid = np.isfinite(rm)
        check(bool((np.isfinite(km) == valid).all()),
              f"forest_best_splits ({label}): validity differs from the "
              f"oracle's")
        check(bool(valid.any()), f"forest_best_splits ({label}): no valid "
                                 f"split to compare")
        e1 = _assert_close(f"forest_best_splits ({label}) merit",
                           km[valid], rm[valid], TOL)
        e2 = _assert_close(f"forest_best_splits ({label}) threshold",
                           kt[valid], rt[valid], TOL)
        say(f"kernel forest_best_splits ({label}, {int(attempt.sum())} "
            f"attempting leaves) vs ref.forest_query_ref on all {N} x {F} "
            f"tables: {int(valid.sum())} valid, worst error "
            f"{max(e1, e2):.3g} x the tolerance (atol = rtol = {TOL})")


# --------------------------------------------------------------------------
# phase: the engine's train-and-serve loop
# --------------------------------------------------------------------------

def make_stream(sz: Sizes, seed: int):
    n = sz.batch * sz.batches
    X, y = synth.piecewise_regression(n, n_features=sz.features, seed=seed)
    pool, _ = synth.piecewise_regression(max(REQUEST_ROWS),
                                         n_features=sz.features,
                                         seed=seed + 1)
    return X, y, pool


def run_engine(fcfg, sz: Sizes, data, seed: int, serve_backend):
    """Learn the stream through a fresh ServingEngine, one batch per step.

    Before each batch is learned, the batch itself (prequential scoring
    of the published model) and one ragged request are submitted and
    served.  Returns (engine, prequential tickets, ragged tickets, wall
    seconds)."""
    X, y, pool = data
    B = sz.batch
    stream = lambda s: None if s >= sz.batches else (
        X[s * B:(s + 1) * B], y[s * B:(s + 1) * B])
    cfg = eg.EngineConfig(sync_every=sz.sync_every,
                          max_batch_rows=2 * max(B, max(REQUEST_ROWS)),
                          max_queue_rows=4 * max(B, max(REQUEST_ROWS)),
                          keep_versions=sz.batches + 1,
                          backend=serve_backend)
    t0 = time.perf_counter()
    engine = eg.ServingEngine(fcfg, fr.init_forest(fcfg,
                                                   jax.random.PRNGKey(seed)),
                              stream, cfg=cfg)
    preq, ragged = [], []
    for s in range(sz.batches):
        preq.append(engine.submit(X[s * B:(s + 1) * B]))
        ragged.append(engine.submit(pool[:REQUEST_ROWS[s % len(REQUEST_ROWS)]]))
        while engine.serve_once():
            pass
        check(engine.train_once(), f"stream ended early at step {s}")
    check(not engine.train_once(), "stream did not end after its batches")
    jax.block_until_ready(engine.snapshot_for_version(
        engine.published_version).leaf_mean)
    return engine, preq, ragged, time.perf_counter() - t0


def check_engine_health(name: str, engine, tickets) -> dict:
    m = engine.metrics()
    bad = {k: m[k] for k in FAILURE_COUNTERS if m[k]}
    if bad:
        for k in ("last_trainer_error", "last_publish_error",
                  "last_ckpt_error"):
            if m[k]:
                say(f"{name} {k}: {m[k]}")
        raise SmokeFailure(f"{name}: engine failures {bad}")
    states = {t.status for t in tickets}
    check(states == {"done"}, f"{name}: ticket states {sorted(states)}")
    check(m["shed_requests"] == 0, f"{name}: {m['shed_requests']} shed")
    say(f"{name}: engine failures 0 ({', '.join(FAILURE_COUNTERS)}); "
        f"{len(tickets)} of {len(tickets)} tickets done; "
        f"{m['publishes']} publishes, published v{m['published_version']}")
    return m


def served_mse(preq, y, batch: int) -> float:
    sq = [np.sum((np.asarray(t.result, np.float64)
                  - y[s * batch:(s + 1) * batch]) ** 2)
          for s, t in enumerate(preq)]
    return float(np.sum(sq) / (batch * len(preq)))


def check_read_path(engine, tickets, X_eval, backend: str) -> None:
    """(a) every ticket == predict_snapshot on its version, bitwise, and
    every retained snapshot routes X_eval exactly as the scalar oracle."""
    for t in tickets:
        want = np.asarray(sv.predict_snapshot(
            engine.snapshot_for_version(t.version), t.X))
        check(np.array_equal(t.result, want),
              f"ticket of {t.rows} rows on v{t.version} differs from "
              f"predict_snapshot")
    versions = sorted({t.version for t in tickets})
    for v in versions:
        snap = engine.snapshot_for_version(v)
        arrs = (snap.feature, snap.threshold, snap.child, snap.is_leaf)
        got = ops.forest_route(*arrs, X_eval, depth=snap.depth,
                               backend=backend)
        want = ref.forest_route_ref(*arrs, jnp.asarray(X_eval), snap.depth)
        check(np.array_equal(np.asarray(got), np.asarray(want)),
              f"v{v}: forest_route({backend!r}) leaf ids differ from "
              f"ref.forest_route_ref")
    say(f"read path: {len(tickets)} tickets bit-identical to "
        f"predict_snapshot on their version; forest_route({backend!r}) == "
        f"ref.forest_route_ref on {len(X_eval)} rows for all "
        f"{len(versions)} served versions")


def smoke_one_chip(sz: Sizes, seed: int, backend: str,
                   split_backend: str) -> None:
    """The default mode's phases (``backend``: the kernel path checked
    against the oracles; ``split_backend``: what the forest config asks
    the platform for)."""
    F, C, T, M = sz.features, sz.bins, sz.trees, sz.max_nodes
    tables = T * M * F * C
    Mp, Cp = round_up(T * M, 128), round_up(C, 128)
    say(f"sizes: T={T} trees, M={M} nodes, F={F} features, C={C} bins, "
        f"B={sz.batch} rows x {sz.batches} batches = "
        f"{sz.batch * sz.batches} rows, sync_every={sz.sync_every}")
    say(f"observer state (M, F, C) layout: 4 planes x {tables} x 4 B = "
        f"{4 * tables * 4} bytes; kernel pack (F, {FOREST_ROWS}, {Mp}, {Cp}) "
        f"= {F * FOREST_ROWS * Mp * Cp * 4} bytes per pack")
    check(ops.get_tuning() == {}, "a tuning table is installed; the smoke "
                                  "runs on DEFAULT_PARAMS")

    check_kernels(sz, backend, seed)

    counter = CompileCounter()
    data = make_stream(sz, seed)
    X, y, pool = data
    fcfg = forest_config(sz, split_backend)

    c0 = counter.count, counter.seconds
    eng, preq, ragged, cold_s = run_engine(fcfg, sz, data, seed, None)
    say(f"cold run: {cold_s:.3f} s wall, {counter.count - c0[0]} compiles "
        f"taking {counter.seconds - c0[1]:.3f} s")
    m = check_engine_health("pallas engine", eng, preq + ragged)
    check(m["published_version"] >= 2, "the trainer never published")
    final = eng.snapshot_for_version(eng.published_version)
    grown = np.asarray(~final.is_leaf).any(axis=1)
    check(bool(grown.all()), f"members that never split: "
                             f"{np.flatnonzero(~grown).tolist()}")
    say(f"trees grew: every member split; internal nodes per member "
        f"{np.asarray(~final.is_leaf).sum(axis=1).tolist()}, realized "
        f"depth {final.depth}")
    check_read_path(eng, preq + ragged, pool, backend)

    c1 = counter.count
    eng2, preq2, ragged2, warm_s = run_engine(fcfg, sz, data, seed, None)
    warm_compiles = counter.count - c1
    check_engine_health("pallas engine replay", eng2, preq2 + ragged2)
    for a, b in zip(preq + ragged, preq2 + ragged2):
        check(a.version == b.version and np.array_equal(a.result, b.result),
              "the replay of the same stream served different bits")
    check(warm_compiles == 0, f"the replay compiled {warm_compiles} programs")
    rows = sz.batch * sz.batches
    served = len(preq2) + len(ragged2)
    say(f"replay (smoke observation, one run, not a benchmark): 0 compiles, "
        f"bit-identical to the cold run, {warm_s:.3f} s wall, "
        f"{rows / warm_s:.1f} rows/s absorbed while serving {served} "
        f"requests ({eng2.metrics()['served_rows']} rows)")

    mse = served_mse(preq, y, sz.batch)
    eng_j, preq_j, ragged_j, _ = run_engine(
        forest_config(sz, "jnp"), sz, data, seed, "jnp")
    check_engine_health("jnp engine", eng_j, preq_j + ragged_j)
    mse_j = served_mse(preq_j, y, sz.batch)
    rel = abs(mse - mse_j) / mse_j
    say(f"served prequential MSE: kernel forest {mse!r}, jnp forest "
        f"{mse_j!r}, relative difference {rel!r} (limit {MSE_REL_TOL})")
    check(np.isfinite(mse) and rel <= MSE_REL_TOL,
          "kernel forest's prequential MSE is not within the limit of the "
          "jnp forest's")


# --------------------------------------------------------------------------
# four chips: the data-parallel stream trainer vs its reference
# --------------------------------------------------------------------------

def _delta_devices(dpstate):
    return {s.device for s in dpstate["delta"]["ao_sum_x"].addressable_shards}


def smoke_four_chips(sz: Sizes, seed: int, n_dev: int = 4,
                     steps: int = 16) -> None:
    from repro.launch.mesh import make_mesh_auto
    from repro.train import sharding as sh

    fcfg = forest_config(sz, "auto")
    Bg = n_dev * sz.batch
    say(f"data-parallel: {n_dev} shards x {sz.batch} rows = {Bg} rows per "
        f"global batch, {steps} batches, sync_every={sz.sync_every}; "
        f"T={sz.trees} M={sz.max_nodes} F={sz.features} C={sz.bins}")
    X, y = synth.piecewise_regression(Bg * steps, n_features=sz.features,
                                      seed=seed)
    mesh = make_mesh_auto((n_dev,), ("data",))
    init_s, upd_s, _, pred_s = sh.build_data_parallel_forest(
        fcfg, mesh, "data", sync_every=sz.sync_every)
    init_r, upd_r, _, pred_r = sh.build_data_parallel_reference(
        fcfg, n_dev, sync_every=sz.sync_every)
    key = jax.random.PRNGKey(seed)
    st_s, st_r = init_s(key), init_r(key)
    n_syncs = 0
    for i in range(steps):
        Xb, yb = X[i * Bg:(i + 1) * Bg], y[i * Bg:(i + 1) * Bg]
        st_s, aux_s = upd_s(st_s, Xb, yb)
        st_r, aux_r = upd_r(st_r, Xb, yb)
        devs = _delta_devices(st_s)
        check(len(devs) == n_dev, f"step {i}: shard deltas on "
                                  f"{len(devs)} devices, not {n_dev}")
        check((aux_s is None) == (aux_r is None), "sync cadence differs")
        if aux_s is None:
            continue
        n_syncs += 1
        flat_s = jax.tree_util.tree_flatten_with_path(st_s["forest"])[0]
        diff = [jax.tree_util.keystr(p) for (p, a), b in
                zip(flat_s, jax.tree.leaves(st_r["forest"]))
                if not np.array_equal(np.asarray(a), np.asarray(b),
                                      equal_nan=True)]
        check(not diff, f"sync {n_syncs}: sharded forest differs from the "
                        f"reference in {diff}")
        check(all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(jax.tree.leaves(aux_s),
                                  jax.tree.leaves(aux_r))),
              f"sync {n_syncs}: aux differs from the reference")
        say(f"sync {n_syncs} (step {i + 1}): sharded forest bitwise equal "
            f"to build_data_parallel_reference; deltas on "
            f"{sorted(str(d) for d in devs)}")
    check(n_syncs == steps // sz.sync_every, f"{n_syncs} syncs")
    nodes = np.asarray(st_s["forest"]["trees"]["n_nodes"])
    check(bool((nodes > 1).all()), f"members that never split: {nodes}")
    Xq = X[:Bg]
    check(np.array_equal(np.asarray(pred_s(st_s, Xq)),
                         np.asarray(pred_r(st_r, Xq))),
          "sharded predict differs from the reference")
    say(f"data-parallel: {n_syncs} syncs bitwise equal on {n_dev} distinct "
        f"devices; n_nodes per member {nodes.tolist()}; predict equal")


# --------------------------------------------------------------------------

def require_tpu(count: int) -> list:
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU found: JAX's first device is on platform "
          f"{devs[0].platform!r}; this smoke runs only on a TPU")
    check(len(devs) >= count, f"{count} TPU chips needed, {len(devs)} found")
    check(ops.resolve_backend(None) == "pallas",
          f"resolve_backend(None) = {ops.resolve_backend(None)!r}, not "
          f"'pallas'")
    return devs[:count]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        devs = require_tpu(args.chips)
        say(f"compile cache: {configure_compile_cache(ROOT)}")
        say(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}")
        t0 = time.perf_counter()
        if args.chips == 1:
            smoke_one_chip(Sizes(), args.seed, "pallas", "auto")
        else:
            smoke_four_chips(Sizes(), args.seed, n_dev=args.chips)
        stats_ = devs[0].memory_stats() or {}
        say(f"peak_bytes_in_use (device 0): "
            f"{stats_.get('peak_bytes_in_use', 'not reported')}")
        say(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

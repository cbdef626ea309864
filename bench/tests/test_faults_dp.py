"""The data-parallel cell on the CPU at a tiny size, with four forced host
devices: a sound run matches the plain protocol (``reference_dp.py``)
within the cell's own limits, traced too, and a run whose trainer is
broken underneath comes out with ``correct`` false: one shard's delta
left out of every merge, two shards given the same rows, every other
sync skipped."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import BENCH, ROOT

CHILD = """
import copy, dataclasses, io, json, sys, time
from contextlib import redirect_stdout
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import harness, run
from repro.train import sharding as sh

cell = harness.load_cell("friedman1_dp4.train_dp")
cfg = copy.deepcopy(cell.config)
cfg["forest"].update(n_trees=4, max_nodes=31, n_bins=32)
cfg["batch_rows"] = 256
cell = harness.Cell(name=cell.name, chips=4, config=cfg,
                    traffic=cell.traffic, end_to_end=cell.end_to_end,
                    per_layer=cell.per_layer)


# the CPU has no published peaks; the readers only need numbers
harness.peaks = lambda kind: {{"flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9}}


def result(trace=False):
    ctx = run.Context(cell=cell, seed=2 ** 31 + 7, seconds=1.0, trace=trace,
                      t_start=time.perf_counter(), devs=jax.devices()[:4],
                      counter=harness.CompileCounter())
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.report(ctx, cell.driver.run(ctx)) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


build, reduce = sh.build_data_parallel_forest, sh._dp_reduce_deltas


def dropped(fcfg, delta):
    # the last shard's delta becomes the merge identity
    return reduce(fcfg, jax.tree.map(lambda a: a.at[-1].set(0), delta))


def duplicated(*a, **kw):
    dp = build(*a, **kw)

    def update(st, X, y):
        b = X.shape[0] // 4
        X, y = X.copy(), y.copy()
        X[b:2 * b], y[b:2 * b] = X[:b], y[:b]
        return dp.update(st, X, y)
    return dataclasses.replace(dp, update=update)


def skipping(*a, **kw):
    return build(*a, **dict(kw, sync_every=2))


faults = {{"sound": {{}}, "dropped_delta": {{"_dp_reduce_deltas": dropped}},
          "duplicated_rows": {{"build_data_parallel_forest": duplicated}},
          "skipped_sync": {{"build_data_parallel_forest": skipping}}}}
for name, patch in faults.items():
    for k, v in patch.items():
        setattr(sh, k, v)
    sh._dp_sync_jit.cache_clear()
    print(json.dumps({{"fault": name, "result": result()}}), flush=True)
    sh._dp_reduce_deltas = reduce
    sh.build_data_parallel_forest = build
sh._dp_sync_jit.cache_clear()
print(json.dumps({{"fault": "traced", "result": result(trace=True)}}),
      flush=True)
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(CHILD.format(bench=BENCH,
                                        src=os.path.join(ROOT, "src")))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith('{"fault"')]
    return {r["fault"]: r["result"] for r in rows}


def _failed(r) -> set:
    return {k for k, c in r["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_matches_the_plain_protocol(results):
    r = results["sound"]
    assert r["correct"], r["checks"]
    assert list(r["checks"]) == ["state_gap", "final_state_gap",
                                 "predict_gap", "mass_gap",
                                 "engine_failures"]
    assert r["checks"]["mass_gap"]["value"] == 0.0


def test_traced_run_reads_the_exchange_from_host_spans(results):
    r = results["traced"]
    assert r["correct"], r["checks"]
    # the CPU trace has host spans and no TPU plane: the exchange reads,
    # the readers of device time find nothing and stay silent
    assert r["metrics"]["dp_exchange_ms_per_sync"]["value"] > 0
    assert "dp_sync_ms_per_sync" not in r["metrics"]
    assert "qo_merge_roofline" not in r["metrics"]


def test_dropped_delta_is_caught(results):
    r = results["dropped_delta"]
    assert not r["correct"]
    assert {"state_gap", "mass_gap"} <= _failed(r)


def test_duplicated_rows_are_caught(results):
    r = results["duplicated_rows"]
    assert not r["correct"]
    assert "state_gap" in _failed(r)


def test_skipped_sync_is_caught(results):
    r = results["skipped_sync"]
    assert not r["correct"]
    assert "state_gap" in _failed(r)

"""The data-parallel deployment as its cell drives it: the program's
``build_data_parallel_forest`` trainer over a mesh of the cell's chips,
inside the program's own ``ServingEngine``, and what the checks read back.
"""
from __future__ import annotations

import numpy as np

import program
from harness import BenchError


def sharding_module():
    """``repro.train.sharding``, or BenchError where its trainer cannot be
    driven by the engine (a program that predates that)."""
    program.import_program()
    from repro.train import sharding as sh
    if not callable(getattr(sh.DataParallelForest, "model", None)):
        raise BenchError("the program's engine cannot drive the "
                         "data-parallel trainer")
    return sh


def build_engine(config: dict, seed: int, stream_fn, devices):
    """A fresh ``ServingEngine`` over the data-parallel trainer: one shard
    per device of ``devices``, merging every ``dp_sync_every`` global
    batches, its state ``init(PRNGKey(seed))``."""
    import jax
    eg = program.import_program()[0]
    sh = sharding_module()
    if len(devices) != config["shards"]:
        raise BenchError(f"{config['shards']} shards need as many devices, "
                         f"{len(devices)} given")
    mesh = jax.sharding.Mesh(np.asarray(devices), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
    dp = sh.build_data_parallel_forest(program.forest_config(config), mesh,
                                       "data",
                                       sync_every=config["dp_sync_every"])
    return eg.ServingEngine(dp, dp.init(program.forest_key(seed)),
                            stream_fn, cfg=eg.EngineConfig(**config["engine"]))


def trainer_forest(engine):
    """The trainer's replicated forest state (device arrays, immutable:
    holding it reads nothing)."""
    return engine._trainer.model(engine._state)


def bin_mass(forest) -> np.ndarray:
    """(T,) total bin mass per member of a forest state (feature 0)."""
    n = np.asarray(forest["trees"]["ao_y"]["n"][:, :, 0, :], np.float64)
    return n.sum(axis=(1, 2))


def exchange(engine) -> dict:
    """The engine's exchange counters: syncs, bytes, host seconds."""
    m = engine.metrics()
    return {k: m[k] for k in ("dp_syncs", "dp_exchange_bytes",
                              "dp_exchange_s")}


def leaf_count(forest) -> int:
    """Leaves of a forest state, over every member."""
    return int(np.sum(np.asarray(forest["trees"]["is_leaf"])))

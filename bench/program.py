"""The system under test, as the cells drive it: its configuration objects
built from a deployment file, its engine, and what the checks read back.

The program is imported from ``<checkout>/src``; the benchmark takes from
it only the entry points the window drives and what they hand back.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from harness import ROOT

#: engine counters that stay 0 in a sound run (no fault is injected)
FAILURE_COUNTERS = ("trainer_crashes", "publish_failures", "ckpt_failures",
                    "recoveries", "rollbacks", "publishes_dropped")

#: the one path the plain reference models
DEFAULT_PATH = {"observer": "qo", "decision": "hoeffding",
                "schedule": "grace", "vote": "inverse_error"}


def import_program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import engine, forest, hoeffding, serve
    from repro.kernels import ops
    return engine, forest, hoeffding, serve, ops


def check_path(config: dict) -> None:
    f = config["forest"]
    for k, v in DEFAULT_PATH.items():
        if f[k] != v:
            raise ValueError(f"forest {k}={f[k]!r}: the reference models "
                             f"only {k}={v!r}")


def forest_config(config: dict):
    _, fr, ht, _, _ = import_program()
    check_path(config)
    f = config["forest"]
    tree = ht.HTRConfig(
        n_features=f["n_features"], max_nodes=f["max_nodes"],
        n_bins=f["n_bins"], grace_period=f["grace_period"],
        delta=f["delta"], tau=f["tau"], max_depth=f["max_depth"],
        r0=f["r0"], sigma_k=f["sigma_k"], observer_backend=f["observer"],
        decision_backend=f["decision"], attempt_schedule=f["schedule"])
    return fr.ForestConfig(
        tree=tree, n_trees=f["n_trees"], lam=f["lam"],
        subspace=f["subspace"], vote=f["vote"], vote_power=f["vote_power"],
        drift_alpha=f["drift_alpha"], drift_decay=f["drift_decay"],
        drift_kappa=f["drift_kappa"],
        drift_min_batches=f["drift_min_batches"])


def forest_key(seed: int):
    import jax
    return jax.random.PRNGKey(seed % 2 ** 32)


def build_engine(config: dict, seed: int, stream_fn):
    """A fresh ``ServingEngine`` over ``init_forest(PRNGKey(seed))``, with
    the engine's defaults where the deployment names none."""
    eg, fr, _, _, _ = import_program()
    fcfg = forest_config(config)
    e = config["engine"]
    cfg = eg.EngineConfig(**e)
    return eg.ServingEngine(fcfg, fr.init_forest(fcfg, forest_key(seed)),
                            stream_fn, cfg=cfg)


def trainer_state(engine) -> dict:
    """The trainer's live forest state, copied to the host."""
    import jax
    return jax.device_get(engine._state)


def forest_counts(engine) -> dict:
    """Leaves and member resets of the trainer's live forest (a small
    read: two leaves of the state)."""
    import jax
    st = engine._state
    leaf, resets = jax.device_get((st["trees"]["is_leaf"], st["resets"]))
    return {"leaves": int(np.sum(leaf)), "resets": int(np.sum(resets))}


def published_step(engine) -> tuple:
    """(version, step) of the snapshot serving now, as host integers."""
    st = engine.staleness()
    return st["published_version"], st["published_step"]


def engine_failures(engine) -> int:
    m = engine.metrics()
    return int(sum(m[k] for k in FAILURE_COUNTERS))


def predict_snapshot(snap, X) -> np.ndarray:
    _, _, _, sv, _ = import_program()
    return np.asarray(sv.predict_snapshot(snap, X))


def kernel_backend() -> str:
    _, _, _, _, ops = import_program()
    return ops.resolve_backend(None)


def tuning_installed() -> bool:
    _, _, _, _, ops = import_program()
    return bool(ops.get_tuning())

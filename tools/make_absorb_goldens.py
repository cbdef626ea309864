"""Regenerate tests/goldens/absorb_goldens.npz — the absorb's bit-identity pin.

Absorbs one fixed batch into one fixed table set through
``ops.forest_update`` in its one-group form, on the ``interpret`` and
``jnp`` backends: as a single tree calls it (M = 130 tables, not a
multiple of the kernel's leaf tile), and as a forest of G = 3 members
folded into one table axis of 3 x 13 tables (global leaf ids
``g*13 + leaf``, the batch tiled once per member), which is how
``core/forest.py`` called it before the group form.  It also learns a
few batches of a small forest through ``forest.update`` on both
backends.  Inputs and outputs are saved together.

tests/test_qo_batched.py asserts that the one-group call and the ``jnp``
backend still reproduce these arrays bitwise, and that the group form
and the forest agree with the folded arrays.  Every call here exists in
the one-group API, so the file can be regenerated from code before the
group form existed.

Run from the repo root:
``JAX_PLATFORMS=cpu PYTHONPATH=src python tools/make_absorb_goldens.py``
Only regenerate when an INTENTIONAL change of the absorb's bits is being
made (and say so in the commit).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import forest as fr
from repro.core import hoeffding as ht
from repro.data import synth
from repro.kernels import ops

OUT = os.path.join(os.path.dirname(__file__), os.pardir,
                   "tests", "goldens", "absorb_goldens.npz")

F, C, B = 3, 48, 300
FOREST = dict(n_features=3, max_nodes=15, n_bins=16, grace_period=100,
              max_depth=4, r0=0.3)
FOREST_TREES, FOREST_STEPS, FOREST_ROWS = 3, 3, 256


def tables(rng, N):
    """A seeded (N, F, C) table set: some empty bins, the rest occupied."""
    n = rng.integers(0, 5, (N, F, C)).astype(np.float32)
    occ = n > 0
    return {"n": n,
            "mean": np.where(occ, rng.normal(0, 2, n.shape), 0).astype(np.float32),
            "m2": np.where(occ, rng.gamma(1.0, 1.0, n.shape), 0).astype(np.float32),
            "sum_x": np.where(occ, rng.normal(0, 1, n.shape), 0).astype(np.float32),
            "radius": rng.uniform(0.05, 0.4, (N, F)).astype(np.float32),
            "origin": rng.normal(0, 0.5, (N, F)).astype(np.float32)}


def absorb(tabs, leaf, X, y, w, backend):
    ao_y = {k: jnp.asarray(tabs[k]) for k in ("n", "mean", "m2")}
    ao_y, sx = ops.forest_update(ao_y, jnp.asarray(tabs["sum_x"]),
                                 jnp.asarray(tabs["radius"]),
                                 jnp.asarray(tabs["origin"]), leaf, X, y, w,
                                 backend=backend)
    return {**{k: np.asarray(v) for k, v in ao_y.items()},
            "sum_x": np.asarray(sx)}


def forest_cfg(backend):
    return fr.ForestConfig(tree=ht.HTRConfig(**FOREST, split_backend=backend),
                           n_trees=FOREST_TREES)


def learn_forest(backend, X, y):
    cfg = forest_cfg(backend)
    state = fr.init_forest(cfg, jax.random.PRNGKey(3))
    upd = jax.jit(lambda s, Xb, yb: fr.update(cfg, s, Xb, yb)[0])
    for s in range(FOREST_STEPS):
        rows = slice(s * FOREST_ROWS, (s + 1) * FOREST_ROWS)
        state = upd(state, X[rows], y[rows])
    trees = state["trees"]
    return {"n": trees["ao_y"]["n"], "mean": trees["ao_y"]["mean"],
            "m2": trees["ao_y"]["m2"], "sum_x": trees["ao_sum_x"],
            "ystats_mean": trees["ystats"]["mean"],
            "n_nodes": trees["n_nodes"]}


def main():
    rng = np.random.default_rng(20240515)
    out = {}
    X = rng.normal(0, 1, (B, F)).astype(np.float32)
    y = rng.normal(0, 2, B).astype(np.float32)
    w = np.where(rng.uniform(size=B) < 0.2, 0.0,
                 rng.uniform(0.1, 3.0, B)).astype(np.float32)
    out.update(X=X, y=y, w=w)

    # --- one tree: M = 130 tables, B = 300 rows -------------------------
    single = tables(rng, 130)
    leaf = rng.integers(0, 130, B).astype(np.int32)
    out.update({f"single_in_{k}": v for k, v in single.items()},
               single_leaf=leaf)
    for backend in ("interpret", "jnp"):
        got = absorb(single, leaf, X, y, w, backend)
        out.update({f"single_{backend}_{k}": v for k, v in got.items()})

    # --- a forest's members folded: G = 3 groups of 13 tables -----------
    G, M = 3, 13
    grouped = tables(rng, G * M)
    gleaf = rng.integers(0, M, (G, B)).astype(np.int32)
    gw = np.where(rng.uniform(size=(G, B)) < 0.2, 0.0,
                  rng.poisson(2.0, (G, B))).astype(np.float32)
    out.update({f"grouped_in_{k}": v for k, v in grouped.items()},
               grouped_leaf=gleaf, grouped_w=gw)
    gl = (np.arange(G)[:, None] * M + gleaf).reshape(-1).astype(np.int32)
    for backend in ("interpret", "jnp"):
        got = absorb(grouped, gl, np.tile(X, (G, 1)), np.tile(y, G),
                     gw.reshape(-1), backend)
        out.update({f"folded_{backend}_{k}": v for k, v in got.items()})

    # --- a small forest learning a few batches ---------------------------
    Xs, ys = synth.piecewise_regression(FOREST_STEPS * FOREST_ROWS,
                                        n_features=3, seed=11)
    for backend in ("interpret", "jnp"):
        got = learn_forest(backend, jnp.asarray(Xs), jnp.asarray(ys))
        out.update({f"forest_{backend}_{k}": np.asarray(v)
                    for k, v in got.items()})
    out.update(forest_X=np.asarray(Xs), forest_y=np.asarray(ys))

    np.savez_compressed(OUT, **out)
    print(f"wrote {len(out)} arrays to {os.path.normpath(OUT)}")


if __name__ == "__main__":
    main()

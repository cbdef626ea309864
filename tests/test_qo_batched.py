"""Forest-scale batched kernels vs the jnp reference (interpret mode).

Property coverage demanded by the batched-QO pipeline: ragged batches
(B not a tile multiple), empty leaves (no routed rows), and tables with a
single occupied bin (no valid boundary).  Acceptance bar: bin counts and
VR scores within 1e-4 of the per-table :mod:`repro.core.qo` oracle.

The absorb's group form (one group of tables per forest member) is held
to the same oracle, to its own groups, and to the one-group call's bits
in ``tests/goldens/absorb_goldens.npz`` (``tools/make_absorb_goldens.py``,
generated from the code before the group form).
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forest as fr
from repro.core import hoeffding as ht
from repro.core import stats
from repro.data import synth
from repro.kernels import ops, ref
from repro.kernels.qo_update_leaves import pack_forest, unpack_forest

TOL = 1e-4


def _random_forest(rng, M, F, C, occupied_frac=1.0):
    """A forest state built by streaming random rows through the oracle."""
    ao_y = stats.init((M, F, C))
    ao_sum_x = jnp.zeros((M, F, C))
    ao_radius = jnp.array(rng.uniform(0.05, 0.4, (M, F)).astype(np.float32))
    ao_origin = jnp.array(rng.normal(0, 0.5, (M, F)).astype(np.float32))
    B = 160
    leaf = jnp.array(rng.integers(0, max(1, int(M * occupied_frac)), B),
                     jnp.int32)
    X = jnp.array(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.array(rng.normal(0, 2, B).astype(np.float32))
    ao_y, ao_sum_x = ref.forest_update_ref(
        ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y)
    return ao_y, ao_sum_x, ao_radius, ao_origin


@pytest.mark.parametrize("B", [1, 37, 129, 256])
def test_update_leaves_kernel_matches_oracle_ragged(B, rng):
    """Ragged batch sizes: padding rows must contribute nothing."""
    M, F, C = 9, 3, 48
    ao_y = stats.init((M, F, C))
    ao_sum_x = jnp.zeros((M, F, C))
    ao_radius = jnp.array(rng.uniform(0.05, 0.4, (M, F)).astype(np.float32))
    ao_origin = jnp.array(rng.normal(0, 0.5, (M, F)).astype(np.float32))
    # leaf 0 never routed -> stays empty through the kernel too
    leaf = jnp.array(rng.integers(1, M, B), jnp.int32)
    X = jnp.array(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.array(rng.normal(0, 2, B).astype(np.float32))

    ry, rsx = ref.forest_update_ref(ao_y, ao_sum_x, ao_radius, ao_origin,
                                    leaf, X, y)
    for backend in ("interpret", "jnp"):
        ky, ksx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                    leaf, X, y, backend=backend)
        for k in ("n", "mean", "m2"):
            np.testing.assert_allclose(np.asarray(ky[k]), np.asarray(ry[k]),
                                       atol=TOL, rtol=TOL,
                                       err_msg=f"{backend}:{k}")
        np.testing.assert_allclose(np.asarray(ksx), np.asarray(rsx),
                                   atol=TOL, rtol=TOL)
        # empty leaf stays exactly empty
        assert float(jnp.abs(ky["n"][0]).max()) == 0.0


def test_update_leaves_kernel_weighted_and_incremental(rng):
    """Two seeded kernel calls == one oracle pass over the concatenation."""
    M, F, C = 6, 2, 48
    ao_y = stats.init((M, F, C))
    ao_sum_x = jnp.zeros((M, F, C))
    ao_radius = jnp.full((M, F), 0.2, jnp.float32)
    ao_origin = jnp.zeros((M, F), jnp.float32)
    B = 120
    leaf = jnp.array(rng.integers(0, M, B), jnp.int32)
    X = jnp.array(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.array(rng.normal(0, 1, B).astype(np.float32))
    w = jnp.array(rng.uniform(0.1, 2.0, B).astype(np.float32))

    ky, ksx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                leaf[:60], X[:60], y[:60], w[:60],
                                backend="interpret")
    ky, ksx = ops.forest_update(ky, ksx, ao_radius, ao_origin,
                                leaf[60:], X[60:], y[60:], w[60:],
                                backend="interpret")
    ry, rsx = ref.forest_update_ref(ao_y, ao_sum_x, ao_radius, ao_origin,
                                    leaf, X, y, w)
    for k in ("n", "mean", "m2"):
        np.testing.assert_allclose(np.asarray(ky[k]), np.asarray(ry[k]),
                                   atol=5e-4, rtol=5e-4, err_msg=k)
    np.testing.assert_allclose(np.asarray(ksx), np.asarray(rsx),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_query_batched_matches_oracle(backend, rng):
    M, F, C = 12, 3, 48
    ao_y, ao_sum_x, ao_radius, ao_origin = _random_forest(rng, M, F, C)
    attempt = jnp.array(rng.uniform(size=M) < 0.6)

    rm, rt = ref.forest_query_ref(ao_y, ao_sum_x, attempt)
    km, kt = ops.forest_best_splits(ao_y, ao_sum_x, ao_radius, ao_origin,
                                    attempt, backend=backend)
    rm, rt = np.asarray(rm), np.asarray(rt)
    km, kt = np.asarray(km), np.asarray(kt)
    valid = np.isfinite(rm)
    assert (np.isfinite(km) == valid).all(), "validity mask must agree"
    np.testing.assert_allclose(km[valid], rm[valid], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(kt[valid], rt[valid], atol=TOL, rtol=TOL)


def test_query_batched_empty_and_single_bin_tables(rng):
    """Empty tables and single-occupied-bin tables -> no valid boundary."""
    M, F, C = 4, 2, 48
    ao_y = stats.init((M, F, C))
    ao_sum_x = jnp.zeros((M, F, C))
    ao_radius = jnp.full((M, F), 0.1, jnp.float32)
    ao_origin = jnp.zeros((M, F), jnp.float32)
    # leaf 1: every observation lands in ONE bin (identical x)
    leaf = jnp.full((50,), 1, jnp.int32)
    X = jnp.zeros((50, F), jnp.float32)
    y = jnp.array(rng.normal(0, 1, 50).astype(np.float32))
    ao_y, ao_sum_x = ref.forest_update_ref(ao_y, ao_sum_x, ao_radius,
                                           ao_origin, leaf, X, y)
    # leaf 2: a real two-cluster table
    leaf2 = jnp.full((60,), 2, jnp.int32)
    X2 = jnp.array(np.repeat([[-1.0], [1.0]], 30, 0).astype(np.float32))
    X2 = jnp.tile(X2, (1, F))
    y2 = jnp.array(np.repeat([0.0, 5.0], 30).astype(np.float32))
    ao_y, ao_sum_x = ref.forest_update_ref(ao_y, ao_sum_x, ao_radius,
                                           ao_origin, leaf2, X2, y2)

    attempt = jnp.ones((M,), bool)
    for backend in ("interpret", "jnp"):
        km, kt = ops.forest_best_splits(ao_y, ao_sum_x, ao_radius, ao_origin,
                                        attempt, backend=backend)
        km = np.asarray(km)
        assert not np.isfinite(km[0]).any(), "empty leaf must be invalid"
        assert not np.isfinite(km[1]).any(), "single-bin tables are invalid"
        assert np.isfinite(km[2]).all(), "two-cluster tables must be valid"
        # the split must separate the clusters
        assert (-1.0 < np.asarray(kt)[2]).all() and (np.asarray(kt)[2] < 1.0).all()
        # masked leaves report -inf even with valid tables
        km_masked, _ = ops.forest_best_splits(
            ao_y, ao_sum_x, ao_radius, ao_origin,
            jnp.zeros((M,), bool), backend=backend)
        assert not np.isfinite(np.asarray(km_masked)).any()


def test_pack_unpack_roundtrip(rng):
    M, F, C = 13, 3, 48
    ao_y, ao_sum_x, ao_radius, ao_origin = _random_forest(rng, M, F, C)
    dense = pack_forest(ao_y, ao_sum_x, ao_radius, ao_origin)
    uy, usx = unpack_forest(dense, M, C)
    for k in ("n", "mean", "m2"):
        np.testing.assert_array_equal(np.asarray(uy[k]), np.asarray(ao_y[k]))
    np.testing.assert_array_equal(np.asarray(usx), np.asarray(ao_sum_x))


def test_tree_backends_agree_end_to_end():
    """jnp fast path and oracle backend grow near-identical trees."""
    X, y = synth.piecewise_regression(6000, n_features=3, seed=9)
    trees = {}
    for backend in ("jnp", "oracle"):
        cfg = ht.HTRConfig(n_features=3, max_nodes=31, n_bins=32,
                           grace_period=200, max_depth=6, r0=0.3,
                           split_backend=backend)
        s = ht.init_state(cfg)
        upd = jax.jit(functools.partial(ht.update, cfg))
        for i in range(0, 6000 - 255, 256):
            s = upd(s, jnp.array(X[i:i + 256]), jnp.array(y[i:i + 256]))
        trees[backend] = (cfg, s)
    cfg_j, s_j = trees["jnp"]
    cfg_o, s_o = trees["oracle"]
    assert int(s_j["n_nodes"]) == int(s_o["n_nodes"])
    Xt, yt = synth.piecewise_regression(1500, n_features=3, seed=99)
    p_j = np.asarray(ht.predict(cfg_j, s_j, jnp.array(Xt)))
    p_o = np.asarray(ht.predict(cfg_o, s_o, jnp.array(Xt)))
    mse_j = float(np.mean((p_j - yt) ** 2))
    mse_o = float(np.mean((p_o - yt) ** 2))
    assert abs(mse_j - mse_o) <= 0.01 * max(mse_o, 1e-9)


def test_update_stream_matches_batch_loop():
    """One-dispatch scan driver == the per-batch python loop."""
    X, y = synth.piecewise_regression(4096, n_features=2, seed=4)
    cfg = ht.HTRConfig(n_features=2, max_nodes=15, n_bins=32,
                       grace_period=150, max_depth=4, r0=0.3)
    s_loop = ht.init_state(cfg)
    upd = jax.jit(functools.partial(ht.update, cfg))
    for i in range(0, 4096, 256):
        s_loop = upd(s_loop, jnp.array(X[i:i + 256]), jnp.array(y[i:i + 256]))
    s_scan = ht.update_stream(cfg, ht.init_state(cfg), jnp.array(X),
                              jnp.array(y), batch_size=256)
    assert int(s_loop["n_nodes"]) == int(s_scan["n_nodes"])
    np.testing.assert_array_equal(np.asarray(s_loop["is_leaf"]),
                                  np.asarray(s_scan["is_leaf"]))
    np.testing.assert_allclose(np.asarray(s_loop["ystats"]["mean"]),
                               np.asarray(s_scan["ystats"]["mean"]),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# the absorb's group form: one group of tables per forest member
# --------------------------------------------------------------------------

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens",
                       "absorb_goldens.npz")
STAT_KEYS = ("n", "mean", "m2", "sum_x")


def _seeded_tables(rng, shape):
    """Random occupied/empty bins; radius/origin over the leading axes."""
    n = rng.integers(0, 5, shape).astype(np.float32)
    occ = n > 0
    pick = lambda a: jnp.asarray(np.where(occ, a, 0).astype(np.float32))
    ao_y = {"n": jnp.asarray(n), "mean": pick(rng.normal(0, 2, shape)),
            "m2": pick(rng.gamma(1.0, 1.0, shape))}
    return (ao_y, pick(rng.normal(0, 1, shape)),
            jnp.asarray(rng.uniform(0.05, 0.4, shape[:-1]).astype(np.float32)),
            jnp.asarray(rng.normal(0, 0.5, shape[:-1]).astype(np.float32)))


def _assert_close_per_table(got, want, key, rel=1e-6):
    """Every table (the last axis) within ``rel`` of its own largest
    magnitude: the bins of a table whose mean nearly cancels are held to
    the table's scale, not to their own."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max(-1, keepdims=True)
    bad = np.abs(got - want) > rel * scale
    assert not bad.any(), (f"{key}: {bad.sum()} bins off by more than "
                           f"{rel} of their table's scale")


def _as_dict(ao_y, ao_sum_x):
    return {**{k: np.asarray(v) for k, v in ao_y.items()},
            "sum_x": np.asarray(ao_sum_x)}


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
@pytest.mark.parametrize("M", [13, 130])
@pytest.mark.parametrize("G", [1, 3])
def test_grouped_update_matches_oracle(G, M, backend, rng):
    """Each group's tables == the oracle over that group's rows alone:
    M not a multiple of the leaf tile, B = 300 not a multiple of the
    batch tile, weight-0 rows, and (G = 3) one group with no rows."""
    F, C, B = 2, 48, 300
    ao_y, ao_sum_x, ao_radius, ao_origin = _seeded_tables(rng, (G, M, F, C))
    leaf = jnp.asarray(rng.integers(0, M, (G, B)), jnp.int32)
    X = jnp.asarray(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.asarray(rng.normal(0, 2, B).astype(np.float32))
    w = np.where(rng.uniform(size=(G, B)) < 0.2, 0.0,
                 rng.uniform(0.1, 3.0, (G, B))).astype(np.float32)
    if G > 1:
        w[1] = 0.0                                   # a group with no rows
    w = jnp.asarray(w)

    ky, ksx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                leaf, X, y, w, backend=backend)
    assert ksx.shape == (G, M, F, C)
    for g in range(G):
        ry, rsx = ref.forest_update_ref(
            jax.tree.map(lambda a: a[g], ao_y), ao_sum_x[g], ao_radius[g],
            ao_origin[g], leaf[g], X, y, w[g])
        for k in ("n", "mean", "m2"):
            np.testing.assert_allclose(np.asarray(ky[k][g]), np.asarray(ry[k]),
                                       atol=TOL, rtol=TOL,
                                       err_msg=f"group {g}: {k}")
        np.testing.assert_allclose(np.asarray(ksx[g]), np.asarray(rsx),
                                   atol=TOL, rtol=TOL, err_msg=f"group {g}")


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_grouped_rows_stay_in_their_group(backend, rng):
    """Rows of group g never reach group h's tables: empty tables stay
    exactly empty in the group whose rows all weigh 0, and every other
    group holds exactly its own weight, once per feature."""
    G, M, F, C, B = 3, 13, 2, 16, 300
    ao_y = stats.init((G, M, F, C))
    ao_sum_x = jnp.zeros((G, M, F, C))
    ao_radius = jnp.full((G, M, F), 0.25, jnp.float32)
    ao_origin = jnp.zeros((G, M, F), jnp.float32)
    leaf = jnp.asarray(rng.integers(0, M, (G, B)), jnp.int32)
    X = jnp.asarray(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.asarray(rng.normal(0, 2, B).astype(np.float32))
    w = rng.integers(1, 4, (G, B)).astype(np.float32)   # integer weights:
    w[1] = 0.0                                          # exact f32 sums
    ky, ksx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                leaf, X, y, jnp.asarray(w), backend=backend)
    for k in ("n", "mean", "m2"):
        assert not np.asarray(ky[k][1]).any(), k
    assert not np.asarray(ksx[1]).any()
    n = np.asarray(ky["n"]).sum(axis=(1, 3))                    # (G, F)
    np.testing.assert_array_equal(n, np.repeat(w.sum(1)[:, None], F, 1))


@pytest.fixture(scope="module")
def goldens():
    with np.load(GOLDENS) as g:
        return dict(g)


def _golden_absorb(goldens, part, leaf, X, y, w, backend, tabs=None):
    tabs = tabs or {k: goldens[f"{part}_in_{k}"]
                    for k in STAT_KEYS + ("radius", "origin")}
    out = ops.forest_update({k: jnp.asarray(tabs[k]) for k in STAT_KEYS[:3]},
                            jnp.asarray(tabs["sum_x"]),
                            jnp.asarray(tabs["radius"]),
                            jnp.asarray(tabs["origin"]), leaf, X, y, w,
                            backend=backend)
    return _as_dict(*out)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
@pytest.mark.parametrize("part", ["single", "folded"])
def test_one_group_absorb_bit_identical_to_golden(goldens, part, backend):
    """The one-group call — a single tree's, or a forest folded into one
    table axis — reproduces the pre-group-form bits on both backends."""
    X, y = goldens["X"], goldens["y"]
    if part == "single":
        got = _golden_absorb(goldens, "single", goldens["single_leaf"], X, y,
                             goldens["w"], backend)
    else:
        G, M = goldens["grouped_leaf"].shape[0], \
            goldens["grouped_in_n"].shape[0] // 3
        gl = (np.arange(G)[:, None] * M + goldens["grouped_leaf"]).reshape(-1)
        got = _golden_absorb(goldens, "grouped", gl, np.tile(X, (G, 1)),
                             np.tile(y, G), goldens["grouped_w"].reshape(-1),
                             backend)
    for k in STAT_KEYS:
        np.testing.assert_array_equal(got[k], goldens[f"{part}_{backend}_{k}"],
                                      err_msg=k)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_group_form_matches_folded_golden(goldens, backend):
    """The group form against the folded call it replaces in the forest:
    bit-identical on jnp (which folds internally), within 1e-6 of each
    table's scale on the kernel path (which skips the folded call's
    empty visits, each of which could move a mean by an ulp)."""
    G = goldens["grouped_leaf"].shape[0]
    M = goldens["grouped_in_n"].shape[0] // G
    tabs = {k: goldens[f"grouped_in_{k}"].reshape(
        (G, M) + goldens[f"grouped_in_{k}"].shape[1:])
        for k in STAT_KEYS + ("radius", "origin")}
    got = _golden_absorb(goldens, "grouped", goldens["grouped_leaf"],
                         goldens["X"], goldens["y"], goldens["grouped_w"],
                         backend, tabs=tabs)
    for k in STAT_KEYS:
        want = goldens[f"folded_{backend}_{k}"]
        got_k = got[k].reshape(want.shape)
        if backend == "jnp":
            np.testing.assert_array_equal(got_k, want, err_msg=k)
        else:
            _assert_close_per_table(got_k, want, k)


@pytest.mark.parametrize("backend", ["interpret", "jnp"])
def test_forest_update_matches_folded_golden(goldens, backend):
    """A small forest learning three batches: every table, leaf mean and
    node count against the forest that absorbed through the folded call —
    bit-identical on jnp, within 1e-6 of each table's scale on the kernel
    path."""
    cfg = fr.ForestConfig(tree=ht.HTRConfig(
        n_features=3, max_nodes=15, n_bins=16, grace_period=100,
        max_depth=4, r0=0.3, split_backend=backend), n_trees=3)
    state = fr.init_forest(cfg, jax.random.PRNGKey(3))
    upd = jax.jit(lambda s, Xb, yb: fr.update(cfg, s, Xb, yb)[0])
    X, y = goldens["forest_X"], goldens["forest_y"]
    for s in range(3):
        state = upd(state, X[s * 256:(s + 1) * 256], y[s * 256:(s + 1) * 256])
    trees = state["trees"]
    got = {**_as_dict(trees["ao_y"], trees["ao_sum_x"]),
           "ystats_mean": np.asarray(trees["ystats"]["mean"])}
    np.testing.assert_array_equal(np.asarray(trees["n_nodes"]),
                                  goldens[f"forest_{backend}_n_nodes"])
    for k, v in got.items():
        want = goldens[f"forest_{backend}_{k}"]
        if backend == "jnp":
            np.testing.assert_array_equal(v, want, err_msg=k)
        else:
            _assert_close_per_table(v, want, k)


def _pallas_grids(jaxpr):
    """Grid of every ``pallas_call`` in a jaxpr, nested jaxprs included."""
    from jax.extend import core as jex
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(sub, jex.ClosedJaxpr):
                    grids += _pallas_grids(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    grids += _pallas_grids(sub)
    return grids


def test_absorb_grid_walks_member_by_member():
    """At the benchmark cells' shapes (T = 10 members of M = 1,023 tables,
    F = 10, C = 64, B = 4,096) the forest's absorb stage launches one
    kernel of 12,800 grid steps, where the folded call it replaces
    walks 128,000: the tree axis is engaged."""
    T, M, F, C, B = 10, 1023, 10, 64, 4096
    cfg = fr.ForestConfig(tree=ht.HTRConfig(
        n_features=F, max_nodes=M, n_bins=C, split_backend="interpret"),
        n_trees=T)
    trees = jax.eval_shape(lambda: fr.init_forest(
        cfg, jax.random.PRNGKey(0)))["trees"]
    sd = jax.ShapeDtypeStruct
    leaf, w = sd((T, B), jnp.int32), sd((T, B), jnp.float32)
    X, y = sd((B, F), jnp.float32), sd((B,), jnp.float32)
    grouped = jax.make_jaxpr(
        lambda tr, l, X, y, w: fr._fused_absorb_tables(
            cfg, tr["ao_y"], tr["ao_sum_x"], tr, l, X, y, w))(
        trees, leaf, X, y, w)
    assert [math.prod(g) for g in _pallas_grids(grouped.jaxpr)] == [12800]

    flat = lambda a: sd((T * M,) + a.shape[2:], a.dtype)
    folded = jax.make_jaxpr(
        lambda ay, sx, r, o, gl, X, y, w: ops.forest_update(
            ay, sx, r, o, gl, X, y, w, backend="interpret"))(
        jax.tree.map(flat, trees["ao_y"]), flat(trees["ao_sum_x"]),
        flat(trees["ao_radius"]), flat(trees["ao_origin"]),
        sd((T * B,), jnp.int32), sd((T * B, F), jnp.float32),
        sd((T * B,), jnp.float32), sd((T * B,), jnp.float32))
    assert [math.prod(g) for g in _pallas_grids(folded.jaxpr)] == [128000]

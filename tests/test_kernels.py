"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qo
from repro.kernels import ops, ref
from repro.kernels.qo_update import qo_update_pallas
from repro.kernels.qo_query import qo_query_pallas


@pytest.mark.parametrize("cap", [128, 256, 512])
@pytest.mark.parametrize("n", [64, 1000, 4096])
def test_qo_update_kernel_matches_oracle(cap, n, rng):
    x = rng.normal(0.3, 1.7, n).astype(np.float32)
    y = (np.sin(x) * 3).astype(np.float32)
    t0 = qo.init(cap, radius=0.07, origin=0.3)
    t_ref = qo.update(t0, jnp.array(x), jnp.array(y))
    t_ker = ops.qo_update(t0, jnp.array(x), jnp.array(y), interpret=True)
    for k in ("n", "mean", "m2"):
        np.testing.assert_allclose(np.asarray(t_ref["y"][k]),
                                   np.asarray(t_ker["y"][k]),
                                   rtol=5e-4, atol=5e-4, err_msg=k)
    np.testing.assert_allclose(np.asarray(t_ref["sum_x"]),
                               np.asarray(t_ker["sum_x"]), rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("cap", [128, 256])
def test_qo_update_kernel_weighted(cap, rng):
    n = 777
    x = rng.normal(0, 1, n).astype(np.float32)
    y = (x * 2 + 1).astype(np.float32)
    w = rng.uniform(0.1, 2.0, n).astype(np.float32)
    t0 = qo.init(cap, radius=0.1)
    t_ref = qo.update(t0, jnp.array(x), jnp.array(y), jnp.array(w))
    t_ker = ops.qo_update(t0, jnp.array(x), jnp.array(y), jnp.array(w),
                          interpret=True)
    for k in ("n", "mean", "m2"):
        np.testing.assert_allclose(np.asarray(t_ref["y"][k]),
                                   np.asarray(t_ker["y"][k]),
                                   rtol=1e-3, atol=1e-3, err_msg=k)


def test_qo_update_kernel_incremental(rng):
    """Seeded continuation: second call accumulates onto the first."""
    cap = 128
    x = rng.normal(0, 1, 600).astype(np.float32)
    y = x.copy()
    t = qo.init(cap, radius=0.1)
    t = ops.qo_update(t, jnp.array(x[:300]), jnp.array(y[:300]), interpret=True)
    t = ops.qo_update(t, jnp.array(x[300:]), jnp.array(y[300:]), interpret=True)
    ref_t = qo.update(qo.init(cap, radius=0.1), jnp.array(x), jnp.array(y))
    np.testing.assert_allclose(np.asarray(t["y"]["n"]),
                               np.asarray(ref_t["y"]["n"]), atol=1e-3)
    np.testing.assert_allclose(float(qo.total_stats(t)["mean"]),
                               float(qo.total_stats(ref_t)["mean"]), rtol=1e-4)


@pytest.mark.parametrize("cap", [128, 256, 512])
def test_qo_query_kernel_matches_oracle(cap, rng):
    x = rng.normal(0.5, 2.0, 3000).astype(np.float32)
    y = np.where(x <= 1.0, 0.0, 5.0).astype(np.float32)
    t = qo.update(qo.init(cap, radius=0.15, origin=0.5),
                  jnp.array(x), jnp.array(y))
    dense, _ = ref.pack_table(t)
    out_k = qo_query_pallas(dense, interpret=True)
    out_r = ref.qo_query_ref(dense)
    # VR scores equal where valid
    valid = np.isfinite(np.asarray(out_r[0]))
    np.testing.assert_allclose(np.asarray(out_k[0])[valid],
                               np.asarray(out_r[0])[valid], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out_k[1])[valid],
                               np.asarray(out_r[1])[valid], rtol=1e-4)
    r_api = ops.qo_best_split(t, interpret=True)
    r_core = qo.best_split(t)
    np.testing.assert_allclose(float(r_api.threshold), float(r_core.threshold),
                               rtol=1e-4)
    np.testing.assert_allclose(float(r_api.merit), float(r_core.merit),
                               rtol=1e-3)


def test_query_kernel_sparse_table(rng):
    """Few occupied, widely separated bins."""
    t = qo.init(256, radius=0.01)
    x = np.array([-1.0, -1.0, 0.5, 0.5, 0.9], np.float32)
    y = np.array([0.0, 0.1, 5.0, 5.1, 5.2], np.float32)
    t = qo.update(t, jnp.array(x), jnp.array(y))
    r_k = ops.qo_best_split(t, interpret=True)
    r_c = qo.best_split(t)
    assert bool(r_k.valid)
    np.testing.assert_allclose(float(r_k.threshold), float(r_c.threshold), rtol=1e-5)
    # split must separate the -1 cluster from the rest
    assert -1.0 < float(r_k.threshold) < 0.5


def test_kernel_tile_padding(rng):
    """N not a multiple of the tile: padding rows must not contribute."""
    for n in (1, 127, 129, 1025):
        x = rng.normal(0, 1, n).astype(np.float32)
        t = ops.qo_update(qo.init(128, radius=0.2), jnp.array(x), jnp.array(x),
                          interpret=True)
        assert abs(float(qo.total_stats(t)["n"]) - n) < 1e-3


# --------------------------------------------------------------------------
# qo_update tile clamp: pad/clamp is a schedule, never a semantics, knob
# --------------------------------------------------------------------------

def test_qo_update_tile_clamp_formula():
    """A batch whose pow-2 round-up fits one maximal tile is absorbed in
    a SINGLE pass of exactly that round-up (floored at the 128-lane
    alignment) no matter what tile was requested — the request is a
    streaming cap for big batches, not a splitter for small ones.  The
    old min(tile, round_up) clamp split B = 129 into two 128-passes
    under tile=128 but one 256-pass otherwise: same math, different f32
    merge order, different bits."""
    assert ops.qo_update_tile(1, 1024) == 128
    assert ops.qo_update_tile(127, 1024) == 128
    assert ops.qo_update_tile(128, 1024) == 128
    assert ops.qo_update_tile(129, 1024) == 256
    assert ops.qo_update_tile(129, 128) == 256     # request ignored: 1 pass
    assert ops.qo_update_tile(1024, 128) == 1024   # still single-pass
    assert ops.qo_update_tile(4096, 1024) == 1024  # big B: requested cap
    assert ops.qo_update_tile(4096, 512) == 512    # streaming cap honored


@pytest.mark.parametrize("B", [1, 127, 128, 129])
def test_qo_update_clamp_bit_identical_across_tiles(B, rng):
    """B around the 128 boundary x every tile choice: the padded/clamped
    update must be BIT-identical — the single-pass rule resolves every
    request to the same one-tile schedule, and pad rows carry w = 0 and
    vanish, so no tile choice may perturb a single bit."""
    x = rng.normal(0.2, 1.3, B).astype(np.float32)
    y = (x * 1.7 - 0.4).astype(np.float32)
    t0 = qo.init(128, radius=0.15)
    outs = []
    for tile in (128, 256, 1024):
        t = ops.qo_update(t0, jnp.array(x), jnp.array(y), tile=tile,
                          interpret=True)
        outs.append(jax.tree.leaves(t))
    for leaves in outs[1:]:
        for a, b in zip(outs[0], leaves):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"B={B}: tile choice changed bits")


def test_pallas_backend_falls_back_off_tpu(rng):
    """backend="pallas" off-TPU must refuse to run (no silent fallback
    to the interpreter, which would pass interpreter numbers off as the
    compiled kernel's), while the explicit "interpret" backend runs the
    same kernel body and agrees with the jnp lowering."""
    if jax.default_backend() == "tpu":
        pytest.skip("native kernel path exists here")
    assert ops._kernel_interpret("interpret") is True
    M, F, C, B = 16, 3, 8, 64
    from repro.core import stats
    ao_y = stats.init((M, F, C))
    ao_sum_x = jnp.zeros((M, F, C))
    ao_radius = jnp.full((M, F), 0.2, jnp.float32)
    ao_origin = jnp.zeros((M, F), jnp.float32)
    leaf = jnp.array(rng.integers(0, M, B), jnp.int32)
    X = jnp.array(rng.normal(0, 1, (B, F)).astype(np.float32))
    y = jnp.array(rng.normal(0, 1, B).astype(np.float32))
    with pytest.raises(RuntimeError, match="TPU"):
        ops._kernel_interpret("pallas")
    with pytest.raises(RuntimeError, match="TPU"):
        ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                          leaf, X, y, backend="pallas")
    ky, ksx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                leaf, X, y, backend="interpret")
    jy, jsx = ops.forest_update(ao_y, ao_sum_x, ao_radius, ao_origin,
                                leaf, X, y, backend="jnp")
    np.testing.assert_allclose(np.asarray(ky["n"]), np.asarray(jy["n"]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(ksx), np.asarray(jsx),
                               rtol=1e-4, atol=1e-3)

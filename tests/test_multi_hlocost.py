"""Tests for the multi-target QO extension and the HLO cost walker."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multi, qo
from repro.launch import hlocost


# ---- multi-target QO (paper §7 future work) ------------------------------

def test_multi_target_reduces_to_single(rng):
    x = rng.normal(0, 1, 4000).astype(np.float32)
    y = np.where(x <= 0.2, 1.0, 7.0).astype(np.float32)
    t1 = qo.update(qo.init(256, radius=0.1), jnp.array(x), jnp.array(y))
    tm = multi.update(multi.init(256, 1, radius=0.1), jnp.array(x),
                      jnp.array(y[:, None]))
    r1, rm = qo.best_split(t1), multi.best_split(tm)
    np.testing.assert_allclose(float(r1.threshold), float(rm.threshold),
                               rtol=1e-4)
    assert int(qo.n_slots(t1)) == int(multi.n_slots(tm))


def test_multi_target_finds_shared_split(rng):
    """Two targets that agree on the cut point; one has 100x the scale —
    per-target normalization must keep both influential."""
    x = rng.normal(0, 1, 6000).astype(np.float32)
    y1 = np.where(x <= -0.1, 0.0, 1.0) + 0.05 * rng.normal(0, 1, 6000)
    y2 = 100 * np.where(x <= -0.1, 2.0, 5.0) + rng.normal(0, 1, 6000)
    Y = np.stack([y1, y2], 1).astype(np.float32)
    t = multi.update(multi.init(512, 2, radius=0.05), jnp.array(x),
                     jnp.array(Y))
    r = multi.best_split(t)
    assert bool(r.valid)
    assert abs(float(r.threshold) + 0.1) < 0.06


def test_multi_target_conflicting_targets(rng):
    """Targets with different best cuts: merit maximizes the AVERAGE."""
    x = rng.uniform(-1, 1, 8000).astype(np.float32)
    y1 = np.where(x <= -0.5, 0.0, 1.0)
    y2 = np.where(x <= 0.5, 0.0, 1.0)
    Y = np.stack([y1, y2], 1).astype(np.float32)
    t = multi.update(multi.init(512, 2, radius=0.02), jnp.array(x),
                     jnp.array(Y))
    r = multi.best_split(t)
    # either boundary is a 0.5-normalized-VR optimum; both beat the middle
    assert bool(r.valid)
    assert abs(abs(float(r.threshold)) - 0.5) < 0.1


# ---- HLO cost walker ------------------------------------------------------

def test_walker_counts_scan_trip_counts():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0]

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)).compile()
    r = hlocost.analyze(comp.as_text())
    assert r["flops"] == 5 * 2 * 64 ** 3
    # raw cost_analysis counts the body once — the walker must not
    assert comp.cost_analysis()["flops"] < r["flops"]


def test_walker_nested_scans_multiply():
    def g(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), None
            return jax.lax.scan(inner, c, None, length=3)[0], None
        return jax.lax.scan(outer, x, w)[0]

    comp = jax.jit(g).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
        jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)).compile()
    r = hlocost.analyze(comp.as_text())
    assert r["flops"] == 4 * 3 * 2 * 32 ** 3


def test_walker_plain_matmul():
    comp = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 64), jnp.float32)).compile()
    r = hlocost.analyze(comp.as_text())
    assert r["flops"] == 2 * 128 * 256 * 64
    # traffic at least the operands + result once
    assert r["bytes"] >= (128 * 256 + 256 * 64 + 128 * 64) * 4

"""Distributed sketch + QO telemetry tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import qo, sketch
from repro.train import monitor as MON


def test_quantile_accuracy(rng):
    x = rng.normal(10, 3, 50000).astype(np.float32)
    t = qo.update(qo.init(512, radius=0.1, origin=10.0), jnp.array(x),
                  jnp.array(x))
    for q in (0.1, 0.5, 0.9, 0.99):
        est = float(sketch.quantile(t, jnp.asarray(q)))
        true = float(np.quantile(x, q))
        assert abs(est - true) < 0.15, (q, est, true)


def test_all_merge_across_devices():
    """shard_map all_merge == single-stream table (1 device => trivial but
    exercises the collective path; multi-device covered in test_sharding)."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((1,), ("d",))
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1, 1024).astype(np.float32)

    def f(xs):
        t = qo.update(qo.init(64, radius=0.2), xs, xs)
        return sketch.all_merge(t, "d")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P(),
                                check_vma=False))(jnp.array(x))
    ref = qo.update(qo.init(64, radius=0.2), jnp.array(x), jnp.array(x))
    np.testing.assert_allclose(np.asarray(out["y"]["n"]),
                               np.asarray(ref["y"]["n"]), atol=1e-3)


def test_monitor_observe_and_alerts():
    mon = MON.init_monitor()
    for i in range(100):
        mon = MON.observe(mon, loss=jnp.float32(5.0 + 0.01 * i),
                          grad_norm=jnp.float32(1.0),
                          step_time=jnp.float32(1.0))
    assert not bool(MON.loss_spike(mon, jnp.float32(5.5)))
    assert bool(MON.loss_spike(mon, jnp.float32(50.0)))
    assert not bool(MON.is_straggler(mon, jnp.float32(1.0)))
    assert bool(MON.is_straggler(mon, jnp.float32(10.0)))
    s = MON.summaries(mon)
    assert abs(float(s["step_time"]["mean"]) - 1.0) < 1e-3
    assert float(s["loss"]["count"]) == 100

"""Gradient compression: sketch-thresholded top-k + int8 all-reduce."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import compress


def test_sparsify_keeps_top_fraction(rng):
    g = {"a": jnp.array(rng.normal(0, 1, (64, 64)).astype(np.float32)),
         "b": jnp.array(rng.normal(0, 3, (128,)).astype(np.float32))}
    err = compress.init_error_state(g)
    sparse, new_err, m = compress.sparsify_with_sketch(g, err, keep_frac=0.1)
    dens = float(m["density"])
    assert 0.02 < dens < 0.35  # sketch threshold approximates 10%
    # kept entries are the large ones
    kept = np.abs(np.asarray(sparse["a"]))[np.asarray(sparse["a"]) != 0]
    dropped_max = np.abs(np.asarray(g["a"] - sparse["a"])).max()
    assert kept.min() >= dropped_max * 0.5


def test_error_feedback_is_lossless_over_time(rng):
    """sum(transmitted) + final_error == sum(original grads)."""
    g = jnp.array(rng.normal(0, 1, (256,)).astype(np.float32))
    err = jnp.zeros_like(g)
    sent = jnp.zeros_like(g)
    for _ in range(5):
        sparse, err, _ = compress.sparsify_with_sketch(
            {"g": g}, {"g": err}, keep_frac=0.2)
        sparse, err = sparse["g"], err["g"]
        sent = sent + sparse
    np.testing.assert_allclose(np.asarray(sent + err), np.asarray(5 * g),
                               rtol=1e-4, atol=1e-4)


def test_int8_quantized_psum_single_device(rng):
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((1,), ("pod",))
    g = jnp.array(rng.normal(0, 0.1, (64,)).astype(np.float32))

    out = jax.shard_map(
        lambda x: compress.quantized_psum({"g": x}, "pod")["g"],
        mesh=mesh, in_specs=P(), out_specs=P())(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g),
                               atol=float(jnp.abs(g).max()) / 100)


def test_int8_encode_decode_roundtrip(rng):
    g = jnp.array(rng.normal(0, 2, (1000,)).astype(np.float32))
    q, s = compress.int8_encode(g)
    rec = compress.int8_decode(q, s)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(rec), np.asarray(g),
                               atol=float(jnp.abs(g).max()) / 120)

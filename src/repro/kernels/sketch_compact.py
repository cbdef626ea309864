"""Pallas TPU kernel: rank-bucket compaction of sorted sketch centroids.

The compute stage of the sketch observer's compaction (DESIGN.md §2.8):
the jnp caller sorts each table's J centroids by prototype and assigns
rank buckets (``repro.core.sketch.sort_planes`` / ``_bucket_ids`` — sort
networks don't pay their way in a hand kernel), and this kernel reduces
each bucket with the exact grouped two-pass (n, mean, M2) form:

    grid  = (row-tiles,)
    in    = (5, tile_r, Jp)     rows: n / mean / M2 / sum_x / bucket
    out   = (4, tile_r, Kp)

with the (T·M, F) table axes flattened to R rows (same packing idiom as
``qo_merge``), J input centroids and K output buckets each padded to the
128-lane tile.  Per output bucket k (a rolled ``fori_loop`` — K is a
config constant, typically 8-64):

    mask_k = (bucket == k)                            VPU compare
    n_k, Σwy_k, Σwx_k = Σ_lanes mask_k · plane        row reduction
    mean_k = Σwy_k / n_k                              (0 where n_k == 0)
    M2_k   = Σ_lanes mask_k · (M2 + n·(mean − mean_k)²)

and the k-th output lane is selected with a ``broadcasted_iota`` one-hot
(1-D iota doesn't lower on TPU).  Pad lanes carry bucket = −1 and zero
weight, so they match no k and contribute nothing; pad rows produce
all-zero output rows.  Exactness: bucket statistics are bit-for-bit a
fixed-order reduction of their member centroids, so kernel vs jnp
``segment_sum`` agree to f32 reduction-order tolerance (the tuner gate
compares bitwise only within one backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qo_update_leaves import round_up

__all__ = ["pack_compact_planes", "unpack_compact_planes",
           "sketch_compact_pallas"]


def pack_compact_planes(n, mean, m2, sum_x, bucket, *,
                        tile_r: int = 256) -> jax.Array:
    """Sorted (..., J) centroid planes + bucket ids -> (5, Rp, Jp) blocks.

    Leading axes flatten row-major to R rows; rows pad to the row tile
    and lanes to 128.  Bucket ids ride as f32 with −1 in every pad lane
    and pad row, so padding can never alias a real bucket.
    """
    J = n.shape[-1]
    R = 1
    for d in n.shape[:-1]:
        R *= d
    Jp, Rp = round_up(J, 128), round_up(R, tile_r)
    planes = jnp.stack([a.reshape(R, J) for a in
                        (n, mean, m2, sum_x, bucket.astype(jnp.float32))])
    return jnp.full((5, Rp, Jp), -1.0, jnp.float32) \
        .at[:4].set(0.0).at[:, :R, :J].set(planes)


def unpack_compact_planes(dense: jax.Array, lead, k_out: int):
    """Dense (4, Rp, Kp) -> four ``lead + (k_out,)`` planes."""
    R = 1
    for d in lead:
        R *= d
    planes = dense[:, :R, :k_out].reshape((4,) + tuple(lead) + (k_out,))
    return planes[0], planes[1], planes[2], planes[3]


def _sketch_compact_kernel(a_ref, o_ref, *, k_out: int):
    n, mean, m2, sx, bk = (a_ref[i] for i in range(5))
    tile_r, Kp = n.shape[0], o_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_r, Kp), 1) \
        .astype(jnp.float32)            # Mosaic builds integer iotas only

    def bucket(k, acc):
        out_n, out_mean, out_m2, out_sx = acc
        mask = (bk == k.astype(jnp.float32)).astype(jnp.float32)
        n_k = jnp.sum(mask * n, axis=-1)
        sy_k = jnp.sum(mask * n * mean, axis=-1)
        sx_k = jnp.sum(mask * sx, axis=-1)
        occ = n_k > 0
        mean_k = jnp.where(occ, sy_k / jnp.where(occ, n_k, 1.0), 0.0)
        d = mean - mean_k[:, None]
        m2_k = jnp.where(occ, jnp.sum(mask * (m2 + n * d * d), axis=-1), 0.0)
        col = (lane == k.astype(jnp.float32)).astype(jnp.float32)
        return (out_n + n_k[:, None] * col,
                out_mean + mean_k[:, None] * col,
                out_m2 + m2_k[:, None] * col,
                out_sx + sx_k[:, None] * col)

    # a rolled loop keeps one bucket's temporaries live at a time: the
    # unrolled form outgrows the 16 MiB scoped VMEM at K = 64
    zero = jnp.zeros((tile_r, Kp), jnp.float32)
    out_n, out_mean, out_m2, out_sx = jax.lax.fori_loop(
        0, k_out, bucket, (zero, zero, zero, zero))
    o_ref[0] = out_n
    o_ref[1] = out_mean
    o_ref[2] = out_m2
    o_ref[3] = out_sx


@functools.partial(jax.jit, static_argnames=("k_out", "tile_r", "interpret"))
def sketch_compact_pallas(packed: jax.Array, *, k_out: int,
                          tile_r: int = 256,
                          interpret: bool = False) -> jax.Array:
    """Reduce packed (5, Rp, Jp) sorted-centroid blocks to (4, Rp, Kp)."""
    rows, Rp, Jp = packed.shape
    assert rows == 5, packed.shape
    assert Rp % tile_r == 0, (Rp, tile_r)
    Kp = round_up(k_out, 128)
    return pl.pallas_call(
        functools.partial(_sketch_compact_kernel, k_out=k_out),
        grid=(Rp // tile_r,),
        in_specs=[pl.BlockSpec((5, tile_r, Jp), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((4, tile_r, Kp), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((4, Rp, Kp), jnp.float32),
        interpret=interpret,
    )(packed)

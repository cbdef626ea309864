"""Pallas TPU kernel: level-synchronous batched routing for T trees at once.

The read path's hot loop (DESIGN.md §2.6).  The seed routed with
``vmap``-of-scalar ``fori_loop`` — per-row dependent gathers through five
separate node arrays, re-dispatched per tree by the forest layer.  Here
routing is the batch-parallel primitive (Pham et al.'s massively-parallel
traversal model, PAPERS.md): ALL B rows advance through ALL T trees one
depth ply at a time over SoA node tables, one ``pallas_call`` with

    grid = (T, batch-tiles)

so each grid step owns one tree's (tile_b,) slice of row states while the
(tile_b, Fp) X block is shared across the T grid dimension — the batch is
never materialized T times.  Each tree's node attributes pack into one
dense plane, and grid step t reads only tree t's:

    attrs : (T, Mp, 128) f32
      lane 0: feature   lane 1: threshold   lane 2: left    lane 3: right

with tree-local node ids and leaves self-looped (``left = right =
self``), so a settled row keeps re-selecting its own leaf and no
``is_leaf`` test exists at all.  Per ply the whole transition is one MXU
contraction and one compare:

    oh_node : (tile_b, Mp)   row r -> its current node
    a       = oh_node @ attrs[t]                   (tile_b, 128) on the MXU
    x_r     = sum(onehot(feature_r) * X_r)         per-row feature select
    node'   = where(x_r <= threshold_r, left_r, right_r)

The one-hot spans one tree's Mp nodes, not the forest's T·M: its VMEM
footprint and MXU work stay those of a single tree as T grows.  The
one-hot matmul is exact (a single 1.0 per row), so thresholds and
integer ids round-trip bit-identically; routing therefore matches the
scalar oracle id-for-id on every backend.  ``plies`` (the ply count) is
static — any count >= the realized tree depth returns identical leaves,
which is what lets ops.py bucket it and core/serve.py trim snapshots to
the *realized* depth rather than ``cfg.max_depth``.  Batch padding rides
free: pad rows route from the root like any other and are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qo_update_leaves import round_up

ATTR_LANES = 128
LANE_FEATURE, LANE_THRESHOLD, LANE_LEFT, LANE_RIGHT = 0, 1, 2, 3

__all__ = [
    "ATTR_LANES", "LANE_FEATURE", "LANE_THRESHOLD", "LANE_LEFT",
    "LANE_RIGHT", "fold_route_tables", "pack_route_attrs", "qo_route_pallas",
]


def fold_route_tables(feature, threshold, child, is_leaf):
    """SoA node arrays -> folded self-looped transition tables.

    feature/threshold/is_leaf: (T, M); child: (T, M, 2) with -1 at leaves.
    Folds the tree axis into global node ids (``t*M + j``), rewrites
    children to global ids and self-loops every leaf, so one transition
    step is a no-op exactly at settled rows.  Returns
    ``(feature, threshold, left, right)``, all (T*M,) — feature/left/right
    int32, threshold f32.  The jnp sweep gathers these as one packed row;
    :func:`pack_route_attrs` builds the same self-looped relation with
    tree-local ids for the kernel.
    """
    T, M = feature.shape
    N = T * M
    gids = (jnp.arange(T, dtype=jnp.int32)[:, None] * M
            + jnp.arange(M, dtype=jnp.int32)[None, :])            # (T, M)
    gchild = jnp.where(
        child >= 0,
        child + (jnp.arange(T, dtype=jnp.int32) * M)[:, None, None], -1)
    left = jnp.where(is_leaf, gids, gchild[..., 0]).reshape(N)
    right = jnp.where(is_leaf, gids, gchild[..., 1]).reshape(N)
    return (feature.reshape(N), threshold.reshape(N), left, right)


def pack_route_attrs(feature, threshold, child, is_leaf) -> jax.Array:
    """SoA node arrays (T, M) -> per-tree (T, Mp, 128) routing planes.

    Node ids stay tree-local; leaves and the pad rows in [M, Mp) self-loop,
    so no reachable transition leaves a tree's plane.  All-f32: node ids
    are exact far past any real tree's size (one-hot contractions copy
    them bit-exactly).
    """
    T, M = feature.shape
    Mp = round_up(max(M, 8), 8)
    own = jnp.arange(M, dtype=jnp.int32)[None, :]
    left = jnp.where(is_leaf, own, child[..., 0])
    right = jnp.where(is_leaf, own, child[..., 1])
    ids = jnp.broadcast_to(jnp.arange(Mp, dtype=jnp.float32), (T, Mp))
    attrs = jnp.zeros((T, Mp, ATTR_LANES), jnp.float32)
    attrs = attrs.at[:, :, LANE_LEFT].set(ids).at[:, :, LANE_RIGHT].set(ids)
    attrs = attrs.at[:, :M, LANE_FEATURE].set(feature.astype(jnp.float32))
    attrs = attrs.at[:, :M, LANE_THRESHOLD].set(threshold)
    attrs = attrs.at[:, :M, LANE_LEFT].set(left.astype(jnp.float32))
    return attrs.at[:, :M, LANE_RIGHT].set(right.astype(jnp.float32))


def _qo_route_kernel(x_ref, attrs_ref, out_ref, *, plies: int):
    attrs = attrs_ref[0]                                         # (Mp, 128)
    x = x_ref[...]                                               # (tile_b, Fp)
    tile_b, Fp = x.shape
    Mp = attrs.shape[0]

    # Mosaic builds integer iotas only; cast to compare with f32 ids
    slot = jax.lax.broadcasted_iota(jnp.int32, (tile_b, Mp), 1) \
        .astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_b, ATTR_LANES), 1)
    lane_f = jax.lax.broadcasted_iota(jnp.int32, (tile_b, Fp), 1) \
        .astype(jnp.float32)
    # HIGHEST keeps f32 operands f32 on the MXU: the one-hot gather must
    # copy thresholds and node ids exactly
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    pick = functools.partial(jnp.sum, axis=1, keepdims=True)

    def ply(_, node):                                            # (tile_b, 1)
        oh = (node == slot).astype(jnp.float32)
        a = dot(oh, attrs)                                       # (tile_b, 128)
        f = pick(jnp.where(lane == LANE_FEATURE, a, 0.0))
        thr = pick(jnp.where(lane == LANE_THRESHOLD, a, 0.0))
        left = pick(jnp.where(lane == LANE_LEFT, a, 0.0))
        right = pick(jnp.where(lane == LANE_RIGHT, a, 0.0))
        xv = pick(jnp.where(lane_f == f, x, 0.0))
        return jnp.where(xv <= thr, left, right)

    # every row starts at its tree's root (local id 0).  The ply loop is
    # rolled over a 2-D column carry: one body to compile however deep the
    # bucket (Mosaic cannot carry 1-D vectors through a loop)
    node = jax.lax.fori_loop(0, plies, ply,
                             jnp.zeros((tile_b, 1), jnp.float32))
    out_ref[0, 0, :] = node[:, 0].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("plies", "tile_b", "interpret"))
def qo_route_pallas(x: jax.Array, attrs: jax.Array, *, plies: int,
                    tile_b: int = 256, interpret: bool = False) -> jax.Array:
    """x: (Bp, Fp) f32; attrs: (T, Mp, 128) from :func:`pack_route_attrs`.
    Bp must be a multiple of ``tile_b`` (ops.py pads; pad rows route from
    the root and are sliced off there).  Returns (T, 1, Bp) i32
    tree-local leaf ids after ``plies`` transition steps from the root.
    """
    Bp, Fp = x.shape
    T, Mp, lanes = attrs.shape
    assert Bp % tile_b == 0 and lanes == ATTR_LANES
    if plies == 0:
        return jnp.zeros((T, 1, Bp), jnp.int32)
    return pl.pallas_call(
        functools.partial(_qo_route_kernel, plies=plies),
        grid=(T, Bp // tile_b),
        in_specs=[
            pl.BlockSpec((tile_b, Fp), lambda t, i: (i, 0)),      # shared X
            pl.BlockSpec((1, Mp, ATTR_LANES),
                         lambda t, i: (t, 0, 0)),                 # tree t's plane
        ],
        out_specs=pl.BlockSpec((1, 1, tile_b), lambda t, i: (t, 0, i)),
        out_shape=jax.ShapeDtypeStruct((T, 1, Bp), jnp.int32),
        interpret=interpret,
    )(x, attrs)

"""Pallas TPU kernel: fused QO update for EVERY (leaf, feature) table.

This is the forest-scale generalization of :mod:`repro.kernels.qo_update`
(DESIGN.md §2.3).  The tree-level hot path routes a batch of B instances to
leaves and must fold each row into F per-feature QO tables of its leaf —
`M*F` tables of C bins each.  The pure-jnp seed path did this with four
``segment_sum`` scatters over a flat ``M*F*C`` id space; here the whole
absorb stage is one ``pallas_call`` with a

    grid = (F, G, leaf-tiles per group, batch-tiles)

over G groups of M tables each (a forest's members, DESIGN.md §5.1; one
group for a single tree).  The batch is shared by every group, but each
group routes it on its own: group g's leaf ids and weights are row g of
``leaf``/``w``, and its rows can only land in its own tables, so the grid
pairs group g's leaf tiles with group g's ids alone — a row never meets
another group's tables, and no grid step is spent on pairs that cannot
hold a row.  Each grid step owns a (tile_m, Cp) slab of tables for one
feature and streams a (tile_b,) slice of the batch through the MXU:

    onehot_leaf : (T, tile_m)   row t -> local leaf slot (0 outside tile)
    onehot_bin  : (T, Cp)       row t -> quantized bin of x[t, f]
    n_add       = onehot_leaf^T @ onehot_bin                  (weighted)
    sum_x_add   = onehot_leaf^T @ (onehot_bin * x)
    sum_y_add   = onehot_leaf^T @ (onehot_bin * y)

The per-(leaf, bin) tile M2 uses the two-pass residual form: the tile bin
means are gathered back per row with one more MXU matvec and squared
residuals are contracted exactly like the sums — no naive `sum y^2`
cancellation (paper §3).  Tile statistics merge into the running table
with the Chan operator (Eqs. 4-5) kept in VMEM across the (sequential,
innermost) batch-tile grid dimension, so each table slab does one HBM
round-trip per call regardless of B.

Dense forest layout (lane dim Cp = C rounded up to 128):

    tables : (F, 8, G*Mp_g, Cp) f32
      row 0: n        row 1: mean     row 2: M2      row 3: sum_x
      row 4: radius   row 5: origin   (broadcast along lanes)
      row 6: attempt mask (query kernel only)        row 7: padding

Each group's M tables are padded to Mp_g (a multiple of tile_m) on their
own, so leaf tile j of group g is table block ``g*(Mp_g/tile_m) + j``
and no tile straddles two groups (M = 1,023 pads to Mp_g = 1,024).

Routed leaf ids are group-local (0..M-1) and ride along as int32
``(G, 1, Bp)``, the weights as ``(G, 1, Bp)``; x ``(F, 1, Bp)`` and y
``(1, Bp)`` are read by every group.  Rows whose leaf falls outside the
current leaf tile contribute nothing (their one-hot leaf row is all
zero), which also makes batch padding (leaf id = -1, w = 0) free.  No
``(B*F,)`` segment-id array is ever materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import qo as qo_lib  # noqa: F401  (layout mirrors the dict table)

FOREST_ROWS = 8
ROW_N, ROW_MEAN, ROW_M2, ROW_SUMX = 0, 1, 2, 3
ROW_RADIUS, ROW_ORIGIN, ROW_ATTEMPT = 4, 5, 6

__all__ = [
    "FOREST_ROWS", "ROW_N", "ROW_MEAN", "ROW_M2", "ROW_SUMX",
    "ROW_RADIUS", "ROW_ORIGIN", "ROW_ATTEMPT",
    "round_up", "pack_forest", "unpack_forest", "qo_update_leaves_pallas",
]


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def pack_forest(ao_y, ao_sum_x, ao_radius, ao_origin, attempt=None,
                *, tile_m: int = 128, groups: int = 1) -> jax.Array:
    """(G*M, F, C) dict-of-arrays state -> dense (F, 8, G*Mp_g, Cp) forest.

    The table axis holds ``groups`` blocks of M tables; each block is
    padded to Mp_g on its own (module docstring)."""
    N, F, C = ao_sum_x.shape
    M = N // groups
    Mp = round_up(M, min(tile_m, round_up(M, 8)))
    Cp = round_up(C, 128)
    dense = jnp.zeros((F, FOREST_ROWS, groups * Mp, Cp), jnp.float32)

    def spread(arr):  # (G*M, ...) -> (G*Mp, ...), zero rows after each group
        a = arr.reshape((groups, M) + arr.shape[1:])
        a = jnp.pad(a, [(0, 0), (0, Mp - M)] + [(0, 0)] * (a.ndim - 2))
        return a.reshape((groups * Mp,) + arr.shape[1:])

    def put(row, arr):  # arr: (G*M, F, C)
        return dense.at[:, row, :, :C].set(jnp.transpose(spread(arr), (1, 0, 2)))

    dense = put(ROW_N, ao_y["n"])
    dense = put(ROW_MEAN, ao_y["mean"])
    dense = put(ROW_M2, ao_y["m2"])
    dense = put(ROW_SUMX, ao_sum_x)
    # per-(leaf, feature) scalars broadcast along the lane dim
    dense = dense.at[:, ROW_RADIUS].set(spread(ao_radius).T[:, :, None])
    dense = dense.at[:, ROW_ORIGIN].set(spread(ao_origin).T[:, :, None])
    if attempt is not None:
        att = spread(attempt.astype(jnp.float32))[None, :, None]
        dense = dense.at[:, ROW_ATTEMPT].set(jnp.broadcast_to(
            att, (F, groups * Mp, Cp)))
    return dense


def unpack_forest(dense: jax.Array, M: int, C: int, groups: int = 1):
    """Dense (F, 8, G*Mp_g, Cp) -> (ao_y dict, ao_sum_x), shapes (G*M, F, C);
    ``M`` is the table count of one group."""
    F, _, GMp, _ = dense.shape
    Mp = GMp // groups

    def get(row):
        a = dense[:, row, :, :C].reshape(F, groups, Mp, C)[:, :, :M]
        return jnp.transpose(a, (1, 2, 0, 3)).reshape(groups * M, F, C)

    ao_y = {"n": get(ROW_N), "mean": get(ROW_MEAN), "m2": get(ROW_M2)}
    return ao_y, get(ROW_SUMX)


def _qo_update_leaves_kernel(leaf_ref, x_ref, y_ref, w_ref, tab_ref, out_ref,
                             *, n_bins: int, tile_m: int):
    j = pl.program_id(2)          # leaf tile within the group
    i = pl.program_id(3)          # batch tile (innermost: VMEM accumulation)

    @pl.when(i == 0)
    def _seed():
        out_ref[...] = tab_ref[...]

    Cp = out_ref.shape[3]
    T = x_ref.shape[2]
    x = x_ref[0, 0, :]
    yv = y_ref[0, :]
    w = w_ref[0, 0, :]
    leaf = leaf_ref[0, 0, :]

    # one-hot over the local leaf slots; rows outside this tile are all-zero
    lloc = leaf - j * tile_m
    slot = jax.lax.broadcasted_iota(jnp.int32, (T, tile_m), 1)
    oh_leaf = (lloc[:, None] == slot).astype(jnp.float32)

    # per-row radius/origin: gather via MXU, read back from lane 0
    # (HIGHEST keeps f32 operands f32 on the MXU here and below)
    dot_lm = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (T, Cp), 1)
    r_row = jnp.sum(jnp.where(lane == 0, dot_lm(oh_leaf, out_ref[0, ROW_RADIUS]),
                              0.0), axis=1)
    o_row = jnp.sum(jnp.where(lane == 0, dot_lm(oh_leaf, out_ref[0, ROW_ORIGIN]),
                              0.0), axis=1)

    safe_r = jnp.where(r_row > 0, r_row, 1.0)
    ids = jnp.floor((x - o_row) / safe_r).astype(jnp.int32) + n_bins // 2
    ids = jnp.clip(ids, 0, n_bins - 1)
    oh_bin = lane == ids[:, None]
    wbin = jnp.where(oh_bin, w[:, None], 0.0)

    # (tile_m, Cp) <- (T, tile_m)^T @ (T, Cp) contractions on the MXU
    contract = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    n_b = contract(oh_leaf, wbin)
    sx_b = contract(oh_leaf, wbin * x[:, None])
    sy_b = contract(oh_leaf, wbin * yv[:, None])

    safe_nb = jnp.where(n_b > 0, n_b, 1.0)
    mean_b = jnp.where(n_b > 0, sy_b / safe_nb, 0.0)
    # two-pass M2: gather each row's tile bin mean back, contract residuals
    mean_i = jnp.sum(jnp.where(oh_bin, dot_lm(oh_leaf, mean_b), 0.0), axis=1)
    resid = yv - mean_i
    m2_b = contract(oh_leaf, wbin * (resid * resid)[:, None])

    # Chan merge (Eqs. 4-5) of tile stats into the running table
    n0 = out_ref[0, ROW_N]
    mean0 = out_ref[0, ROW_MEAN]
    m20 = out_ref[0, ROW_M2]
    n = n0 + n_b
    safe_n = jnp.where(n > 0, n, 1.0)
    delta = mean_b - mean0
    mean = jnp.where(n > 0, (n0 * mean0 + n_b * mean_b) / safe_n, 0.0)
    m2 = jnp.where(n > 0, m20 + m2_b + delta * delta * (n0 * n_b) / safe_n, 0.0)

    out_ref[0, ROW_N] = n
    out_ref[0, ROW_MEAN] = mean
    out_ref[0, ROW_M2] = m2
    out_ref[0, ROW_SUMX] = out_ref[0, ROW_SUMX] + sx_b


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "tile_b", "tile_m", "interpret"))
def qo_update_leaves_pallas(tab: jax.Array, leaf: jax.Array, x: jax.Array,
                            y: jax.Array, w: jax.Array, *, n_bins: int,
                            tile_b: int = 256, tile_m: int = 128,
                            interpret: bool = False) -> jax.Array:
    """tab: (F, 8, G*Mp_g, Cp); leaf: (G, 1, Bp) i32 group-local ids;
    x: (F, 1, Bp); y: (1, Bp); w: (G, 1, Bp).

    The group count G is ``leaf.shape[0]``.  Bp must be a multiple of
    ``tile_b`` and Mp_g of ``tile_m`` (ops.py pads with w = 0 / leaf = -1).
    Returns the merged dense forest.
    """
    F, rows, GMp, Cp = tab.shape
    assert rows == FOREST_ROWS
    G, Bp = leaf.shape[0], x.shape[2]
    tiles = GMp // (G * tile_m)         # leaf tiles per group
    assert tiles * G * tile_m == GMp and Bp % tile_b == 0
    grid = (F, G, tiles, Bp // tile_b)

    kernel = functools.partial(_qo_update_leaves_kernel,
                               n_bins=n_bins, tile_m=tile_m)
    slab = lambda f, g, j, i: (f, 0, g * tiles + j, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile_b), lambda f, g, j, i: (g, 0, i)),  # leaf ids
            pl.BlockSpec((1, 1, tile_b), lambda f, g, j, i: (f, 0, i)),  # x feature
            pl.BlockSpec((1, tile_b), lambda f, g, j, i: (0, i)),        # y
            pl.BlockSpec((1, 1, tile_b), lambda f, g, j, i: (g, 0, i)),  # w
            pl.BlockSpec((1, FOREST_ROWS, tile_m, Cp), slab),  # seed tables
        ],
        out_specs=pl.BlockSpec((1, FOREST_ROWS, tile_m, Cp), slab),
        out_shape=jax.ShapeDtypeStruct((F, FOREST_ROWS, GMp, Cp), jnp.float32),
        interpret=interpret,
    )(leaf, x, y, w, tab)

"""Pallas TPU kernel: QO split-candidate query for ALL M*F tables at once.

Grid-over-tables variant of :mod:`repro.kernels.qo_query` (DESIGN.md §2.3).
The seed evaluated every (leaf, feature) table with ``vmap(vmap(best_split))``
— hundreds of tiny interpreter-glued scans.  Here one ``pallas_call`` with

    grid = (F, leaf-tiles)

lays a (tile_m, Cp) slab of tables across VPU sublanes and runs the
Hillis-Steele inclusive prefix *merge* (Chan operator, paper Eqs. 4-5)
along the lane dimension for all tables simultaneously: log2(Cp) steps of
shift + merge, no sequential per-table work.  The right-hand complement
comes from the paper's subtraction (Eqs. 6-7), giving the Variance
Reduction of every candidate boundary

    VR_i = s2(d) - nL_i/n * s2(left_i) - nR_i/n * s2(right_i)

Candidate thresholds are midpoints of neighbouring occupied prototypes,
found with two more log-depth last/next-valid-value propagations (no
gathers — TPU lanes shift, they don't scatter).

Attempt masking: row 6 of each table slab carries the leaf's attempt flag
(set when the leaf passed its grace period).  A slab whose leaves are all
below grace skips the whole evaluation via ``pl.when`` — split attempts
cost nothing for quiet regions of the forest — and masked tables report
``-inf`` scores.

Input:  dense forest (F, 8, Mp, Cp) — layout of qo_update_leaves.
Output: (F, 8, Mp, Cp): row 0 = VR scores (-inf invalid), row 1 =
candidate thresholds, rows 2-7 zero.  The per-table argmax is a trivial
epilogue in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.qo_update_leaves import (
    FOREST_ROWS, ROW_N, ROW_MEAN, ROW_M2, ROW_SUMX, ROW_ATTEMPT)

__all__ = ["qo_query_batched_pallas"]


def _shift_right(a, d, fill):
    """(R, C) shifted right by static d along lanes, filled on the left."""
    pad = jnp.full((a.shape[0], d), fill, a.dtype)
    return jnp.concatenate([pad, a[:, :-d]], axis=1)


def _shift_left(a, d, fill):
    pad = jnp.full((a.shape[0], d), fill, a.dtype)
    return jnp.concatenate([a[:, d:], pad], axis=1)


def _qo_query_batched_kernel(tab_ref, out_ref):
    Cp = tab_ref.shape[3]
    zero = jnp.zeros(out_ref.shape[2:], jnp.float32)

    att = tab_ref[0, ROW_ATTEMPT, :, 0:1] > 0                 # (tile_m, 1)

    # grace-period gate: a quiet slab writes -inf and skips all the math
    @pl.when(jnp.logical_not(jnp.any(att)))
    def _quiet():
        out_ref[0, 0] = jnp.full(zero.shape, -jnp.inf, jnp.float32)
        for r in range(1, FOREST_ROWS):
            out_ref[0, r] = zero

    @pl.when(jnp.any(att))
    def _evaluate():
        n = tab_ref[0, ROW_N]                                  # (tile_m, Cp)
        mean = tab_ref[0, ROW_MEAN]
        m2 = tab_ref[0, ROW_M2]
        sum_x = tab_ref[0, ROW_SUMX]
        occ = n > 0

        # ---- inclusive prefix merge, Hillis-Steele over lanes ------------
        pn, pmean, pm2 = n, mean, m2
        d = 1
        while d < Cp:
            sn = _shift_right(pn, d, 0.0)
            smean = _shift_right(pmean, d, 0.0)
            sm2 = _shift_right(pm2, d, 0.0)
            tn = sn + pn
            safe = jnp.where(tn > 0, tn, 1.0)
            delta = pmean - smean
            pmean = jnp.where(tn > 0, (sn * smean + pn * pmean) / safe, 0.0)
            pm2 = jnp.where(tn > 0,
                            sm2 + pm2 + delta * delta * (sn * pn) / safe, 0.0)
            pn = tn
            d *= 2

        tot_n = pn[:, Cp - 1:Cp]
        tot_mean = pmean[:, Cp - 1:Cp]
        tot_m2 = pm2[:, Cp - 1:Cp]

        # ---- complement via the paper's subtraction (Eqs. 6-7) -----------
        rn = tot_n - pn
        safe_rn = jnp.where(rn > 0, rn, 1.0)
        rmean = jnp.where(rn > 0, (tot_n * tot_mean - pn * pmean) / safe_rn,
                          0.0)
        delta = pmean - rmean
        safe_tot = jnp.where(tot_n > 0, tot_n, 1.0)
        rm2 = tot_m2 - pm2 - delta * delta * (rn * pn) / safe_tot
        rm2 = jnp.where(rn > 0, jnp.maximum(rm2, 0.0), 0.0)

        def var(nn, mm2):
            dd = nn - 1.0
            return jnp.where(dd > 0, mm2 / jnp.where(dd > 0, dd, 1.0), 0.0)

        s2_d = var(tot_n, tot_m2)
        n_tot = jnp.maximum(tot_n, 1.0)
        vr = s2_d - (pn / n_tot) * var(pn, pm2) - (rn / n_tot) * var(rn, rm2)

        # ---- neighbouring occupied prototypes via value propagation ------
        # the "has an occupied bin" flags ride as f32 0/1 masks: Mosaic
        # cannot lane-shift (concatenate) i1 vectors
        proto = jnp.where(occ, sum_x / jnp.where(occ, n, 1.0), 0.0)
        occf = occ.astype(jnp.float32)
        lval, lhas = proto, occf         # last occupied value at-or-before i
        rval, rhas = proto, occf         # first occupied value at-or-after i
        d = 1
        while d < Cp:
            slv = _shift_right(lval, d, 0.0)
            slh = _shift_right(lhas, d, 0.0)
            lval = jnp.where(lhas > 0, lval, slv)
            lhas = jnp.maximum(lhas, slh)
            srv = _shift_left(rval, d, 0.0)
            srh = _shift_left(rhas, d, 0.0)
            rval = jnp.where(rhas > 0, rval, srv)
            rhas = jnp.maximum(rhas, srh)
            d *= 2
        nval = _shift_left(rval, 1, 0.0)  # first occupied STRICTLY after i
        nhas = _shift_left(rhas, 1, 0.0)

        ok = jnp.logical_and(jnp.logical_and(lhas > 0, nhas > 0), att)
        cand = 0.5 * (lval + nval)

        out_ref[0, 0] = jnp.where(ok, vr, -jnp.inf)
        out_ref[0, 1] = cand
        for r in range(2, FOREST_ROWS):
            out_ref[0, r] = zero


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def qo_query_batched_pallas(tab: jax.Array, *, tile_m: int = 128,
                            interpret: bool = False) -> jax.Array:
    """tab: (F, 8, Mp, Cp) with attempt flags in row 6 -> scores/thresholds."""
    F, rows, Mp, Cp = tab.shape
    assert rows == FOREST_ROWS and Mp % tile_m == 0
    grid = (F, Mp // tile_m)
    return pl.pallas_call(
        _qo_query_batched_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, FOREST_ROWS, tile_m, Cp),
                               lambda f, j: (f, 0, j, 0))],
        out_specs=pl.BlockSpec((1, FOREST_ROWS, tile_m, Cp),
                               lambda f, j: (f, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((F, FOREST_ROWS, Mp, Cp), jnp.float32),
        interpret=interpret,
    )(tab)

"""Public jit'd wrappers over the Pallas QO kernels.

Single-table ops (``qo_update`` / ``qo_best_split``) and the forest-scale
ops the Hoeffding tree hot path dispatches through (``forest_update`` /
``forest_best_splits``).  Every op takes a ``backend``:

* ``"pallas"``    — the compiled Mosaic kernel on TPU; anywhere else it
                    raises instead of silently interpreting,
* ``"interpret"`` — the same kernel body under Pallas' CPU interpreter
                    (correctness validation against :mod:`repro.kernels.ref`),
* ``"jnp"``       — a fused pure-jnp lowering of the same math (XLA-fused
                    scatters + cumulative scans), the fast path off-TPU.

``backend=None`` resolves to ``"pallas"`` on TPU and ``"jnp"`` elsewhere.
The jnp lowering of the query uses prefix *sums* of (n, n*mean,
m2 + n*mean^2) rather than log-depth Chan merges — one fused ``cumsum``
instead of hundreds of tiny ops; the kernels and the
:mod:`repro.core.qo` oracle keep the fully robust merge (DESIGN.md §2.4).

Dispatch discipline (DESIGN.md §2.5, §8): both forest ops auto-detect
whether they are being traced.  Called with *concrete* arrays they
dispatch through cached jits keyed on (shape bucket, backend) — batch
sizes round up to bucket ladders and the split query compacts to the
smallest power-of-two bucket holding the K attempting tables, so the
compile cache stays bounded and two same-bucket calls never retrace.
Called under an enclosing trace (e.g. inside ``jax.jit(hoeffding.update)``)
they inline, so the caller's jit still fuses the whole stage; the query
then selects its K bucket at *runtime* with ``lax.switch``.

Every concrete dispatch flows through ONE shared helper pair —
:func:`_dispatch` (the cached-jit factory: one lru keyed on
(impl, statics), one donation policy, one clear hook) and
:func:`dispatch_rows` (the pad-to-bucket → cached jit → slice prologue)
— so the query, route, predict, update and merge families cannot drift
apart in bucketing or caching discipline.  The per-family ``_jit_*``
handles remain as thin keyed shims over :func:`_dispatch` (they are the
``_cache_size()`` / ``cache_info()`` regression hooks).

Tile/grid constants are *schedule* knobs, never semantics: pad rows
vanish (w = 0 / leaf = -1 / attempt = False) and extra route plies
self-loop, so every dispatch-shaping choice (ladders, ply rounding,
query buckets, table-axis tiles) is bit-identical on every backend.
The one exception is the batch-STREAMING tile width on the kernel path
(forest_update ``tile_b``, qo_update ``tile``): it sets the granularity
of a sequential Chan merge, so a different width reorders f32
accumulation — same math, different bits — and the tuner therefore
pins those knobs at their defaults off the jnp backend
(``repro.perf.tune.KERNEL_STREAM_KNOBS``).  Defaults were eyeballed on
one container, so
:mod:`repro.perf.tune` can override them per (family, backend, shape
class) through :func:`set_tuning` — a caller-supplied explicit value
always wins, and with no tuning installed the defaults (and therefore
the jit cache keys) are exactly the historical constants.
"""
from __future__ import annotations

import bisect
import functools

import jax
import jax.numpy as jnp

from repro.core import qo as qo_lib
from repro.core import stats
from repro.kernels import ref as _ref
from repro.kernels.qo_update import qo_update_pallas
from repro.kernels.qo_query import qo_query_pallas
from repro.kernels.qo_update_leaves import (
    pack_forest, unpack_forest, qo_update_leaves_pallas, round_up)
from repro.kernels.qo_query_batched import qo_query_batched_pallas
from repro.kernels.qo_route import (
    fold_route_tables, pack_route_attrs, qo_route_pallas)
from repro.kernels.qo_merge import (
    pack_merge_planes, unpack_merge_planes, qo_merge_pallas)
from repro.core import sketch as sketch_lib
from repro.kernels.sketch_compact import (
    pack_compact_planes, unpack_compact_planes, sketch_compact_pallas)

__all__ = [
    "qo_update", "qo_best_split", "default_interpret", "resolve_backend",
    "forest_bin_ids", "forest_update", "forest_best_splits", "forest_merge",
    "sketch_update", "sketch_merge", "sketch_to_bins",
    "route", "forest_route", "depth_bucket",
    "query_buckets", "clear_jit_caches", "QUERY_MIN_BUCKET",
    "set_tuning", "get_tuning", "tuned", "DEFAULT_PARAMS",
]


def default_interpret() -> bool:
    """True off-TPU: single-table kernels run under the Pallas interpreter
    unless the caller forces compiled mode."""
    return jax.default_backend() != "tpu"


def resolve_backend(backend: str | None) -> str:
    """None/'auto' -> compiled kernels on TPU, fused jnp elsewhere."""
    if backend in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert backend in ("pallas", "interpret", "jnp"), backend
    return backend


def _kernel_interpret(backend: str) -> bool:
    """Interpreter-mode flag for a kernel-path backend: ``"interpret"``
    always interprets; ``"pallas"`` compiles natively on TPU and raises
    anywhere else — a run that asked for the compiled kernels must never
    quietly measure the interpreter instead."""
    if backend == "interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"backend='pallas' compiles the Pallas kernels for a TPU, but "
            f"JAX's default backend is {jax.default_backend()!r}; use "
            f"backend='interpret' (the same kernel bodies under the Pallas "
            f"interpreter) or 'jnp' off-TPU")
    return False


# --------------------------------------------------------------------------
# tuned dispatch parameters (DESIGN.md §8; populated by repro.perf.tune)
# --------------------------------------------------------------------------

#: The historical hard-coded schedule constants, per dispatch family.
#: These are the fallbacks when no tuning entry matches — an untuned
#: machine dispatches (and caches) exactly as before the perf layer
#: existed — and the per-family search space in repro.perf.tune must
#: stay a superset of them.
DEFAULT_PARAMS = {
    "qo_update": {"tile": 1024},
    "forest_update": {"tile_b": 256, "tile_m": 128, "batch_ladder": "pow2"},
    "forest_query": {"tile_m": 128, "min_bucket": 8},
    "forest_route": {"tile_b": 256, "batch_ladder": "pow2", "ply_round": 2},
    "forest_merge": {"tile_r": 256},
    "sketch_update": {"tile_r": 256, "batch_ladder": "pow2"},
    "sketch_merge": {"tile_r": 256},
}

# (family, backend, shape_class) -> {param: value} overrides.  Kept
# deliberately dumb (a dict the perf layer swaps in) so kernels never
# import the tuner: repro.perf.tune owns measurement, persistence and
# device-kind filtering and calls set_tuning with the survivors.
_TUNING: dict = {}


def set_tuning(table: dict) -> None:
    """Install tuned dispatch parameters: ``{(family, backend,
    shape_class): {param: value}}``.  Replaces the whole table.  Entries
    apply only where the caller left a parameter unspecified; unknown
    params are ignored by :func:`tuned`.  Changing the table does not
    drop already-compiled programs (old keys stay warm; call
    :func:`clear_jit_caches` to reclaim them)."""
    global _TUNING
    _TUNING = dict(table)


def get_tuning() -> dict:
    """The installed tuning table (read-only view for tests/tools)."""
    return dict(_TUNING)


def tuned(family: str, backend: str, shape_class: str, **overrides):
    """Resolve the dispatch parameters for one (family, backend, shape
    class): start from :data:`DEFAULT_PARAMS`, apply the installed
    tuning entry, then apply caller ``overrides`` whose value is not
    None (an explicit argument always beats the tuner).  Returns a fresh
    dict — pure lookup, no measurement, safe at trace time."""
    p = dict(DEFAULT_PARAMS[family])
    entry = _TUNING.get((family, backend, shape_class))
    if entry:
        p.update({k: v for k, v in entry.items() if k in p})
    p.update({k: v for k, v in overrides.items() if v is not None})
    return p


def _shape_class_tables(M: int, F: int, C: int) -> str:
    """Tuner key for the table-axis families (update/query/merge): the
    dense (M, F, C) geometry IS the workload; B rides the bucket ladder."""
    return f"M{M}xF{F}xC{C}"


def _shape_class_route(T: int, M: int, F: int) -> str:
    """Tuner key for the routing/predict families: folded node count and
    feature width set the sweep's working set; B rides the ladder and
    the ply count is a dispatch key, not a tuning key."""
    return f"T{T}xM{M}xF{F}"


# --------------------------------------------------------------------------
# the ONE cached-jit dispatch engine (all concrete entry points funnel here)
# --------------------------------------------------------------------------

def _is_traced(*trees) -> bool:
    """True when any leaf of the argument pytrees is a JAX tracer — i.e.
    the caller is already inside a jit/vmap/scan trace and the op must
    inline rather than dispatch through its own cached jit."""
    return any(isinstance(leaf, jax.core.Tracer)
               for t in trees for leaf in jax.tree.leaves(t))


@functools.lru_cache(maxsize=None)
def _dispatch_cached(impl, donate_x: bool, statics: tuple):
    """The single cached-jit factory behind every concrete dispatch
    family: one entry per (impl, donation policy, static params).  The
    inner jit cache is keyed on argument shapes, which the public
    wrappers bucket.  ``donate_x=True`` donates the batch argument
    (every row-dispatch impl names it ``X``) so XLA can reuse the
    request buffer for sweep temporaries; XLA:CPU cannot alias donated
    buffers (it would only warn per compile), so donation engages on
    TPU only and callers must hand an engine-owned buffer."""
    donate = ("X",) if donate_x and jax.default_backend() == "tpu" else ()
    return jax.jit(functools.partial(impl, **dict(statics)),
                   donate_argnames=donate)


def _dispatch(impl, *, donate_x: bool = False, **statics):
    """Resolve the cached jit for ``impl`` closed over ``statics``.
    Same (impl, statics) -> the same jit object, process-wide — the
    no-recompile invariant every ``_jit_*`` family shim inherits."""
    return _dispatch_cached(impl, donate_x, tuple(sorted(statics.items())))


def _ladder_bucket(n: int, lo: int, ladder: str) -> int:
    """Smallest bucket >= n on the chosen ladder (``lo`` a power of two).

    ``"pow2"``: {lo, 2lo, 4lo, ...} — O(log n) compiled programs, up to
    2x pad waste just past a boundary.  ``"pow2_half"``: half-steps
    {lo, 1.5lo, 2lo, 3lo, 4lo, ...} — still O(log n) programs (two per
    octave) but caps pad waste at 1.33x; the tuner picks it when the
    measured per-row cost outweighs the extra compiles for a shape
    class.  Both ladders are schedule-only: pad rows vanish on every
    backend."""
    b = lo
    while b < n:
        if ladder == "pow2_half":
            h = b + b // 2
            if n <= h:
                return h
        b *= 2
    return b


def _pow2_bucket(n: int, lo: int) -> int:
    """Smallest power-of-two multiple of ``lo`` holding ``n`` (``lo`` must
    itself be a power of two) — the shape-bucketing rule that bounds the
    cached-jit compile count to O(log n) entries."""
    return _ladder_bucket(n, lo, "pow2")


def pad_rows(X, lo: int = 128, ladder: str = "pow2"):
    """Pad request rows up to their ladder bucket — the dispatch
    prologue every concrete row-dispatch entry point shares.  Returns
    ``(padded X, original B, padded?)``; pad rows are zero and the
    callers slice ``[:B]`` back iff padding happened."""
    B, F = X.shape
    Bp = _ladder_bucket(max(B, lo), lo, ladder)
    if Bp == B:
        return X, B, False
    return jnp.concatenate([X, jnp.zeros((Bp - B, F), X.dtype)]), B, True


def pad_rows_pow2(X, lo: int = 128):
    """:func:`pad_rows` on the power-of-two ladder (the historical
    default; kept as the stable public name)."""
    return pad_rows(X, lo, "pow2")


def dispatch_rows(impl, tables, X, *, statics: dict, ladder: str = "pow2",
                  donate_x: bool = False):
    """Concrete row dispatch: pad ``X`` to its ladder bucket, run the
    cached jit for (impl, statics) over ``(*tables, X)``, slice the
    padded rows back off the LAST axis of the result.  The one body
    behind ``forest_route``/``route``/``predict_snapshot``/live forest
    predict — the three read-path dispatch layers this replaces each
    hand-rolled the same four lines."""
    X, B, padded = pad_rows(X, 128, ladder)
    if donate_x and not padded and jax.default_backend() == "tpu":
        X = jnp.copy(X)     # donate our copy, not the caller's buffer
    out = _dispatch(impl, donate_x=donate_x, **statics)(*tables, X)
    return out[..., :B] if padded else out


# --------------------------------------------------------------------------
# single-table ops
# --------------------------------------------------------------------------

def _pad_to(arr, mult, fill=0.0):
    n = arr.shape[0]
    rem = (-n) % mult
    if rem == 0:
        return arr
    return jnp.concatenate([arr, jnp.full((rem,), fill, arr.dtype)])


#: A batch whose pow-2 round-up fits this width is absorbed in ONE tile
#: pass no matter what tile was requested (see :func:`qo_update_tile`).
QO_SINGLE_PASS_MAX = 1024


def qo_update_tile(B: int, tile: int) -> int:
    """Resolve the streamed batch-tile width for a B-row update.

    The requested ``tile`` is a *streaming-granularity cap for big
    batches*, not a splitter for small ones: a batch whose pow-2
    round-up fits one maximal tile (:data:`QO_SINGLE_PASS_MAX`) is
    absorbed in a single pass of exactly that round-up (floored at the
    128-lane alignment), so for B <= 1024 EVERY tile request is
    bit-identical — pad rows carry w = 0 and vanish, and there is no
    partial-tile Chan merge whose f32 order could differ
    (tests/test_kernels.py pins B in {1, 127, 128, 129} across tile
    choices).  The old ``min(tile, round_up)`` clamp split B = 129 into
    two 128-passes under ``tile=128`` but one 256-pass under larger
    requests — same math, different bits.  Batches past the single-pass
    width stream at the requested tile, where granularity is a real
    VMEM/occupancy knob (and IS bit-sensitive, which is why the tuner
    never searches it on the kernel path — repro.perf.tune)."""
    up = max(128, 1 << (B - 1).bit_length())
    if up <= QO_SINGLE_PASS_MAX:
        return up
    return min(max(tile, 128), up)


def _qo_update_impl(table, x, y, w, *, tile: int, interpret: bool):
    dense, scal = _ref.pack_table(table)
    dense = qo_update_pallas(dense, scal, x, y, w, tile=tile,
                             interpret=interpret)
    return _ref.unpack_table(dense, scal)


def qo_update(table: qo_lib.QOTable, x, y, w=None, *, tile: int | None = None,
              interpret: bool | None = None) -> qo_lib.QOTable:
    """Kernel-backed equivalent of :func:`repro.core.qo.update`.

    table: dict QO table (capacity C); x/y: (B,) f32 observations;
    w: optional (B,) f32 sample weights (default 1, weight-0 rows vanish);
    tile: batch tile streamed through VMEM per grid step (None: the
    tuned value for this capacity class, default 1024, clamped by
    :func:`qo_update_tile`).  Returns the merged table (same shapes).
    """
    interpret = default_interpret() if interpret is None else interpret
    x = jnp.asarray(x, jnp.float32).reshape(-1)
    y = jnp.asarray(y, jnp.float32).reshape(-1)
    w = jnp.ones_like(x) if w is None else jnp.asarray(w, jnp.float32).reshape(-1)
    cap = int(table["sum_x"].shape[0])
    tile = tuned("qo_update", "pallas", f"C{cap}", tile=tile)["tile"]
    tile = qo_update_tile(int(x.shape[0]), tile)
    xp, yp, wp = _pad_to(x, tile), _pad_to(y, tile), _pad_to(w, tile)
    if _is_traced(table, xp, yp, wp):
        return _qo_update_impl(table, xp, yp, wp, tile=tile,
                               interpret=interpret)
    return _dispatch(_qo_update_impl, tile=tile, interpret=interpret)(
        table, xp, yp, wp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qo_best_split(table: qo_lib.QOTable, *,
                  interpret: bool | None = None) -> qo_lib.SplitResult:
    """Kernel-backed equivalent of :func:`repro.core.qo.best_split`.

    Returns a scalar :class:`repro.core.qo.SplitResult` (threshold, VR
    merit, validity) evaluated for all C boundaries in one pass.
    """
    interpret = default_interpret() if interpret is None else interpret
    dense, _ = _ref.pack_table(table)
    out = qo_query_pallas(dense, interpret=interpret)
    score, cand = out[0], out[1]
    best = jnp.argmax(score)
    valid = jnp.isfinite(score[best])
    return qo_lib.SplitResult(
        threshold=cand[best],
        merit=jnp.where(valid, score[best], 0.0),
        valid=valid,
    )


# --------------------------------------------------------------------------
# forest-scale ops: every (leaf, feature) table of a Hoeffding tree at once
# --------------------------------------------------------------------------

def forest_bin_ids(ao_radius, ao_origin, leaf, X, n_bins: int) -> jax.Array:
    """Quantize each routed row into its leaf's per-feature tables.

    ao_radius/ao_origin: (M, F) per-(leaf, feature) quantization; leaf:
    (B,) i32 routed leaf ids; X: (B, F) f32.  Returns (B, F) i32 bin ids
    clipped into [0, n_bins).
    """
    r = ao_radius[leaf]                     # (B, F)
    o = ao_origin[leaf]
    h = jnp.floor((X - o) / r).astype(jnp.int32) + n_bins // 2
    return jnp.clip(h, 0, n_bins - 1)


def _forest_update_jnp(ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y, w):
    """Fused-jnp lowering: ONE stacked segment-reduction + two-pass M2."""
    M, F, C = ao_sum_x.shape
    bins = forest_bin_ids(ao_radius, ao_origin, leaf, X, C)
    seg = ((leaf[:, None] * F + jnp.arange(F)[None, :]) * C + bins).reshape(-1)
    wr = jnp.repeat(w, F)
    yr = jnp.repeat(y, F)
    xf = X.reshape(-1)
    pay = jnp.stack([wr, wr * yr, wr * xf], 1)              # (B*F, 3)
    acc = jax.ops.segment_sum(pay, seg, M * F * C)
    nb, syb, sxb = acc[:, 0], acc[:, 1], acc[:, 2]
    meanb = jnp.where(nb > 0, syb / jnp.where(nb > 0, nb, 1.0), 0.0)
    # second pass: residuals against the tile bin mean (exact within tile)
    m2b = jax.ops.segment_sum(wr * (yr - meanb[seg]) ** 2, seg, M * F * C)
    tile = {"n": nb.reshape(M, F, C), "mean": meanb.reshape(M, F, C),
            "m2": m2b.reshape(M, F, C)}
    # Chan merge (Eqs. 4-5) of the tile into the running tables
    return stats.merge(ao_y, tile), ao_sum_x + sxb.reshape(M, F, C)


def _pad_batch(leaf, X, y, w, tile_b):
    """Pad the batch axis (the last of ``leaf``/``w``, the first of X/y)
    to a multiple of ``tile_b`` with leaf = -1, w = 0 rows."""
    B, F = X.shape
    Bp = round_up(max(B, tile_b), tile_b)
    pad = Bp - B
    if pad:
        fill = lambda a, v: jnp.concatenate(
            [a, jnp.full(a.shape[:-1] + (pad,), v, a.dtype)], -1)
        leaf = fill(leaf, -1)
        X = jnp.concatenate([X, jnp.zeros((pad, F), X.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
        w = fill(w, 0)
    return leaf, X, y, w


def _forest_update_impl(ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y, w,
                        *, backend: str, tile_b: int, tile_m: int):
    """Backend dispatch body of :func:`forest_update` (inputs normalized
    to the group form: tables (G, M, F, C), leaf/w (G, B))."""
    G, M, F, C = ao_sum_x.shape
    fold = lambda a: a.reshape((G * M,) + a.shape[2:])
    unfold = lambda a: a.reshape((G, M) + a.shape[1:])
    if backend == "jnp":
        # the fused lowering folds the groups into one table axis; a pad
        # row (leaf = -1) must not fold into the previous group's tables
        gl = jnp.where(leaf >= 0, jnp.arange(G, dtype=leaf.dtype)[:, None] * M
                       + leaf, -1).reshape(-1)
        ao_y, ao_sum_x = _forest_update_jnp(
            jax.tree.map(fold, ao_y), fold(ao_sum_x), fold(ao_radius),
            fold(ao_origin), gl, jnp.tile(X, (G, 1)), jnp.tile(y, G),
            w.reshape(-1))
        return jax.tree.map(unfold, ao_y), unfold(ao_sum_x)

    tile_m = min(tile_m, round_up(M, 8))
    tile_b = min(tile_b, round_up(X.shape[0], 128))
    leaf, X, y, w = _pad_batch(leaf, X, y, w, tile_b)
    dense = pack_forest(jax.tree.map(fold, ao_y), fold(ao_sum_x),
                        fold(ao_radius), fold(ao_origin), tile_m=tile_m,
                        groups=G)
    dense = qo_update_leaves_pallas(
        dense, leaf[:, None, :], X.T[:, None, :], y[None, :], w[:, None, :],
        n_bins=C, tile_b=tile_b, tile_m=tile_m, interpret=_kernel_interpret(backend))
    ao_y, ao_sum_x = unpack_forest(dense, M, C, groups=G)
    return jax.tree.map(unfold, ao_y), unfold(ao_sum_x)


def _jit_forest_update(backend: str, tile_b: int, tile_m: int):
    """Keyed handle for the absorb op's cached jit (the ``_cache_size``
    regression hook); delegates to the shared :func:`_dispatch`."""
    return _dispatch(_forest_update_impl, backend=backend,
                     tile_b=tile_b, tile_m=tile_m)


def forest_update(ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y, w=None, *,
                  backend: str | None = None, tile_b: int | None = None,
                  tile_m: int | None = None):
    """Absorb a routed batch into every (leaf, feature) QO table.

    ao_y: Stats dict of (M, F, C); ao_sum_x: (M, F, C); ao_radius/ao_origin:
    (M, F); leaf: (B,) int32 routed leaf ids; X: (B, F); y: (B,);
    w: optional (B,) f32 sample weights (default 1) — every accumulated
    statistic carries w, so weight-0 rows vanish and integer weight k
    equals k repeated unit rows (the online-bagging contract,
    property-tested in tests/test_weighted.py).
    Returns the merged (ao_y, ao_sum_x).

    Group form: tables with a leading group axis — (G, M, F, C) and
    (G, M, F) — take leaf: (G, B) group-local ids and w: (G, B), with X
    and y shared by every group (a forest's members, each routing the
    same batch; DESIGN.md §5.1).  Group g's rows land only in group g's
    tables, and the kernel's grid walks group by group, so no work is
    spent pairing one group's rows with another's tables.  Returns
    (G, M, F, C) tables.  The group count is read from the tables' rank;
    one group is exactly the ungrouped call.

    ``tile_b``/``tile_m`` (None: tuned, defaults 256/128) are schedule
    knobs; pad rows carry leaf = -1, w = 0 and vanish on every backend.
    ``tile_m`` (table-axis grid) and the batch ladder are bit-identical
    under any value everywhere; ``tile_b`` is bit-identical on jnp (the
    fused lowering ignores it) but sets the streaming Chan-merge order
    on the kernel path, where the tuner pins it.  Called with concrete arrays
    this dispatches through a cached jit with the batch padded to its
    ladder bucket, so ragged streaming batches reuse a bounded set of
    compiled programs.  Under an enclosing trace it inlines, so the
    caller's jit fuses the whole absorb stage.
    """
    backend = resolve_backend(backend)
    grouped = ao_sum_x.ndim == 4
    if not grouped:
        ao_y, ao_sum_x, ao_radius, ao_origin = jax.tree.map(
            lambda a: a[None], (ao_y, ao_sum_x, ao_radius, ao_origin))
    G, M, F, C = ao_sum_x.shape
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32).reshape(-1)
    leaf = jnp.asarray(leaf, jnp.int32).reshape(G, -1)
    w = jnp.ones(leaf.shape, jnp.float32) if w is None else \
        jnp.broadcast_to(jnp.asarray(w, jnp.float32), leaf.shape)
    p = tuned("forest_update", backend, _shape_class_tables(G * M, F, C),
              tile_b=tile_b, tile_m=tile_m)
    if _is_traced(ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y, w):
        out = _forest_update_impl(ao_y, ao_sum_x, ao_radius, ao_origin,
                                  leaf, X, y, w, backend=backend,
                                  tile_b=p["tile_b"], tile_m=p["tile_m"])
    else:
        leaf, X, y, w = _pad_batch(
            leaf, X, y, w, _ladder_bucket(X.shape[0], 128, p["batch_ladder"]))
        out = _jit_forest_update(backend, p["tile_b"], p["tile_m"])(
            ao_y, ao_sum_x, ao_radius, ao_origin, leaf, X, y, w)
    return out if grouped else jax.tree.map(lambda a: a[0], out)


def _forest_merge_impl(a_y, a_sum_x, b_y, b_sum_x, *, backend: str,
                       tile_r: int):
    """Backend dispatch body of :func:`forest_merge` (inputs normalized)."""
    if backend == "jnp":
        return stats.merge(a_y, b_y), a_sum_x + b_sum_x
    shape = a_sum_x.shape
    tile_r = min(tile_r, round_up(shape[0] * shape[1], 8))
    dense = qo_merge_pallas(
        pack_merge_planes(a_y, a_sum_x, tile_r=tile_r),
        pack_merge_planes(b_y, b_sum_x, tile_r=tile_r),
        tile_r=tile_r, interpret=_kernel_interpret(backend))
    return unpack_merge_planes(dense, shape)


@functools.lru_cache(maxsize=None)
def _jit_forest_merge(backend: str, tile_r: int):
    """Keyed handle for the table merge's cached jit (``cache_info()``
    is the no-fragmentation hook); delegates to :func:`_dispatch`."""
    return _dispatch(_forest_merge_impl, backend=backend, tile_r=tile_r)


def forest_merge(a_y, a_sum_x, b_y, b_sum_x, *, backend: str | None = None,
                 tile_r: int | None = None):
    """Chan-merge two same-shape QO table sets (DESIGN.md §4.1).

    a_y/b_y: Stats dicts of (N, F, C); a_sum_x/b_sum_x: (N, F, C) — N is
    any table-axis length (a tree's M, a forest's folded T·M, or a
    gathered shard stack reshaped in).  Returns the merged
    ``(ao_y, ao_sum_x)``: per-bin (n, mean, M2) through the Chan operator
    (Eqs. 4-5, empty-operand safe) and ``sum_x`` summed.  Associative +
    commutative — the write-side collective that lets D shard-local
    deltas reduce to exactly the single-stream tables; radius/origin do
    not ride through this op (shards must share the base quantization
    grid for the merge to be meaningful — the §4.1 trainer replicates
    them).

    Called with concrete arrays this dispatches through a cached jit
    (table shapes are fixed for a given forest, so the cache holds one
    program per backend); under an enclosing trace it inlines, so a
    jitted sync step fuses the whole reduction.
    """
    backend = resolve_backend(backend)
    N, F, C = a_sum_x.shape
    tile_r = tuned("forest_merge", backend, _shape_class_tables(N, F, C),
                   tile_r=tile_r)["tile_r"]
    if _is_traced(a_y, a_sum_x, b_y, b_sum_x):
        return _forest_merge_impl(a_y, a_sum_x, b_y, b_sum_x,
                                  backend=backend, tile_r=tile_r)
    return _jit_forest_merge(backend, tile_r)(a_y, a_sum_x, b_y, b_sum_x)


def _forest_query_jnp(ao_y, ao_sum_x, attempt):
    """Fused-jnp lowering of the batched query: one cumsum over stacked
    prefix payloads + cummax/cummin neighbour scans (DESIGN.md §2.4)."""
    M, F, C = ao_sum_x.shape
    n = ao_y["n"].reshape(M * F, C)
    mean = ao_y["mean"].reshape(M * F, C)
    m2 = ao_y["m2"].reshape(M * F, C)
    sum_x = ao_sum_x.reshape(M * F, C)
    occ = n > 0

    # VR is shift-invariant: center bin means on each table's grand mean so
    # SQ - SY^2/N never cancels against a large target offset (the same
    # robustness the Chan-merge paths get structurally)
    n_tab = n.sum(-1, keepdims=True)
    grand = (n * mean).sum(-1, keepdims=True) / jnp.maximum(n_tab, 1.0)
    mu = mean - grand
    sy = n * mu
    sq = m2 + sy * mu
    pref = jnp.cumsum(jnp.stack([n, sy, sq], 0), axis=-1)    # (3, M*F, C)
    Nl, SYl, SQl = pref[0], pref[1], pref[2]
    Nt, SYt, SQt = Nl[:, -1:], SYl[:, -1:], SQl[:, -1:]
    Nr, SYr, SQr = Nt - Nl, SYt - SYl, SQt - SQl

    def var(NN, SY, SQ):
        d = NN - 1.0
        m2_ = jnp.maximum(SQ - SY * SY / jnp.where(NN > 0, NN, 1.0), 0.0)
        return jnp.where(d > 0, m2_ / jnp.where(d > 0, d, 1.0), 0.0)

    s2d = var(Nt, SYt, SQt)
    ntot = jnp.maximum(Nt, 1.0)
    vr = s2d - (Nl / ntot) * var(Nl, SYl, SQl) - (Nr / ntot) * var(Nr, SYr, SQr)

    idx = jnp.arange(C)
    last = jax.lax.cummax(jnp.where(occ, idx, -1), axis=1)
    first_after = jax.lax.cummin(jnp.where(occ, idx, C), axis=1, reverse=True)
    nxt = jnp.concatenate([first_after[:, 1:], jnp.full((M * F, 1), C)], 1)
    ok = (last >= 0) & (nxt < C) & jnp.repeat(attempt, F)[:, None]
    proto = jnp.where(occ, sum_x / jnp.where(occ, n, 1.0), 0.0)
    p_l = jnp.take_along_axis(proto, jnp.maximum(last, 0), 1)
    p_r = jnp.take_along_axis(proto, jnp.minimum(nxt, C - 1), 1)
    cand = 0.5 * (p_l + p_r)
    score = jnp.where(ok, vr, -jnp.inf)
    return score, cand


QUERY_MIN_BUCKET = 8


def query_buckets(M: int, min_bucket: int = QUERY_MIN_BUCKET):
    """Static K_pad buckets for a capacity-M table axis: powers of two from
    ``min_bucket`` up, capped by a final full-scan bucket of M itself (so
    a near-full attempt set pays no gather/scatter overhead)."""
    sizes = []
    b = min_bucket
    while b < M:
        sizes.append(b)
        b *= 2
    return tuple(sizes) + (M,)


def _query_full(ao_y, ao_sum_x, ao_radius, ao_origin, attempt, *,
                backend: str, tile_m: int):
    """Uncompacted query over all M tables -> (merit, thr), both (M, F)."""
    M, F, C = ao_sum_x.shape
    if backend == "jnp":
        score, cand = _forest_query_jnp(ao_y, ao_sum_x, attempt)
    else:
        tile_m = min(tile_m, round_up(M, 8))
        dense = pack_forest(ao_y, ao_sum_x, ao_radius, ao_origin, attempt,
                            tile_m=tile_m)
        out = qo_query_batched_pallas(dense, tile_m=tile_m,
                                      interpret=_kernel_interpret(backend))
        score = jnp.transpose(out[:, 0, :M, :], (1, 0, 2)).reshape(M * F, -1)
        cand = jnp.transpose(out[:, 1, :M, :], (1, 0, 2)).reshape(M * F, -1)
    best = jnp.argmax(score, -1)
    merit = jnp.max(score, -1).reshape(M, F)
    thr = jnp.take_along_axis(cand, best[:, None], 1)[:, 0].reshape(M, F)
    return merit, thr


def _query_compact(ao_y, ao_sum_x, ao_radius, ao_origin, attempt, *,
                   kpad: int, backend: str, tile_m: int):
    """Compact-gather -> query -> scatter-back for a static K_pad bucket.

    Gathers the (at most kpad) attempting tables into a dense
    (kpad, F, C) buffer, runs the ordinary query over it — pad rows carry
    attempt=False, so masked math on jnp and ``pl.when``-skipped tiles on
    the kernel path — and scatters (merit, thr) back to (M, F) with -inf
    fill.  Per-table math is row-independent on every backend, so the
    attempting rows' results are bit-identical to the full scan's.
    """
    M, F, _ = ao_sum_x.shape
    idx = jnp.nonzero(attempt, size=kpad, fill_value=M)[0]       # (kpad,)
    safe = jnp.minimum(idx, M - 1)
    sub = lambda a: a[safe]
    merit_k, thr_k = _query_full(
        jax.tree.map(sub, ao_y), sub(ao_sum_x), sub(ao_radius),
        sub(ao_origin), idx < M, backend=backend, tile_m=tile_m)
    merit = jnp.full((M, F), -jnp.inf, jnp.float32).at[idx].set(
        merit_k, mode="drop")
    thr = jnp.zeros((M, F), jnp.float32).at[idx].set(thr_k, mode="drop")
    return merit, thr


@functools.lru_cache(maxsize=None)
def _jit_forest_query(backend: str, tile_m: int, kpad: int | None):
    """Keyed handle for one query bucket's cached jit (kpad=None: the
    full scan; ``cache_info()``/``_cache_size()`` are the regression
    hooks); delegates to the shared :func:`_dispatch`."""
    if kpad is None:
        return _dispatch(_query_full, backend=backend, tile_m=tile_m)
    return _dispatch(_query_compact, backend=backend, tile_m=tile_m,
                     kpad=kpad)


# --------------------------------------------------------------------------
# batched routing: the read-path primitive (DESIGN.md §2.6)
# --------------------------------------------------------------------------

def depth_bucket(depth: int, round_to: int = 2) -> int:
    """Ply bucket for the routing dispatch: extra plies are self-loop
    no-ops (leaves re-select themselves), so rounding the ply count up is
    free of correctness cost; rounding to the next multiple of
    ``round_to`` bounds the compile cache to max_depth/round_to programs
    per backend while wasting at most round_to - 1 plies (a power-of-two
    ladder would route a depth-9 tree with 16 plies — 7 wasted memory
    passes on the serving hot loop).  ``round_to`` is the tuned
    ``ply_round`` knob: 1 = exact plies (most programs, zero waste),
    default 2 = even plies (the historical choice)."""
    if round_to <= 1:
        return max(0, depth)
    return max(0, -(-depth // round_to) * round_to)


def _forest_route_jnp(feature, threshold, child, is_leaf, X, *, plies: int):
    """Fused-jnp lowering: a fully vectorized (T, B) transition sweep.

    Three takes per ply replace the oracle's six (feature, threshold,
    left, right, is_leaf, x): children are allocated in pairs (right =
    left + 1, see ``hoeffding._split_decision``), so feature and the
    right-child id pack into ONE int32 payload ``fc = right * Fp + f``
    (Fp = features rounded to a power of two — id extraction is two bit
    ops, and T*M*Fp stays far below 2^31 for any real forest), the
    transition becomes the branch-free

        node' = (fc >> log2(Fp)) - (x[f] <= threshold)

    and leaves self-loop with ``fc = self * Fp``, ``threshold = NaN``
    (``x <= NaN`` is False for EVERY x — including -inf, which a -inf
    sentinel would get wrong since ``-inf <= -inf`` is True — and for
    NaN itself, matching the oracle's NaN-goes-right convention
    bit-for-bit).  The X take flattens to one 1D gather
    (``row * F + f``), and the ply loop is unrolled (``plies`` is static
    and small) so XLA fuses the sweep with no ``fori_loop`` re-entry.
    """
    T, M = feature.shape
    B, F = X.shape
    N = T * M
    Fp = max(2, 1 << (F - 1).bit_length())
    shift = Fp.bit_length() - 1
    featg, thr, left, right = fold_route_tables(feature, threshold, child,
                                                is_leaf)
    self_loop = left == jnp.arange(N, dtype=jnp.int32)            # leaves
    fc = jnp.where(self_loop, left * Fp, right * Fp + featg)
    thr = jnp.where(self_loop, jnp.nan, thr)
    xf = X.reshape(-1)
    cols = jnp.tile(jnp.arange(B, dtype=jnp.int32) * F, T)        # (T*B,)
    offs = (jnp.arange(T, dtype=jnp.int32) * M)[:, None]          # (T, 1)
    node = jnp.broadcast_to(offs, (T, B)).reshape(-1)             # roots
    for _ in range(plies):
        fcv = fc[node]
        xv = xf[cols + (fcv & (Fp - 1))]
        node = (fcv >> shift) - (xv <= thr[node])
    return node.reshape(T, B) - offs


def _forest_route_impl(feature, threshold, child, is_leaf, X, *,
                       plies: int, backend: str, tile_b: int):
    """Backend dispatch body of :func:`forest_route` (inputs normalized)."""
    if backend == "jnp":
        return _forest_route_jnp(feature, threshold, child, is_leaf, X,
                                 plies=plies)
    B, F = X.shape
    attrs = pack_route_attrs(feature, threshold, child, is_leaf)
    tile_b = min(tile_b, round_up(B, 128))
    Bp, Fp = round_up(B, tile_b), round_up(F, 128)
    Xp = jnp.zeros((Bp, Fp), jnp.float32).at[:B, :F].set(X)
    out = qo_route_pallas(Xp, attrs, plies=plies, tile_b=tile_b,
                          interpret=_kernel_interpret(backend))
    return out[:, 0, :B]


def _route_single_impl(feature, threshold, child, is_leaf, X, *,
                       plies: int, backend: str, tile_b: int):
    """Single-tree twin of :func:`_forest_route_impl`: the (M,) ->
    (T=1, M) axis expansion happens inside the trace (free), not as
    per-call eager reshapes on the serving hot path."""
    return _forest_route_impl(
        feature[None], threshold[None], child[None], is_leaf[None], X,
        plies=plies, backend=backend, tile_b=tile_b)[0]


@functools.lru_cache(maxsize=None)
def _jit_route(backend: str, tile_b: int, plies: int):
    """Keyed handle for one routing ply bucket's cached jit; delegates
    to the shared :func:`_dispatch`."""
    return _dispatch(_forest_route_impl, backend=backend, tile_b=tile_b,
                     plies=plies)


@functools.lru_cache(maxsize=None)
def _jit_route_single(backend: str, tile_b: int, plies: int):
    """Single-tree twin of :func:`_jit_route` (same shared factory)."""
    return _dispatch(_route_single_impl, backend=backend, tile_b=tile_b,
                     plies=plies)


def _route_params(backend: str, T: int, M: int, F: int,
                  tile_b: int | None):
    """Tuned routing schedule for one folded (T·M, F) geometry."""
    return tuned("forest_route", backend, _shape_class_route(T, M, F),
                 tile_b=tile_b)


def forest_route(feature, threshold, child, is_leaf, X, *,
                 depth: int, backend: str | None = None,
                 tile_b: int | None = None) -> jax.Array:
    """Route a batch through T trees at once — (T, B) i32 leaf ids.

    feature/threshold/is_leaf: (T, M); child: (T, M, 2) with -1 at
    leaves; X: (B, F) f32, shared by every tree; ``depth``: static upper
    bound on any leaf's depth (transition steps past a leaf self-loop, so
    any bound >= the realized depth returns bit-identical ids — callers
    with concrete states pass the *realized* depth, e.g.
    :func:`repro.core.serve.predict_snapshot`).

    Called with concrete arrays this dispatches through cached jits keyed
    on (backend, ply bucket) with the batch padded to its ladder bucket
    (pad rows route from the root and are sliced off), so serving never
    recompiles per request size.  Under an enclosing trace it inlines
    with ``plies = depth`` exactly, so a jitted training step fuses the
    whole sweep.  ``tile_b`` (None: tuned, default 256) and the tuned
    ``ply_round``/``batch_ladder`` knobs are schedule-only.
    """
    backend = resolve_backend(backend)
    feature = jnp.asarray(feature, jnp.int32)
    threshold = jnp.asarray(threshold, jnp.float32)
    child = jnp.asarray(child, jnp.int32)
    is_leaf = jnp.asarray(is_leaf, jnp.bool_)
    X = jnp.asarray(X, jnp.float32)
    T, M = feature.shape
    p = _route_params(backend, T, M, X.shape[1], tile_b)
    if _is_traced(feature, threshold, child, is_leaf, X):
        return _forest_route_impl(feature, threshold, child, is_leaf, X,
                                  plies=depth, backend=backend,
                                  tile_b=p["tile_b"])
    return dispatch_rows(
        _forest_route_impl, (feature, threshold, child, is_leaf), X,
        statics=dict(backend=backend, tile_b=p["tile_b"],
                     plies=depth_bucket(depth, p["ply_round"])),
        ladder=p["batch_ladder"])


def route(feature, threshold, child, is_leaf, X, *, depth: int,
          backend: str | None = None,
          tile_b: int | None = None) -> jax.Array:
    """Single-tree batched routing — (B,) i32 leaf ids.

    The T = 1 view of :func:`forest_route` (same bucketing, same folded
    sweep): feature/threshold/is_leaf: (M,); child: (M, 2); X: (B, F).
    The concrete dispatch keeps the tree-axis expansion inside its
    cached jit, so the serving hot path pays exactly one dispatch.
    """
    backend = resolve_backend(backend)
    feature = jnp.asarray(feature, jnp.int32)
    threshold = jnp.asarray(threshold, jnp.float32)
    child = jnp.asarray(child, jnp.int32)
    is_leaf = jnp.asarray(is_leaf, jnp.bool_)
    X = jnp.asarray(X, jnp.float32)
    p = _route_params(backend, 1, feature.shape[0], X.shape[1], tile_b)
    if _is_traced(feature, threshold, child, is_leaf, X):
        return _route_single_impl(feature, threshold, child, is_leaf, X,
                                  plies=depth, backend=backend,
                                  tile_b=p["tile_b"])
    return dispatch_rows(
        _route_single_impl, (feature, threshold, child, is_leaf), X,
        statics=dict(backend=backend, tile_b=p["tile_b"],
                     plies=depth_bucket(depth, p["ply_round"])),
        ladder=p["batch_ladder"])


_JIT_CACHES = []


def register_jit_cache(fn):
    """Register an ``lru_cache``-wrapped jit factory with the shared
    clear hook (the serving layers add theirs on import, so one call
    resets every cached dispatch in the process)."""
    _JIT_CACHES.append(fn)
    return fn


register_jit_cache(_dispatch_cached)
register_jit_cache(_jit_forest_merge)
register_jit_cache(_jit_forest_query)
register_jit_cache(_jit_route)
register_jit_cache(_jit_route_single)


def clear_jit_caches() -> None:
    """Drop the cached-jit entry points (test hook: lets a fresh trace see
    monkeypatched query/update internals and resets ``_cache_size``)."""
    for fn in _JIT_CACHES:
        fn.cache_clear()


def forest_best_splits(ao_y, ao_sum_x, ao_radius, ao_origin, attempt, *,
                       backend: str | None = None, tile_m: int | None = None,
                       compact: bool = True,
                       min_bucket: int | None = None):
    """Best split candidate of every (leaf, feature) table.

    attempt: (M,) bool — tables of leaves below their grace period are
    masked out.  Returns (merit, threshold), both (M, F); merit is -inf
    where no valid boundary exists or the leaf is not attempting (thr is
    0 there on the compacted path and unspecified on the full scan — only
    positions with finite merit are meaningful).

    With ``compact=True`` (default) the evaluation cost scales with the
    number of *attempting* leaves K, not capacity M (DESIGN.md §2.5): the
    K attempting tables gather into the smallest power-of-two bucket
    >= K (``query_buckets``), the query runs over that dense buffer, and
    results scatter back.  Called with concrete arrays, K is known and
    the bucket dispatches in Python through a cached jit — K = 0 performs
    no query at all; under an enclosing trace the bucket is selected at
    runtime by ``lax.switch``, so a jitted streaming update still only
    pays for the branch it takes.  ``compact=False`` keeps the full
    M-table scan (the reference path; attempting rows of both paths are
    bit-identical).  ``tile_m``/``min_bucket`` (None: tuned, defaults
    128/8) are schedule knobs — every legal value is bit-identical.
    """
    backend = resolve_backend(backend)
    M, F, C = ao_sum_x.shape
    p = tuned("forest_query", backend, _shape_class_tables(M, F, C),
              tile_m=tile_m, min_bucket=min_bucket)
    tile_m, min_bucket = p["tile_m"], p["min_bucket"]
    buckets = query_buckets(M, min_bucket)
    traced = _is_traced(ao_y, ao_sum_x, ao_radius, ao_origin, attempt)
    if not compact or len(buckets) == 1:
        if traced:
            return _query_full(ao_y, ao_sum_x, ao_radius, ao_origin, attempt,
                               backend=backend, tile_m=tile_m)
        return _jit_forest_query(backend, tile_m, None)(
            ao_y, ao_sum_x, ao_radius, ao_origin, attempt)

    if traced:
        K = jnp.sum(attempt, dtype=jnp.int32)
        bidx = jnp.searchsorted(jnp.asarray(buckets, jnp.int32), K)
        branches = [
            functools.partial(_query_compact, kpad=b, backend=backend,
                              tile_m=tile_m) for b in buckets[:-1]
        ] + [functools.partial(_query_full, backend=backend, tile_m=tile_m)]
        return jax.lax.switch(bidx, branches, ao_y, ao_sum_x, ao_radius,
                              ao_origin, attempt)

    K = int(jnp.sum(attempt))
    if K == 0:  # nothing attempts: no query is dispatched at all
        return (jnp.full((M, F), -jnp.inf, jnp.float32),
                jnp.zeros((M, F), jnp.float32))
    kpad = buckets[bisect.bisect_left(buckets, K)]
    return _jit_forest_query(backend, tile_m, None if kpad == M else kpad)(
        ao_y, ao_sum_x, ao_radius, ao_origin, attempt)


# --------------------------------------------------------------------------
# sketch-observer ops (DESIGN.md §2.8): O(K·F) per-leaf state for massive F·C
# --------------------------------------------------------------------------

def _sketch_compact_backend(n, mean, m2, sum_x, k_out: int, *, backend: str,
                            tile_r: int):
    """Backend body of one compaction: the prototype sort + rank-bucket
    assignment is pure jnp on EVERY backend (sort networks don't pay
    their way in a hand kernel — same reasoning as the route fold), and
    only the grouped bucket reduction dispatches to the Pallas kernel or
    its fused ``segment_sum`` twin.  ``tile_r`` tiles the flattened
    table axis on the kernel path only — schedule-only there (rows are
    independent), and the jnp lowering ignores it, so unlike the
    streaming ``tile_b`` there is NO bit-sensitive stream knob for the
    tuner to pin in this family (a compaction reduces each bucket once;
    there is no sequential Chan merge across tiles)."""
    if backend == "jnp":
        return sketch_lib.compact_planes(n, mean, m2, sum_x, k_out)
    n, mean, m2, sum_x = sketch_lib.sort_planes(n, mean, m2, sum_x)
    bucket = sketch_lib._bucket_ids(n, k_out)
    lead = n.shape[:-1]
    R = 1
    for d in lead:
        R *= d
    tile_r = min(tile_r, round_up(R, 8))
    dense = sketch_compact_pallas(
        pack_compact_planes(n, mean, m2, sum_x, bucket, tile_r=tile_r),
        k_out=k_out, tile_r=tile_r, interpret=_kernel_interpret(backend))
    return unpack_compact_planes(dense, lead, k_out)


def _cat_planes(a_y, a_sum_x, b_y, b_sum_x):
    cat = lambda a, b: jnp.concatenate([a, b], axis=-1)
    return (cat(a_y["n"], b_y["n"]), cat(a_y["mean"], b_y["mean"]),
            cat(a_y["m2"], b_y["m2"]), cat(a_sum_x, b_sum_x))


def _sketch_merge_impl(a_y, a_sum_x, b_y, b_sum_x, *, backend: str,
                       tile_r: int):
    """Backend dispatch body of :func:`sketch_merge`: concatenate the 2K
    centroids and compact back to K."""
    k = a_sum_x.shape[-1]
    n, mean, m2, sum_x = _sketch_compact_backend(
        *_cat_planes(a_y, a_sum_x, b_y, b_sum_x), k,
        backend=backend, tile_r=tile_r)
    return {"n": n, "mean": mean, "m2": m2}, sum_x


@register_jit_cache
@functools.lru_cache(maxsize=None)
def _jit_sketch_merge(backend: str, tile_r: int):
    """Keyed handle for the sketch merge's cached jit (the
    ``_cache_size`` regression hook); delegates to :func:`_dispatch`."""
    return _dispatch(_sketch_merge_impl, backend=backend, tile_r=tile_r)


def sketch_merge(a_y, a_sum_x, b_y, b_sum_x, *, backend: str | None = None,
                 tile_r: int | None = None):
    """Merge two same-shape sketch-observer table sets (DESIGN.md §2.8).

    a_y/b_y: Stats dicts of (N, F, K); a_sum_x/b_sum_x: (N, F, K) — N is
    any table-axis length (a tree's M, a forest's folded T·M, or a
    gathered shard stack reshaped in), K the sketch capacity.  Returns
    the merged ``(ao_y, ao_sum_x)``: the 2K concatenated centroids
    rank-compacted back to K (exact bucket statistics; O(1/K) rank error
    in which centroids share a bucket).  Same mergeability contract as
    :func:`forest_merge` — commutative (bitwise for distinct
    prototypes), associative within the rank bound, empty-operand exact
    — so the §4.1 DP sync and checkpointing swap this in for the Chan
    table merge with no protocol change.  The positional signature
    matches :func:`forest_merge` on purpose; the elementwise Chan merge
    would be WRONG here (slot i of two sketches covers different rank
    ranges), which is why the observer backend must select the family.

    Called with concrete arrays this dispatches through a cached jit;
    under an enclosing trace it inlines.  ``tile_r`` (None: tuned,
    default 256) is schedule-only on every backend — no stream knob
    exists in this family (see :func:`_sketch_compact_backend`).
    """
    backend = resolve_backend(backend)
    N, F, K = a_sum_x.shape
    tile_r = tuned("sketch_merge", backend, _shape_class_tables(N, F, K),
                   tile_r=tile_r)["tile_r"]
    if _is_traced(a_y, a_sum_x, b_y, b_sum_x):
        return _sketch_merge_impl(a_y, a_sum_x, b_y, b_sum_x,
                                  backend=backend, tile_r=tile_r)
    return _jit_sketch_merge(backend, tile_r)(a_y, a_sum_x, b_y, b_sum_x)


def _sketch_update_impl(ao_y, ao_sum_x, leaf, X, y, w, *, backend: str,
                        tile_r: int):
    """Backend dispatch body of :func:`sketch_update`: pre-sketch the
    routed batch into per-(leaf, feature) rank buckets (pure jnp on all
    backends — it is sorts and one segment reduction), then merge the
    batch sketch into the running state via the compaction backend."""
    M, F, K = ao_sum_x.shape
    b_n, b_mean, b_m2, b_sx = sketch_lib.from_batch_planes(leaf, X, y, w, M, K)
    return _sketch_merge_impl(
        ao_y, ao_sum_x, {"n": b_n, "mean": b_mean, "m2": b_m2}, b_sx,
        backend=backend, tile_r=tile_r)


@register_jit_cache
@functools.lru_cache(maxsize=None)
def _jit_sketch_update(backend: str, tile_r: int):
    """Keyed handle for the sketch absorb's cached jit; delegates to the
    shared :func:`_dispatch`."""
    return _dispatch(_sketch_update_impl, backend=backend, tile_r=tile_r)


def sketch_update(ao_y, ao_sum_x, leaf, X, y, w=None, *,
                  backend: str | None = None, tile_r: int | None = None):
    """Absorb a routed batch into every (leaf, feature) sketch.

    ao_y: Stats dict of (M, F, K); ao_sum_x: (M, F, K); leaf: (B,) i32
    routed leaf ids (-1 rows vanish); X: (B, F); y: (B,); w: optional
    (B,) f32 sample weights (default 1) — weight-0 rows vanish and the
    batch pad ladder is bit-identical (pad rows never touch a bucket),
    the same contract as :func:`forest_update`.  Returns the merged
    ``(ao_y, ao_sum_x)``.  One batch is ONE compaction (batch pre-sketch
    + merge) — there is no per-tile streaming, so every ``tile_r`` and
    ladder choice is bit-identical on every backend.

    Called with concrete arrays this dispatches through a cached jit
    with the batch padded to its ladder bucket; under an enclosing trace
    it inlines so the caller's jit fuses the whole absorb stage.
    """
    backend = resolve_backend(backend)
    leaf = jnp.asarray(leaf, jnp.int32).reshape(-1)
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32).reshape(-1)
    w = jnp.ones_like(y) if w is None else jnp.asarray(w, jnp.float32).reshape(-1)
    M, F, K = ao_sum_x.shape
    p = tuned("sketch_update", backend, _shape_class_tables(M, F, K),
              tile_r=tile_r)
    if _is_traced(ao_y, ao_sum_x, leaf, X, y, w):
        return _sketch_update_impl(ao_y, ao_sum_x, leaf, X, y, w,
                                   backend=backend, tile_r=p["tile_r"])
    leaf, X, y, w = _pad_batch(
        leaf, X, y, w, _ladder_bucket(X.shape[0], 128, p["batch_ladder"]))
    return _jit_sketch_update(backend, p["tile_r"])(
        ao_y, ao_sum_x, leaf, X, y, w)


def sketch_to_bins(ao_y, ao_sum_x):
    """Densify-at-attempt-time adapter: sketch state -> query-ready bins.

    A sketch's K centroids in ascending-prototype order ARE a valid
    sorted bin table — zero-weight slots are exact identities of the
    §2.4 prefix merge — so "densify" is a defensive stable sort along
    the slot axis (the identity on well-formed state, which
    :func:`sketch_update`/:func:`sketch_merge` keep rank-ordered by
    construction) and :func:`forest_best_splits` consumes the result
    unchanged on every backend.  Pure jnp everywhere (a sort is not a
    profitable hand kernel) and cheap enough to inline at attempt time;
    it takes no backend/tile knobs, so the observer choice can never
    reach a kernel cache key through this adapter.
    """
    n, mean, m2, sum_x = sketch_lib.sort_planes(
        ao_y["n"], ao_y["mean"], ao_y["m2"], ao_sum_x)
    return {"n": n, "mean": mean, "m2": m2}, sum_x

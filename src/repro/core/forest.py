"""Online-bagged forest of QO Hoeffding tree regressors (DESIGN.md §5).

The strongest streaming regressors in practice are ensembles of Hoeffding
trees (Adaptive Random Forests); the paper positions QO as the
split-attempt engine that makes each member cheap enough for real-time
ensembles.  This module is that ensemble layer, built so the whole forest
is ONE program over a leading tree axis:

* **online bagging** — each instance reaches tree t with a Poisson(λ)
  sample weight (Oza & Russell), threaded through every statistic of the
  member update (:func:`repro.core.hoeffding.update` with ``w``), so
  bagging costs nothing on top of the fused absorb;
* **random subspaces** — each member draws a feature mask of
  ``max(1, round(subspace * F))`` features; masked features still fill
  their QO tables but can never win a split (ARF-style decorrelation);
* **fused execution** — the T member updates run as ONE pass: absorb is
  one ``forest_update`` call whose groups are the members (its grid
  walks member by member, so a member's rows meet only its own tables),
  and the tree axis folds into the table axis of ``forest_best_splits``
  (global leaf ids ``t*M + leaf``), so absorb and the split query are
  each a single kernel/XLA call for the whole ensemble and only the
  cheap per-tree decision/scatter stage is vmapped
  (:func:`_fused_member_update`);
* **tree-axis sharding** — every leaf of the forest state carries the
  tree axis first, so :func:`repro.train.sharding.forest_state_specs`
  spreads T trees across the device mesh with ``shard_map``; members
  never communicate except the prediction reduce (``axis_name`` arg);
* **drift-aware member swap** — each tree keeps an ADWIN-style
  prequential-error window (long (n, mean, M2) window + short EWMA, the
  §3 algebra reused on the error stream).  When a short window rises
  ``drift_kappa`` standard deviations above its long reference, the
  WORST signalling member is swapped for a fresh tree + subspace +
  window (at most one per batch, so the forest's memory degrades
  gracefully under abrupt drift).  The test is per-member and local, so
  it adds no cross-tree communication.

Functional API mirrors the single tree: :func:`init_forest` ->
:func:`update` (returns ``(state, aux)`` with prequential metrics) ->
:func:`predict`; :func:`update_stream` scans a stream in one dispatch and
returns the prequential MSE traces the benchmarks report.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import hoeffding as ht
from repro.core import stats
from repro.kernels import ops as kops

ForestState = dict

__all__ = ["ForestConfig", "init_forest", "update", "update_stream",
           "predict", "member_predictions", "vote_weights",
           "n_leaves_per_tree"]


@dataclass(frozen=True)
class ForestConfig:
    """Static forest hyper-parameters (hashable: pass as a jit static arg).

    tree:      the shared member :class:`repro.core.hoeffding.HTRConfig`.
    n_trees:   T, the ensemble size (the vmapped/sharded axis).
    lam:       Poisson rate λ of the online-bagging sample weights
               (λ = 6 after Adaptive Random Forests).
    subspace:  fraction of features each member may split on;
               k = max(1, round(subspace * F)) features are drawn per tree
               (and re-drawn when the member is reset).
    vote:      "mean" or "inverse_error" — prediction reduce over members,
               the latter weighting each tree by
               (1 / (EWMA prequential MSE + eps)) ** vote_power; members
               with no error history yet (fresh after init or a reset)
               vote with weight 0 until their first prequential batch.
    vote_power: sharpness of the inverse-error vote (higher -> closer to
               picking the single best member).
    drift_alpha:       EWMA rate of the short error window.
    drift_decay:       per-batch decay of the long window's effective count
               (effective window length 1/(1-decay) batches), so the
               cold-start transient washes out of the reference.
    drift_kappa:       sigmas above the long window mean that signal drift.
    drift_min_batches: effective batches a member's long window must hold
               before its drift test may fire (cold-start guard; must be
               below 1/(1-drift_decay) or the test never arms).
    """
    tree: ht.HTRConfig
    n_trees: int = 8
    lam: float = 6.0
    subspace: float = 0.7
    vote: str = "inverse_error"
    vote_power: float = 4.0
    drift_alpha: float = 0.5
    drift_decay: float = 0.9
    drift_kappa: float = 3.0
    drift_min_batches: int = 8

    def __post_init__(self):
        if not 0.0 < self.drift_decay < 1.0:
            raise ValueError(
                f"drift_decay={self.drift_decay} must be in (0, 1): it is "
                f"the per-batch retention of the long window's count")
        limit = 1.0 / (1.0 - self.drift_decay)
        if self.drift_min_batches >= limit:
            raise ValueError(
                f"drift_min_batches={self.drift_min_batches} can never be "
                f"reached: the decayed window's effective count asymptotes "
                f"to 1/(1-drift_decay)={limit:.1f}")
        if self.tree.n_features >= 2 and self.subspace_k() < 2:
            raise ValueError(
                f"subspace={self.subspace} leaves each member a single "
                f"candidate feature: the Hoeffding ratio test degenerates "
                f"(second-best merit is -inf, so any positive merit splits "
                f"immediately); raise subspace so k >= 2")

    def subspace_k(self) -> int:
        return max(1, int(round(self.subspace * self.tree.n_features)))


def _draw_mask(key, F: int, k: int):
    perm = jax.random.permutation(key, F)
    return jnp.zeros((F,), bool).at[perm[:k]].set(True)


def _poisson_cdf(lam: float, tail: float = 1e-7):
    """Static inverse-CDF table: [P(X<=0), P(X<=1), ...] up to 1-tail."""
    import math
    cdf, p, k, c = [], math.exp(-lam), 0, math.exp(-lam)
    while c < 1.0 - tail and k < 64:
        cdf.append(c)
        k += 1
        p *= lam / k
        c += p
    cdf.append(c)
    return cdf


def _poisson_weights(key, cdf: jax.Array, shape):
    """Poisson draw by inverse-CDF table lookup.

    Exact up to the table's 1e-7 tail truncation, and — unlike
    ``jax.random.poisson``'s rejection sampler — free of ``while_loop``:
    ~10x cheaper per batch on CPU and transparent to vmap/shard_map
    replication checking.  ``X = #{k : u >= P(X<=k)}``.
    """
    u = jax.random.uniform(key, shape)
    return (u[..., None] >= cdf).sum(-1).astype(jnp.float32)


def init_forest(cfg: ForestConfig, key) -> ForestState:
    """Fresh forest state — a dict pytree whose EVERY leaf has the tree
    axis (T) first, the invariant the sharding layer relies on:

    ``trees``     member TreeStates stacked on axis 0 (T, ...)
    ``feat_mask`` (T, F) bool random-subspace masks
    ``keys``      (T, 2) u32 per-member PRNG keys (bagging + subspace
                  draws stay independent per member and per shard)
    ``err_win``   Stats (T,) — long prequential-error window since reset
    ``err_ewma``  (T,) f32 — short (EWMA) prequential-error window
    ``vote_w``    (T,) f32 — member vote weights, refreshed once per
                  ``update`` from the error windows (the serving read
                  path and :mod:`repro.core.serve` snapshots consume
                  them for free instead of recomputing per call)
    ``resets``    (T,) i32 — drift-reset count (diagnostics)
    """
    T, F = cfg.n_trees, cfg.tree.n_features
    base = ht.init_state(cfg.tree)
    trees = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (T,) + a.shape), base)
    keys = jax.random.split(key, T + 1)
    masks = jax.vmap(
        functools.partial(_draw_mask, F=F, k=cfg.subspace_k()))(keys[1:])
    return {
        "trees": trees,
        "feat_mask": masks,
        "keys": jax.random.split(keys[0], T),
        "err_win": stats.init((T,)),
        "err_ewma": jnp.zeros((T,), jnp.float32),
        "vote_w": jnp.zeros((T,), jnp.float32),   # == vote_weights(fresh)
        "resets": jnp.zeros((T,), jnp.int32),
    }


def member_predictions(cfg: ForestConfig, state: ForestState,
                       X: jax.Array) -> jax.Array:
    """(T, B) f32 — every member's prediction for every row of X (B, F).

    ONE fused route for the whole ensemble: the tree axis folds into the
    routing kernel's node axis (:func:`repro.kernels.ops.forest_route`,
    the read-side twin of the §5.1 table fold), then every member's leaf
    means gather in one take — no per-tree dispatch, no vmapped scalar
    walk.  ``split_backend="oracle"`` keeps the seed's vmap-of-scalar
    engine as the correctness reference.  Concrete states route with a
    sweep trimmed to the deepest member's *realized* depth.
    """
    trees = state["trees"]
    backend = cfg.tree.split_backend
    if backend == "oracle":
        return jax.vmap(functools.partial(ht.predict, cfg.tree),
                        in_axes=(0, None))(trees, X)
    depth = cfg.tree.max_depth
    if not kops._is_traced(trees["feature"], trees["depth"], X):
        depth = min(depth, int(trees["depth"].max()))
    leaf = kops.forest_route(trees["feature"], trees["threshold"],
                             trees["child"], trees["is_leaf"], X,
                             depth=depth, backend=backend)
    return jnp.take_along_axis(trees["ystats"]["mean"], leaf, axis=1)


def vote_weights(cfg: ForestConfig, state: ForestState) -> jax.Array:
    """(T,) f32 un-normalized member vote weights from the error windows.

    ``inverse_error`` weights a member by
    ``(1 / (EWMA prequential MSE + eps)) ** vote_power``; members with no
    error history yet (fresh after init or a drift reset) vote 0 so a
    just-reset blank tree cannot drag the ensemble (an all-fresh forest
    predicts 0 either way; :func:`predict` guards the 0/0).

    :func:`update` calls this ONCE per learned batch and carries the
    result in ``state["vote_w"]``; the read path (:func:`predict`, the
    prequential vote inside :func:`update`, :func:`repro.core.serve`
    snapshots) consumes the carried weights instead of re-deriving them
    per prediction call.
    """
    T = state["err_ewma"].shape[0]
    if cfg.vote == "mean":
        return jnp.ones((T,), jnp.float32)
    assert cfg.vote == "inverse_error", cfg.vote
    seen = state["err_win"]["n"] > 0
    return jnp.where(
        seen, (1.0 / (state["err_ewma"] + 1e-6)) ** cfg.vote_power, 0.0)


def _vote_combine(yhat, wts, axis_name):
    """(T_local, B) member predictions + (T_local,) weights -> (B,) vote.

    The single definition of the prediction reduce, shared by
    :func:`predict` and the prequential error in :func:`update` so the
    reported forest_mse always describes the predictor predict serves.
    With ``axis_name`` (inside shard_map) the num/den psum pair is the
    forest's only collective.
    """
    num = (wts[:, None] * yhat).sum(0)
    den = wts.sum()
    if axis_name is not None:
        num, den = jax.lax.psum((num, den), axis_name)
    return num / jnp.maximum(den, 1e-12)


@kops.register_jit_cache
@functools.lru_cache(maxsize=None)
def _jit_predict_live(backend: str, plies: int):
    """Keyed handle for the whole live read path of one (backend,
    ply-bucket) — serving a live forest dispatches ONE compiled program
    per call instead of an eager epilogue.  The body IS the snapshot
    serving body (:func:`repro.core.serve._predict_impl` — route ->
    gather -> vote), traced over the live state's full-capacity tables
    through the shared :func:`repro.kernels.ops._dispatch` factory (no
    donation: the live state owns X's buffer lifetime, not this path),
    so the two read paths can never diverge."""
    from repro.core import serve as sv
    return kops._dispatch(sv._predict_impl, plies=plies, backend=backend,
                          single=False)


def predict(cfg: ForestConfig, state: ForestState, X: jax.Array,
            axis_name: str | None = None) -> jax.Array:
    """Forest prediction: the vote-weighted mean of member predictions.

    X: (B, F) -> (B,) f32.  ``axis_name``: when the tree axis is split
    over devices with ``shard_map``, pass the mesh axis name — the only
    cross-tree communication in the whole forest is this one psum pair.
    Reads the ``vote_w`` carried by the last :func:`update` (refreshed
    once per learned batch), so serving pays one fused route + one
    gather + one reduce per call and nothing else.  Called with a
    concrete state (the live-serving pattern) the whole read path
    dispatches as ONE cached jit, routing trimmed to the deepest
    member's *realized* depth; results are bit-identical to the traced
    composition.  (The trim costs one tiny device reduce + host sync
    per call — the price of tracking a still-training state; freezing
    with :mod:`repro.core.serve` bakes the depth in as static metadata
    and drops the probe, so prefer snapshots for a frozen model.)
    """
    backend = cfg.tree.split_backend
    trees = state["trees"]
    X = jnp.asarray(X, jnp.float32)
    if (axis_name is None and backend != "oracle"
            and not kops._is_traced(trees["feature"], state["vote_w"], X)):
        depth = min(cfg.tree.max_depth, int(trees["depth"].max()))
        rbackend = kops.resolve_backend(backend)
        T, M = trees["feature"].shape
        p = kops.tuned("forest_route", rbackend,
                       kops._shape_class_route(T, M, int(X.shape[1])))
        X, B, padded = kops.pad_rows(X, 128, p["batch_ladder"])
        out = _jit_predict_live(
            rbackend, kops.depth_bucket(depth, p["ply_round"]))(
            trees["feature"], trees["threshold"], trees["child"],
            trees["is_leaf"], trees["ystats"]["mean"], state["vote_w"], X)
        return out[:B] if padded else out
    return _vote_combine(member_predictions(cfg, state, X),
                         state["vote_w"], axis_name)


def _fold_tables(a, T, M):
    """(T, M, ...) -> (T*M, ...): the tree axis folds into the table axis."""
    return a.reshape((T * M,) + a.shape[2:])


def _global_leaf(leaf, M):
    """(T, B) per-member leaf ids -> (T*B,) folded ids ``t*M + leaf``."""
    T = leaf.shape[0]
    return (jnp.arange(T, dtype=leaf.dtype)[:, None] * M + leaf).reshape(-1)


def _fused_route_stats(cfg: ForestConfig, trees, X, y, w):
    """Route all T members and reduce the batch's per-leaf target stats.

    ONE fused route for all T trees (the §2.6 folded-node-axis sweep) and
    one flat segment reduction over global leaf ids ``t*M + leaf``.
    Returns ``(leaf, batch_leaf)``: the (T, B) per-tree leaf ids and the
    batch's (T, M) Stats — the shard-local monitor quantities of the
    §4.1 data-parallel protocol (which accumulates them in a delta
    instead of folding them straight into ``trees``).
    """
    tcfg = cfg.tree
    M = tcfg.max_nodes
    T = trees["feature"].shape[0]
    with jax.named_scope("forest.route"):
        leaf = kops.forest_route(trees["feature"], trees["threshold"],
                                 trees["child"], trees["is_leaf"], X,
                                 depth=tcfg.max_depth,
                                 backend=tcfg.split_backend)
        batch_leaf = jax.tree.map(
            lambda a: a.reshape(T, M),
            ht._segment_stats(jnp.tile(y, T), _global_leaf(leaf, M), T * M,
                              w.reshape(-1)))
    return leaf, batch_leaf


def _fused_absorb_tables(cfg: ForestConfig, ao_y, ao_sum_x, trees, leaf,
                         X, y, w):
    """Absorb a routed batch into ANY (T, M, F, C) table set in one pass.

    ``ao_y``/``ao_sum_x`` are the accumulation target (the live
    ``trees["ao_*"]`` tables, or a shard-local DELTA starting from
    zero — §4.1); the quantization grid (radius/origin) always comes
    from ``trees``, so every shard bins identically, which is what makes
    the deltas mergeable.  ``leaf``: (T, B) per-tree leaf ids from
    :func:`_fused_route_stats`; w: (T, B).  The members are the groups
    of ONE ``forest_update`` call (§5.1): X and y are shared, and each
    member's rows meet only its own tables.  Returns the merged tables.
    """
    tcfg = cfg.tree
    M = tcfg.max_nodes
    T = trees["feature"].shape[0]
    with jax.named_scope("forest.absorb"):
        if tcfg.observer_backend == "sketch":
            # the sketch needs no quantization grid — folded leaf ids
            # alone segment the batch, so shard deltas stay mergeable by
            # the rank contract instead of by a shared grid
            flat = functools.partial(_fold_tables, T=T, M=M)
            ao_y, ao_sum_x = kops.sketch_update(
                jax.tree.map(flat, ao_y), flat(ao_sum_x),
                _global_leaf(leaf, M), jnp.tile(X, (T, 1)), jnp.tile(y, T),
                w.reshape(-1), backend=tcfg.split_backend)
            unflat = lambda a: a.reshape((T, M) + a.shape[1:])
            return jax.tree.map(unflat, ao_y), unflat(ao_sum_x)
        return kops.forest_update(
            ao_y, ao_sum_x, trees["ao_radius"], trees["ao_origin"],
            leaf, X, y, w, backend=tcfg.split_backend)


def _fused_member_attempt(cfg: ForestConfig, trees, feat_mask):
    """Attempt stage for all T members on their CURRENT statistics.

    The scheduling mask is the shared single-tree definition
    (:func:`repro.core.hoeffding.attempt_mask`) plus the per-tree
    capacity gate; the ONE compacted query spans the whole ensemble's
    folded T*M table axis, and only the cheap O(M) decision/scatter
    stage is vmapped.  Statistics may come from the local batch (the
    fused update below) or from a §4.1 cross-shard merge — the decision
    math is identical either way.
    """
    tcfg = cfg.tree
    M, F = tcfg.max_nodes, tcfg.n_features
    T = feat_mask.shape[0]
    flat = functools.partial(_fold_tables, T=T, M=M)
    with jax.named_scope("forest.attempt"):
        attempt = jax.vmap(functools.partial(ht.attempt_mask, tcfg))(
            trees) & (trees["n_nodes"][:, None] + 1 < M)

        def do(tr, att):
            # the folded T*M table axis compacts across trees: the ONE
            # query gathers only the attempting leaves of the whole ensemble
            ao_y = jax.tree.map(flat, tr["ao_y"])
            ao_sum_x = flat(tr["ao_sum_x"])
            if tcfg.observer_backend == "sketch":
                ao_y, ao_sum_x = kops.sketch_to_bins(ao_y, ao_sum_x)  # §2.8
            merit, thr = kops.forest_best_splits(
                ao_y, ao_sum_x,
                flat(tr["ao_radius"]), flat(tr["ao_origin"]),
                att.reshape(-1), backend=tcfg.split_backend,
                compact=tcfg.compact_query)
            return jax.vmap(functools.partial(ht._apply_splits, tcfg))(
                tr, merit.reshape(T, M, F), thr.reshape(T, M, F), att,
                feat_mask)

        return jax.lax.cond(attempt.any(), do, lambda tr, a: dict(tr),
                            trees, attempt)


def _fused_member_update(cfg: ForestConfig, trees, feat_mask, X, y, w):
    """All T member updates as ONE flat pass over the PR-1 forest kernels.

    A naive ``vmap(hoeffding.update)`` turns every segment-reduction and
    scatter into a *batched* scatter, which XLA (CPU especially) lowers
    poorly — measured ~4x slower than a python loop over trees.  Instead
    the tree axis is folded into the table axis the kernels already
    batch over: T trees x M nodes become one (T*M, F, C) forest with
    global leaf ids ``t*M + leaf``, so absorb is ONE
    :func:`repro.kernels.ops.forest_update` (its group form, one group
    per member, so the grid skips every cross-member pair), the split
    query ONE
    :func:`repro.kernels.ops.forest_best_splits` (both tree-count
    agnostic on every backend), and only the cheap O(M) decision/scatter
    stage (:func:`repro.core.hoeffding._apply_splits`) is vmapped.
    The three stages are factored (:func:`_fused_route_stats`,
    :func:`_fused_absorb_tables`, :func:`_fused_member_attempt`) so the
    §4.1 data-parallel trainer can run the first two per shard and the
    attempt globally on merged statistics.

    trees: stacked TreeStates (T leading); w: (T, B) sample weights.
    """
    leaf, batch_leaf = _fused_route_stats(cfg, trees, X, y, w)
    with jax.named_scope("forest.route"):
        trees = dict(trees,
                     ystats=stats.merge(trees["ystats"], batch_leaf),
                     seen_since_attempt=trees["seen_since_attempt"]
                     + batch_leaf["n"])
    ao_y, ao_sum_x = _fused_absorb_tables(
        cfg, trees["ao_y"], trees["ao_sum_x"], trees, leaf, X, y, w)
    trees = dict(trees, ao_y=ao_y, ao_sum_x=ao_sum_x)
    return _fused_member_attempt(cfg, trees, feat_mask)


def update(cfg: ForestConfig, state: ForestState, X: jax.Array,
           y: jax.Array, axis_name: str | None = None,
           w: jax.Array | None = None):
    """Learn one batch, test-then-train.

    Evaluates every member on the incoming batch (prequential), folds the
    batch into every member with fresh Poisson(λ) sample weights, advances
    the per-member drift windows and resets the worst drifting member.
    ``w``: optional (B,) per-row weights multiplying every member's
    Poisson draw AND weighting the prequential errors — a weight-0 row is
    invisible to both learning and the drift windows, which is how
    :func:`update_stream` folds a ragged tail batch in without bias.

    Returns ``(state, aux)`` with
    ``aux = {"member_mse": (T,), "forest_mse": (), "drift": (T,) bool}``
    — prequential (pre-update) errors of this batch.  The member updates
    execute as one fused flat-forest pass (:func:`_fused_member_update`;
    ``split_backend="oracle"`` falls back to ``vmap(hoeffding.update)``
    as the correctness reference); with ``axis_name`` set (inside
    ``shard_map``) only the forest_mse vote reduce communicates.
    """
    # --- test: prequential member + forest errors on the raw stream ------
    with jax.named_scope("forest.test"):
        X = jnp.asarray(X, jnp.float32)
        y = jnp.asarray(y, jnp.float32).reshape(-1)
        B = y.shape[0]
        row_w = jnp.ones_like(y) if w is None \
            else jnp.asarray(w, jnp.float32).reshape(-1)
        wsum = jnp.maximum(row_w.sum(), 1e-12)
        yhat = member_predictions(cfg, state, X)               # (T, B)
        member_mse = (row_w[None, :] * (yhat - y[None, :]) ** 2).sum(1) \
            / wsum
        fpred = _vote_combine(yhat, state["vote_w"], axis_name)
        forest_mse = (row_w * (fpred - y) ** 2).sum() / wsum

    # --- train: Poisson(λ) bagging weights, one fused member update ------
    with jax.named_scope("forest.route"):
        split = jax.vmap(functools.partial(jax.random.split, num=3))(
            state["keys"])                                     # (T, 3, 2)
        keys, wkeys, mkeys = split[:, 0], split[:, 1], split[:, 2]
        cdf = jnp.asarray(_poisson_cdf(cfg.lam), jnp.float32)
        w = jax.vmap(lambda k: _poisson_weights(k, cdf, (B,)))(wkeys) \
            * row_w[None, :]                                   # (T, B)
    if cfg.tree.split_backend == "oracle":
        trees = jax.vmap(functools.partial(ht.update, cfg.tree),
                         in_axes=(0, None, None, 0, 0))(
            state["trees"], X, y, w, state["feat_mask"])
    else:
        trees = _fused_member_update(cfg, state["trees"], state["feat_mask"],
                                     X, y, w)

    # --- drift: ADWIN-style short-vs-long window test per member ---------
    # the short (EWMA) window is compared against the long window BEFORE
    # this batch is folded in — once errors jump, the reference must not
    # absorb the jump or the test chases its own tail and never fires.
    # The long window decays (effective length 1/(1-drift_decay) batches)
    # so the cold-start transient washes out of the reference.
    # Both windows advance by the batch's REAL-row fraction, not a full
    # step: a masked tail batch with one live row must not move the EWMA
    # at full drift_alpha (one outlier row could otherwise fire a
    # spurious member swap at stream end).
    with jax.named_scope("forest.drift"):
        live = row_w.sum() > 0
        # clamped at 1: importance weights > 1 must not push the EWMA rate
        # past drift_alpha (alpha > 1 would make the recursion sign-flip)
        frac = jnp.where(live,
                         jnp.minimum(wsum / jnp.maximum(jnp.float32(B), 1.0),
                                     1.0), 0.0)
        alpha = cfg.drift_alpha * frac
        first = (state["err_win"]["n"] < 0.5) & live
        ewma = jnp.where(first, member_mse,
                         (1.0 - alpha) * state["err_ewma"]
                         + alpha * member_mse)
        ref = state["err_win"]
        sd = jnp.sqrt(jnp.maximum(stats.variance(ref), 1e-12))
        signal = (ref["n"] >= cfg.drift_min_batches) \
            & (ewma > ref["mean"] + cfg.drift_kappa * sd)
        # swap at most the WORST signalling member per batch (per shard
        # when the tree axis is sharded): staggered resets keep the
        # forest's memory
        worst = jnp.argmax(jnp.where(signal, ewma, -jnp.inf))
        drift = signal & (jnp.arange(signal.shape[0]) == worst)
        # the reference decays by the same real-mass fraction it observes
        # (decay^frac), so persistently sub-unit weights shift the
        # window's time constant instead of silently lowering its n
        # equilibrium below drift_min_batches (which would disarm
        # detection); frac == 1 takes the exact python constant so
        # unweighted streams are bit-identical
        decay = jnp.where(frac >= 1.0, cfg.drift_decay,
                          jnp.float32(cfg.drift_decay) ** frac)
        decayed = {"n": decay * ref["n"], "mean": ref["mean"],
                   "m2": decay * ref["m2"]}
        observed = stats.observe(decayed, member_mse, frac)
        # a signalling member's reference FREEZES (no decay, no observe):
        # if it wasn't this batch's worst it must keep its clean pre-drift
        # reference so it can fire again next batch — otherwise the window
        # absorbs the jump and simultaneous drifts beyond the first are
        # never swapped
        win = jax.tree.map(
            lambda o, r: jnp.where(signal, r, o), observed, ref)

        # --- swap: reset drifting members (fresh tree, subspace, window)
        T = drift.shape[0]               # local shard size under shard_map
        fresh = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (T,) + a.shape),
            ht.init_state(cfg.tree))

        def swap(a, f):
            return jnp.where(drift.reshape((T,) + (1,) * (a.ndim - 1)),
                             f, a)

        trees = jax.tree.map(swap, trees, fresh)
        new_masks = jax.vmap(functools.partial(
            _draw_mask, F=cfg.tree.n_features, k=cfg.subspace_k()))(mkeys)
        state = {
            "trees": trees,
            "feat_mask": jnp.where(drift[:, None], new_masks,
                                   state["feat_mask"]),
            "keys": keys,
            "err_win": jax.tree.map(lambda a: jnp.where(drift, 0.0, a),
                                    win),
            "err_ewma": jnp.where(drift, 0.0, ewma),
            "resets": state["resets"] + drift.astype(jnp.int32),
        }
        # vote weights refresh ONCE per learned batch; every read (predict,
        # the next batch's prequential vote, serve.freeze) reuses them
        state["vote_w"] = vote_weights(cfg, state)
    return state, {"member_mse": member_mse, "forest_mse": forest_mse,
                   "drift": drift}


@functools.partial(jax.jit, static_argnames=("cfg", "batch_size"))
def update_stream(cfg: ForestConfig, state: ForestState, X: jax.Array,
                  y: jax.Array, batch_size: int = 256):
    """Scan a whole stream through :func:`update` in ONE dispatch.

    X: (N, F), y: (N,).  A ragged tail rides in a final weight-0-masked
    batch (:func:`repro.core.hoeffding.pad_stream`: invisible to
    learning, bagging draws and the prequential windows), so ALL N rows
    are learned.  Returns ``(state, trace)`` where ``trace["forest_mse"]``
    is the (ceil(N / batch_size),) prequential forest MSE and
    ``trace["member_mse"]`` the (n_batches, T) per-member traces — the
    benchmark's acceptance data.
    """
    Xc, yc, wc = ht.pad_stream(X, y, None, batch_size)

    def body(s, xyw):
        s, aux = update(cfg, s, xyw[0], xyw[1], w=xyw[2])
        return s, (aux["forest_mse"], aux["member_mse"])

    state, (fmse, mmse) = jax.lax.scan(body, state, (Xc, yc, wc))
    return state, {"forest_mse": fmse, "member_mse": mmse}


def n_leaves_per_tree(state: ForestState) -> jax.Array:
    """(T,) i32 live-leaf count of every member (diagnostics)."""
    return jax.vmap(ht.n_leaves)(state["trees"])

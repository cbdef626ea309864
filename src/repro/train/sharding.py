"""PartitionSpec rules for params, optimizer state, batches, caches — and
the tree-axis sharding of the QO Hoeffding forest (DESIGN.md §5).

Strategy (DESIGN.md §7): TP over the 16-way "model" axis + FSDP over the
data axes ("pod","data") — required for grok-1-314b, whose optimizer state
would otherwise need 235 GB/chip.  Rules are name+shape based over the
param pytree; every rule falls back to replication when a dimension does
not divide the mesh axis (e.g. whisper's 51865 vocab, 8-way KV heads).

Logical mapping:
  d_model / d_inner rows  ->  fsdp axes      (all-gathered for the matmul)
  heads / d_ff / vocab    ->  "model" (TP)
  experts                 ->  "model" when E % tp == 0 (EP), else d_ff TP
  batch                   ->  fsdp axes
  decode KV cache         ->  batch over fsdp; heads over model when
                              divisible, else sequence over model
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    """Returns (fsdp_axes, tp_axis)."""
    names = mesh.axis_names
    tp = "model"
    fsdp = tuple(n for n in names if n != tp)
    return fsdp, tp


def _div(n: int, size: int) -> bool:
    return n > 0 and n % size == 0


def param_specs(cfg, params_shapes, mesh: Mesh, style: str = "contraction"):
    """Pytree of PartitionSpec matching the params pytree.

    ``params_shapes``: pytree of ShapeDtypeStruct (from jax.eval_shape).

    style:
      "contraction" (baseline): FSDP shards the contraction (d_model) dim of
        weights.  XLA then often SPLITS the contraction instead of gathering
        the weight, all-reducing full activation tensors over the data axes
        — measured catastrophic for MoE (§Perf: grok 7.8 TB/step).
      "gather": FSDP co-shards the weight's OUTPUT dim with TP
        (2D sharding).  The output dim cannot be data-sharded twice (tokens
        already are), so the partitioner must ALL-GATHER the weight shards —
        the ZeRO-3 pattern: collective bytes scale with weights, not
        activations.
    """
    fsdp, tp = mesh_axes(mesh)
    tp_n = mesh.shape[tp]
    fsdp_n = 1
    for a in fsdp:
        fsdp_n *= mesh.shape[a]
    d = cfg.d_model
    gather = style == "gather"

    def fs(dim):  # fsdp-shard a dimension if it divides
        return fsdp if _div(dim, fsdp_n) else None

    def tps(dim):
        return tp if _div(dim, tp_n) else None

    def tp_fs(dim):
        """2D shard over (tp, fsdp...) when divisible, else best effort."""
        if _div(dim, tp_n * fsdp_n):
            return (tp,) + fsdp
        return tps(dim)

    def rule(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        name = keys[-1] if keys else ""
        shp = leaf.shape
        nd = len(shp)
        # strip the stacked-layer leading axis for rule matching
        core = shp[1:] if (keys and keys[0] in ("layers", "enc_layers")
                           and nd >= 1) else shp

        def spec(*core_spec):
            pad = (None,) * (nd - len(core_spec))
            return P(*pad, *core_spec)

        if name == "embed":
            if _div(shp[0], tp_n):
                return P(tp, fs(shp[1]))
            return P(None, tps(shp[1]))
        if name == "lm_head":
            if gather:
                return P(None, tp_fs(shp[1]))
            return P(fs(shp[0]), tps(shp[1]))
        if name in ("wq", "wo"):
            # (d, H, hd) / (H, hd, d): heads over TP
            if name == "wq":
                if gather:  # output dims (H, hd) 2D-sharded -> weight gather
                    return spec(None, tps(core[1]), fs(core[2]))
                return spec(fs(core[0]), tps(core[1]), None)
            if gather:
                return spec(tps(core[0]), None, fs(core[2]))
            return spec(tps(core[0]), None, fs(core[2]))
        if name in ("wk", "wv"):
            if gather:
                return spec(None, tps(core[1]), fs(core[2]))
            return spec(fs(core[0]), tps(core[1]), None)
        if name in ("w_gate", "w_up", "w_down", "router"):
            if len(core) == 3:  # MoE (E, d, f) / (E, f, d)
                E = core[0]
                if gather:
                    # contraction dim NEVER data-sharded; FSDP rides the
                    # output dim (core[2]) -> partitioner gathers weights
                    if _div(E, tp_n):  # EP: experts over tp
                        return spec(tp, None, fs(core[2]))
                    if name == "w_down":  # (E, f, d): f row-parallel
                        return spec(None, tps(core[1]), fs(core[2]))
                    return spec(None, None, tp_fs(core[2]))  # (E, d, f)
                if _div(E, tp_n):  # EP
                    return spec(tp, fs(core[1]) if name != "w_down" else None,
                                None)
                if name == "w_down":
                    return spec(None, tps(core[1]), fs(core[2]))
                return spec(None, fs(core[1]), tps(core[2]))
            if name == "router":
                return spec(fs(core[0]) if not gather else None, None)
            if name == "w_down":
                return spec(tps(core[0]), fs(core[1]))
            if gather:
                return spec(None, tp_fs(core[1]))
            return spec(fs(core[0]), tps(core[1]))
        if name in ("in_proj",):  # mamba1 (d, 2di)
            if gather:
                return spec(None, tp_fs(core[1]))
            return spec(fs(core[0]), tps(core[1]))
        if name in ("in_z", "in_x"):
            if gather:
                return spec(None, tp_fs(core[1]))
            return spec(fs(core[0]), tps(core[1]))
        if name in ("in_B", "in_C", "in_dt", "x_proj"):
            return spec(None if gather else fs(core[0]), None)
        if name == "dt_proj":  # (dt_rank, di)
            return spec(None, tps(core[1]))
        if name == "out_proj":  # (di, d)
            return spec(tps(core[0]), fs(core[1]))
        if name in ("A_log", "D", "dt_bias") and len(core) >= 1:
            return spec(*([tps(core[0])] + [None] * (len(core) - 1)))
        if name in ("conv_w", "conv_x"):
            return spec(None, tps(core[1]))
        if name in ("conv_B", "conv_C"):
            return spec(None, None)
        if name == "norm_scale":
            return spec(tps(core[0]))
        # norms, biases, small tables: replicate
        return P(*([None] * nd))

    flat, tdef = jax.tree_util.tree_flatten_with_path(params_shapes)
    return jax.tree_util.tree_unflatten(tdef, [rule(p, l) for p, l in flat])


def batch_specs(cfg, shape_kind: str, global_batch: int, mesh: Mesh):
    """PartitionSpec for data batches by field name."""
    fsdp, tp = mesh_axes(mesh)
    fsdp_n = 1
    for a in fsdp:
        fsdp_n *= mesh.shape[a]
    bspec = fsdp if _div(global_batch, fsdp_n) else None

    def field(name):
        if name in ("tokens", "labels", "loss_mask"):
            return P(bspec, None)
        if name == "embeds":
            return P(bspec, None, None)
        if name == "enc_in":
            return P(bspec, None, None)
        if name == "token":     # decode: (B,) or (B, d)
            return P(bspec)
        raise KeyError(name)

    return field


def cache_specs(cfg, batch: int, mesh: Mesh, cache_shapes):
    """Specs for the decode-cache pytree (stacked layer leading axis)."""
    fsdp, tp = mesh_axes(mesh)
    tp_n = mesh.shape[tp]
    fsdp_n = 1
    for a in fsdp:
        fsdp_n *= mesh.shape[a]
    bspec = fsdp if _div(batch, fsdp_n) else None

    def rule(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        name = keys[-1]
        shp = leaf.shape
        if name in ("k", "v"):
            # (L, B, S, Hkv, hd): heads over TP if divisible, else seq
            if _div(shp[3], tp_n):
                return P(None, bspec, None, tp, None)
            if _div(shp[2], tp_n):
                return P(None, bspec, tp, None, None)
            return P(None, bspec, None, None, None)
        if name == "pos":
            return P(*([None] * len(shp)))
        if name == "ssm":
            # mamba1 (L,B,di,N): di over TP; mamba2 (L,B,nh,hd,N): nh over TP
            if len(shp) == 4:
                return P(None, bspec, tp if _div(shp[2], tp_n) else None, None)
            return P(None, bspec, tp if _div(shp[2], tp_n) else None, None, None)
        if name == "conv" or (len(keys) >= 2 and keys[-2] == "conv"):
            ch = shp[-1]
            return P(*([None, bspec, None] + [tp if _div(ch, tp_n) else None]))
        if name in ("cross_k", "cross_v"):
            if _div(shp[3], tp_n):
                return P(None, bspec, None, tp, None)
            return P(None, bspec, None, None, None)
        return P(*([None] * len(shp)))

    flat, tdef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(tdef, [rule(p, l) for p, l in flat])


def opt_specs(pspecs):
    """Optimizer state shards exactly like params (m, v) + scalar step."""
    return {"m": pspecs, "v": pspecs, "step": P()}


# --------------------------------------------------------------------------
# Hoeffding-forest tree-axis sharding (DESIGN.md §5)
# --------------------------------------------------------------------------

def forest_state_specs(state, axis="data"):
    """PartitionSpec pytree sharding the forest over its tree axis.

    Every leaf of a :mod:`repro.core.forest` state carries the tree axis
    first (the module's layout invariant), so the rule is uniform:
    ``P(axis, None, ...)`` — new per-leaf state rides along automatically
    (e.g. the §2.5 ``seen_since_attempt`` grace counters shard as
    ``P(axis, None)`` like every other (T, M) member field, keeping the
    attempt mask — and therefore the compacted split query's K bucket —
    a purely shard-local decision).  ``state`` may be a real pytree or
    the ``jax.eval_shape`` abstraction of one.
    """
    return jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), state)


def build_sharded_forest(fcfg, mesh: Mesh, axis: str = "data"):
    """jit'd ``(update_fn, predict_fn)`` with T trees spread over ``axis``.

    ``update_fn(state, X, y) -> (state, aux)`` and
    ``predict_fn(state, X) -> (B,)`` are ``shard_map`` wrappers around
    :func:`repro.core.forest.update` / ``predict``: each device owns
    ``T / mesh.shape[axis]`` member trees (T must divide) and runs the
    identical vmapped member program on its shard; the ONLY cross-device
    traffic is the two-scalar psum pair of the prediction vote reduce
    (``axis_name=axis`` inside the mapped body).  Batches are replicated —
    every member sees the whole stream, exactly like the single-host
    forest, so sharded and unsharded training produce identical forests
    while no drift swap fires (tests pin this).  The one intentional
    divergence: the worst-signalling-member swap is resolved per SHARD,
    so under simultaneous drift a D-way sharded forest may reset up to D
    members per batch where the single-host forest resets one.
    """
    from repro.core import forest as fr

    assert fcfg.n_trees % mesh.shape[axis] == 0, \
        (fcfg.n_trees, mesh.shape[axis])
    abstract = jax.eval_shape(
        lambda: fr.init_forest(fcfg, jax.random.PRNGKey(0)))
    sspec = forest_state_specs(abstract, axis)
    aux_spec = {"member_mse": P(axis), "forest_mse": P(),
                "drift": P(axis)}

    # check_vma=False: the P() outputs are replicated by construction
    # (psum), which the varying-manual-axes check cannot see through the
    # member update's gathers
    upd = jax.shard_map(
        lambda s, X, y: fr.update(fcfg, s, X, y, axis_name=axis),
        mesh=mesh, in_specs=(sspec, P(None, None), P(None)),
        out_specs=(sspec, aux_spec), check_vma=False)
    prd = jax.shard_map(
        lambda s, X: fr.predict(fcfg, s, X, axis_name=axis),
        mesh=mesh, in_specs=(sspec, P(None, None)), out_specs=P(None),
        check_vma=False)
    return jax.jit(upd), jax.jit(prd)


def build_sharded_serving(snap, mesh: Mesh, axis: str = "data"):
    """jit'd ``predict_fn(snap, X) -> (B,)`` with X split over ``axis``.

    The read-side complement of :func:`build_sharded_forest`: training
    shards the TREE axis (every device owns T/D members and sees the
    whole batch); serving shards the BATCH axis (every device owns B/D
    request rows and sees the whole — replicated — snapshot, which the
    §5.5 realized trim keeps small).  Each device runs the identical
    fused routing sweep on its rows; there are NO collectives at all —
    the per-row vote reduces over the local (replicated) tree axis.
    B must divide the mesh axis.  ``snap``: a
    :class:`repro.core.serve.Snapshot` (passed per call, so a refreshed
    snapshot of the SAME model needs no recompile while shapes keep
    their bucket; the ply budget is baked in at build, so a refreshed
    snapshot that grew DEEPER than the build-time ply bucket is rejected
    loudly — rebuild then — rather than silently under-routed).
    """
    from functools import partial

    from repro.core import serve as sv

    plies = sv.kops.depth_bucket(snap.depth)
    body = partial(sv._predict_impl, plies=plies,
                   backend=sv.kops.resolve_backend(None), single=snap.single)
    arrays = (snap.feature, snap.threshold, snap.child, snap.is_leaf,
              snap.leaf_mean, snap.vote_w)
    # the snapshot ships as its six array leaves, NOT as the Snapshot
    # pytree: its (depth, single) aux rides in the treedef, and baking it
    # into in_specs would reject every refreshed snapshot whose realized
    # depth merely CHANGED (shallower included) with a treedef mismatch
    # instead of serving it
    specs = tuple(P(*([None] * a.ndim)) for a in arrays)
    # check_vma off: the routing sweep's gathers have no replication rule
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=specs + (P(axis, None),),
        out_specs=P(axis), check_vma=False))

    def predict_fn(s, X):
        if s.single != snap.single or s.depth > plies:
            raise ValueError(
                f"snapshot (single={s.single}, depth={s.depth}) does not "
                f"fit this serving build (single={snap.single}, ply "
                f"budget {plies}): rebuild build_sharded_serving")
        return fn(s.feature, s.threshold, s.child, s.is_leaf, s.leaf_mean,
                  s.vote_w, X)

    return predict_fn


def to_shardings(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# Batch-axis (data-parallel) stream scale-out: cross-shard QO merge training
# (DESIGN.md §4.1) — the write-side complement of build_sharded_serving
# --------------------------------------------------------------------------

def _dp_init_delta(fcfg, n_shards: int):
    """Zeroed shard-local accumulator pytree, every leaf (D, ...)-leading.

    ``ystats``: per-(tree, leaf) target Stats absorbed since the last
    sync (its ``n`` is also the grace mass); ``ao_y``/``ao_sum_x``: the
    QO bin deltas; ``err``: per-member prequential squared-error Stats.
    All start at the merge identity (n = 0), so a sync after zero local
    steps is a no-op.
    """
    from repro.core import stats

    t = fcfg.tree
    D, T, M, F = n_shards, fcfg.n_trees, t.max_nodes, t.n_features
    C = t.observer_bins()
    return {
        "ystats": stats.init((D, T, M)),
        "ao_y": stats.init((D, T, M, F, C)),
        "ao_sum_x": jnp.zeros((D, T, M, F, C), jnp.float32),
        "err": stats.init((D, T)),
    }


def init_data_parallel(fcfg, key, n_shards: int):
    """Fresh data-parallel trainer state (host-layout; placement is the
    builders' job).

    ``forest``: a replicated :func:`repro.core.forest.init_forest` state
    — the shared tree topology, quantization grids and merged
    statistics every shard routes against;
    ``delta``: the shard-local accumulators (:func:`_dp_init_delta`);
    ``keys``: (D, T, 2) u32 per-(shard, member) bagging PRNG keys —
    Poisson draws stay independent across shards AND members;
    ``step``: python int batch counter driving the sync cadence.
    """
    from repro.core import forest as fr

    kf, kd = jax.random.split(key)
    return {
        "forest": fr.init_forest(fcfg, kf),
        "delta": _dp_init_delta(fcfg, n_shards),
        "keys": jax.random.split(kd, n_shards * fcfg.n_trees).reshape(
            n_shards, fcfg.n_trees, 2),
        "step": 0,
    }


def _dp_local_shard(fcfg, forest, delta, keys, X, y):
    """ONE shard's local step: route/absorb into the delta, NO attempts.

    The monitor half of the §4.1 protocol, per device: draw Poisson
    bagging weights from the shard's member keys, route the local rows
    through the REPLICATED trees, accumulate prequential member errors
    (test-then-train) and the batch's leaf/bin statistics into the
    shard-local delta.  The forest itself — topology, quantization
    grids, merged stats — is read-only here, which is what keeps the
    shards' deltas mergeable (identical bins) and the attempt stage a
    sync-boundary-only, globally-identical decision.

    delta/keys: this shard's slices (no leading D axis).
    Returns ``(delta', keys')``.
    """
    from repro.core import forest as fr
    from repro.core import stats

    trees = forest["trees"]
    B = y.shape[0]
    split = jax.vmap(functools.partial(jax.random.split, num=2))(keys)
    keys2, wkeys = split[:, 0], split[:, 1]
    cdf = jnp.asarray(fr._poisson_cdf(fcfg.lam), jnp.float32)
    w = jax.vmap(lambda k: fr._poisson_weights(k, cdf, (B,)))(wkeys)  # (T, B)

    leaf, batch_leaf = fr._fused_route_stats(fcfg, trees, X, y, w)
    # prequential member errors on the raw local rows, pre-absorb
    yhat = jnp.take_along_axis(trees["ystats"]["mean"], leaf, axis=1)
    err = stats.from_batch((yhat - y[None, :]) ** 2, axis=1)      # (T,)

    ao_y, ao_sum_x = fr._fused_absorb_tables(
        fcfg, delta["ao_y"], delta["ao_sum_x"], trees, leaf, X, y, w)
    return {
        "ystats": stats.merge(delta["ystats"], batch_leaf),
        "ao_y": ao_y,
        "ao_sum_x": ao_sum_x,
        "err": stats.merge(delta["err"], err),
    }, keys2


def _dp_local_window(fcfg, forest, delta, keys, Xw, yw):
    """Scan a whole sync window of local steps in ONE dispatch.

    Xw: (S, B_local, F); yw: (S, B_local) — S consecutive local batches
    folded into the shard delta with no host round-trip in between (the
    deployment shape of §4.1: between sync boundaries a shard is fully
    autonomous).  Same per-step body as :func:`_dp_local_shard`, so the
    scanned window is bit-identical to S single-step calls.
    """
    def body(carry, xy):
        d, k = _dp_local_shard(fcfg, forest, carry[0], carry[1],
                               xy[0], xy[1])
        return (d, k), None

    (delta, keys), _ = jax.lax.scan(body, (delta, keys), (Xw, yw))
    return delta, keys


def _dp_block(local_fn, fcfg, forest, delta, keys, X, y):
    """One device's program: ``local_fn`` (:func:`_dp_local_shard` or
    :func:`_dp_local_window`) on a (1, ...) block of the stacked shard
    state.  The mesh trainer runs it under ``shard_map``; the reference
    jits the SAME function and runs it shard by shard, so both compile
    one shard's program.  (A ``vmap`` over the shard axis is a different
    program: on a TPU, XLA reduces it in another order.)"""
    d, k = jax.tree.map(lambda a: a[0], (delta, keys))
    d, k = local_fn(fcfg, forest, d, k, X, y)
    return jax.tree.map(lambda a: a[None], (d, k))


def _dp_reduce_deltas(fcfg, delta):
    """(D, ...) stacked shard deltas -> ONE merged delta (log-depth).

    The same pairwise-halving schedule as
    :func:`repro.core.stats.tree_reduce_merge` — the order a real
    all-reduce combines partials in, and FIXED, so the reduction is
    deterministic and the sharded trainer can be pinned bitwise against
    its single-device reference.  The QO planes go through
    :func:`repro.kernels.ops.forest_merge` (the kernel-backed §4.1
    collective) with the (live, T·M) table axis folded; the small
    per-leaf/per-member Stats go through the same Chan operator.
    """
    from repro.core import stats
    from repro.kernels import ops as kops

    backend = fcfg.tree.split_backend
    F, C = fcfg.tree.n_features, fcfg.tree.observer_bins()
    # the sketch's rank-bucket merge replaces the elementwise Chan merge
    # (slot i of two sketches covers different rank ranges); the protocol
    # — fold, pairwise-halve, unfold — is identical (§2.8)
    table_merge = kops.sketch_merge \
        if fcfg.tree.observer_backend == "sketch" else kops.forest_merge

    def merge_pair(a, b):
        h = a["ao_sum_x"].shape[0] * a["ao_sum_x"].shape[1]
        fold = lambda x: x.reshape((h * fcfg.tree.max_nodes, F, C))
        ao_y, ao_sum_x = table_merge(
            jax.tree.map(fold, a["ao_y"]), fold(a["ao_sum_x"]),
            jax.tree.map(fold, b["ao_y"]), fold(b["ao_sum_x"]),
            backend=backend)
        unfold = lambda x: x.reshape(a["ao_sum_x"].shape)
        return {
            "ystats": stats.merge(a["ystats"], b["ystats"]),
            "ao_y": jax.tree.map(unfold, ao_y),
            "ao_sum_x": unfold(ao_sum_x),
            "err": stats.merge(a["err"], b["err"]),
        }

    with jax.named_scope("dp.merge"):
        while delta["ao_sum_x"].shape[0] > 1:
            k = delta["ao_sum_x"].shape[0]
            half = k // 2
            a = jax.tree.map(lambda x: x[:half], delta)
            b = jax.tree.map(lambda x: x[half:2 * half], delta)
            m = merge_pair(a, b)
            if k % 2:
                delta = jax.tree.map(
                    lambda x, t: jnp.concatenate([x, t[-1:]], 0), m, delta)
            else:
                delta = m
        return jax.tree.map(lambda x: x[0], delta)


def _dp_apply_sync(fcfg, forest, merged):
    """Fold ONE merged delta into the replicated forest + attempt splits.

    The global half of the §4.1 protocol, identical on every device:
    leaf predictors and grace mass advance by the merged batch
    statistics, the QO tables fold through
    :func:`repro.kernels.ops.forest_merge`, and the §2.5 attempt stage
    runs on the MERGED tables — so every shard derives the same splits
    and the topology stays replicated without ever shipping it.  The
    prequential error windows merge into ``err_win`` and refresh
    ``vote_w`` (in DP the short EWMA window degenerates to the merged
    running mean: per-shard EWMAs are not order-mergeable, and the DP
    trainer has no drift-swap — membership is frozen between syncs).
    Returns ``(forest', aux)``.
    """
    from repro.core import forest as fr
    from repro.core import stats
    from repro.kernels import ops as kops

    T, M = fcfg.n_trees, fcfg.tree.max_nodes
    F, C = fcfg.tree.n_features, fcfg.tree.observer_bins()
    table_merge = kops.sketch_merge \
        if fcfg.tree.observer_backend == "sketch" else kops.forest_merge
    with jax.named_scope("dp.apply"):
        trees = forest["trees"]
        trees = dict(trees,
                     ystats=stats.merge(trees["ystats"], merged["ystats"]),
                     seen_since_attempt=trees["seen_since_attempt"]
                     + merged["ystats"]["n"])
        fold = lambda x: x.reshape((T * M, F, C))
        ao_y, ao_sum_x = table_merge(
            jax.tree.map(fold, trees["ao_y"]), fold(trees["ao_sum_x"]),
            jax.tree.map(fold, merged["ao_y"]), fold(merged["ao_sum_x"]),
            backend=fcfg.tree.split_backend)
        unfold = lambda x: x.reshape((T, M) + x.shape[1:])
        trees = dict(trees, ao_y=jax.tree.map(unfold, ao_y),
                     ao_sum_x=unfold(ao_sum_x))
        trees = fr._fused_member_attempt(fcfg, trees, forest["feat_mask"])

        err_win = stats.merge(forest["err_win"], merged["err"])
        state = dict(forest, trees=trees, err_win=err_win,
                     err_ewma=jnp.where(err_win["n"] > 0, err_win["mean"],
                                        0.0))
        state["vote_w"] = fr.vote_weights(fcfg, state)
        aux = {"mass": merged["ystats"]["n"].sum(),
               "member_mse": state["err_ewma"],
               "n_nodes": trees["n_nodes"]}
    return state, aux


@functools.lru_cache(maxsize=None)
def _dp_sync_jit(fcfg):
    """ONE cached jit of reduce + apply per config — shared by the
    sharded trainer and the single-device reference, so the sync math of
    the two paths is literally the same compiled program (the §4.1
    bit-identity pin).  A profiler names its runs ``jit_dp_sync``."""
    def dp_sync(forest, delta):
        return _dp_apply_sync(fcfg, forest, _dp_reduce_deltas(fcfg, delta))

    return jax.jit(dp_sync)


@functools.lru_cache(maxsize=None)
def _dp_apply_jit(fcfg):
    """Cached jit of the apply half alone (the int8-compressed sync path
    hands it an already-psum-merged delta)."""
    return jax.jit(functools.partial(_dp_apply_sync, fcfg))


def _register_dp_caches():
    """Hook the DP sync jits into the shared ``ops.clear_jit_caches``
    registry, so the one-call-resets-everything contract keeps holding
    (function-scoped import to match the module's import discipline —
    no cycle: the kernel stack never imports train.sharding)."""
    from repro.kernels import ops as kops

    kops.register_jit_cache(_dp_sync_jit)
    kops.register_jit_cache(_dp_apply_jit)


_register_dp_caches()


def _stats_linear(s):
    """Stats -> psum-able linear encoding (n, n·mean, M2 + n·mean²)."""
    s1 = s["n"] * s["mean"]
    return {"n": s["n"], "s1": s1, "s2": s["m2"] + s1 * s["mean"]}


def _stats_delinear(p):
    """Inverse of :func:`_stats_linear` after the sum — the
    cancellation-prone form the robust paths avoid (§3); acceptable here
    because it is the explicitly lossy cheap-shipping mode."""
    n = p["n"]
    mean = jnp.where(n > 0, p["s1"] / jnp.where(n > 0, n, 1.0), 0.0)
    m2 = jnp.maximum(p["s2"] - p["s1"] * mean, 0.0)
    return {"n": n, "mean": mean, "m2": jnp.where(n > 0, m2, 0.0)}


def _dp_gather_int8(fcfg, delta, axis: str):
    """Shard-local delta -> merged delta via int8-quantized psum (§4.2).

    The cheap-shipping path: every shipped plane is linear (Stats ride
    the (n, n·mean, M2-corrected) encoding), int8-quantized per leaf
    with one f32 scale (4x wire traffic cut,
    :func:`repro.optim.compress.quantized_psum`), summed across the
    mesh axis, and decoded back.  Lossy by design — quantization error
    ~ max|plane|/127 per element — so it trades the §4.1 bit-exactness
    for bandwidth; use it when the sync payload, not the math, is the
    bottleneck.
    """
    from repro.optim import compress

    linear = {
        "ystats": _stats_linear(delta["ystats"]),
        "ao_y": _stats_linear(delta["ao_y"]),
        "ao_sum_x": delta["ao_sum_x"],
        "err": _stats_linear(delta["err"]),
    }
    summed = compress.quantized_psum(linear, axis)
    return {
        "ystats": _stats_delinear(summed["ystats"]),
        "ao_y": _stats_delinear(summed["ao_y"]),
        "ao_sum_x": summed["ao_sum_x"],
        "err": _stats_delinear(summed["err"]),
    }


def _no_exchange() -> Dict[str, Any]:
    """A trainer's exchange counts before its first sync."""
    return {"dp_syncs": 0, "dp_exchange_bytes": 0, "dp_exchange_s": 0.0}


@dataclasses.dataclass(frozen=True)
class DataParallelForest:
    """The §4.1 trainer's entry points (both builders return one):

    ``init(key) -> dpstate``; ``update(dpstate, X, y) -> (dpstate,
    aux | None)`` — one global batch, sync when the ``sync_every``
    cadence fires; ``update_window(dpstate, Xw, yw) -> (dpstate, aux)``
    — a whole (S, B, F) window of local batches in ONE dispatch followed
    by an unconditional sync (the deployment shape: shards run
    autonomously between boundaries); ``predict(dpstate, X) -> (B,)``.
    It unpacks as those four: ``init, update, window, predict = ...``.

    ``sync_every``: the global batches between two syncs.  ``exchange``:
    what every sync since the build moved between devices — ``dp_syncs``,
    ``dp_exchange_bytes`` (the shard deltas gathered to the root device
    and the merged forest broadcast back, counted from the shapes) and
    ``dp_exchange_s`` (host seconds inside the ``dp.gather`` and
    ``dp.broadcast`` spans).  A :class:`repro.core.engine.ServingEngine`
    drives either trainer through ``update`` and publishes :meth:`model`.
    """
    init: Callable
    update: Callable
    update_window: Callable
    predict: Callable
    sync_every: int
    exchange: Dict[str, Any]

    def __iter__(self):
        return iter((self.init, self.update, self.update_window,
                     self.predict))

    def model(self, dpstate):
        """The replicated forest state a publish freezes."""
        return dpstate["forest"]


def build_data_parallel_forest(fcfg, mesh: Mesh, axis: str = "data",
                               sync_every: int = 1,
                               compress: str | None = None):
    """Data-parallel stream scale-out (DESIGN.md §4.1).

    The third and last sharding axis: :func:`build_sharded_forest`
    spreads the TREE axis (PR 2), :func:`build_sharded_serving` the
    request batch (PR 4) — this one shards the TRAINING STREAM itself
    over ``D = mesh.shape[axis]`` devices.  Every device owns a
    replicated copy of the forest (topology + quantization grids +
    merged stats) and a private delta; a local step is route/absorb
    only, and every ``sync_every`` batches the deltas gather to the
    mesh's first device, reduce there with the Chan-merge
    (:func:`repro.kernels.ops.forest_merge`), the split attempts execute
    on the merged statistics, and the new forest is broadcast back — so
    the D-shard forest is bit-identical to the single-device execution
    of the same protocol at every sync boundary (pinned by tests
    against :func:`build_data_parallel_reference`).

    ``sync_every`` trades collective traffic for split latency: between
    syncs no leaf can split (statistics keep absorbing; nothing is
    lost — the QO algebra is order-free), so the effective grace period
    is at least ``sync_every`` global batches.  ``compress="int8"``
    ships the deltas int8-quantized over a psum instead of exactly
    (§4.2; lossy, ~4x less wire traffic).  Requires a kernel-capable
    ``split_backend`` (not ``"oracle"``).

    Returns a :class:`DataParallelForest`:

    * ``init(key) -> dpstate`` — device-placed trainer state;
    * ``update(dpstate, X, y) -> (dpstate, aux | None)``
      — learn one global batch of B rows, placed from the host straight
      onto the mesh (D must divide B; device d gets the d-th contiguous
      B/D rows, as ``P(axis)`` gives them).  ``aux`` is None between
      syncs and ``{"mass", "member_mse", "n_nodes"}`` at a boundary;
    * ``update_window(dpstate, Xw, yw) -> (dpstate, aux)`` — a whole
      (S, B, F) window of local batches in ONE dispatch, then an
      unconditional sync;
    * ``predict(dpstate, X) -> (B,)`` — request-sharded vote over the
      replicated forest (no collectives; D must divide B).

    A :class:`repro.core.engine.ServingEngine` drives ``update`` and
    publishes ``model(dpstate)`` every engine ``sync_every`` steps, a
    multiple of this ``sync_every`` (DESIGN.md §5.6).

    Each step is named in a profiler trace: ``dp.local`` (the local
    step's dispatch), and at a sync ``dp.gather`` (the deltas to the root
    device), ``dp.sync`` (merge and apply there, whose ops sit under the
    scopes ``dp.merge`` and ``dp.apply``) and ``dp.broadcast`` (the
    merged forest back to every device).
    """
    from repro.core import forest as fr

    assert fcfg.tree.split_backend != "oracle", \
        "data-parallel training needs a fused backend (oracle is per-row)"
    assert compress in (None, "int8"), compress
    D = mesh.shape[axis]

    abstract = jax.eval_shape(
        lambda: init_data_parallel(fcfg, jax.random.PRNGKey(0), D))
    repl = lambda t: jax.tree.map(lambda a: P(*([None] * a.ndim)), t)
    shardy = lambda t: jax.tree.map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), t)
    fspec = repl(abstract["forest"])
    dspec = shardy(abstract["delta"])
    kspec = P(axis, None, None)
    forest_repl = to_shardings(mesh, fspec)
    delta_shard = to_shardings(mesh, dspec)
    # the sync runs on ONE device: its merge and attempt kernels are
    # Mosaic calls, which XLA cannot partition over a mesh, and on one
    # device it is the very executable the reference runs
    root = SingleDeviceSharding(mesh.devices.flat[0])

    # check_vma off: routing/absorb gathers have no replication rule
    local = jax.jit(jax.shard_map(
        functools.partial(_dp_block, _dp_local_shard, fcfg), mesh=mesh,
        in_specs=(fspec, dspec, kspec, P(axis, None), P(axis)),
        out_specs=(dspec, kspec), check_vma=False))

    window = jax.jit(jax.shard_map(
        functools.partial(_dp_block, _dp_local_window, fcfg), mesh=mesh,
        in_specs=(fspec, dspec, kspec, P(None, axis, None), P(None, axis)),
        out_specs=(dspec, kspec), check_vma=False))

    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    if compress == "int8":
        psum = jax.jit(jax.shard_map(
            lambda delta: _dp_gather_int8(
                fcfg, jax.tree.map(lambda a: a[0], delta), axis),
            mesh=mesh, in_specs=(dspec,),
            out_specs=repl(jax.eval_shape(
                lambda d: jax.tree.map(lambda a: a[0], d),
                abstract["delta"])), check_vma=False))
        gather = lambda delta: jax.device_put(psum(delta), root)
        reduce_apply = _dp_apply_jit(fcfg)
        gathered = 0          # the psum moved it; the root's copy is local
    else:
        # the gather to the root device is the collective; reduce + apply
        # run there through the SAME jit as the reference, and the merged
        # forest is broadcast back (``_synced``)
        gather = lambda delta: jax.device_put(delta, root)
        reduce_apply = _dp_sync_jit(fcfg)
        gathered = nbytes(abstract["delta"]) // D * (D - 1)
    exchange_bytes = gathered + nbytes(abstract["forest"]) * (D - 1)
    rows_in = (NamedSharding(mesh, P(axis, None)),
               NamedSharding(mesh, P(axis)))

    zero_delta = jax.device_put(_dp_init_delta(fcfg, D), delta_shard)

    def init_fn(key):
        st = init_data_parallel(fcfg, key, D)
        return {
            "forest": jax.device_put(st["forest"], forest_repl),
            "delta": jax.device_put(st["delta"], delta_shard),
            "keys": jax.device_put(st["keys"],
                                   NamedSharding(mesh, kspec)),
            "step": 0,
        }

    exchange = _no_exchange()

    def _synced(dpstate, delta, keys, step):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dp.gather"):
            forest = jax.device_put(dpstate["forest"], root)
            delta = gather(delta)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dp.sync"):
            forest, aux = reduce_apply(forest, delta)
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dp.broadcast"):
            forest = jax.device_put(forest, forest_repl)
        t3 = time.perf_counter()
        exchange["dp_syncs"] += 1
        exchange["dp_exchange_bytes"] += exchange_bytes
        exchange["dp_exchange_s"] += (t1 - t0) + (t3 - t2)
        return {"forest": forest,
                "delta": zero_delta, "keys": keys, "step": step}, aux

    def update_fn(dpstate, X, y):
        X, y = jax.device_put((X, y), rows_in)
        with jax.profiler.TraceAnnotation("dp.local"):
            delta, keys = local(dpstate["forest"], dpstate["delta"],
                                dpstate["keys"], X, y)
        step = dpstate["step"] + 1
        if step % sync_every:
            return dict(dpstate, delta=delta, keys=keys, step=step), None
        return _synced(dpstate, delta, keys, step)

    def update_window_fn(dpstate, Xw, yw):
        delta, keys = window(dpstate["forest"], dpstate["delta"],
                             dpstate["keys"], Xw, yw)
        return _synced(dpstate, delta, keys,
                       dpstate["step"] + Xw.shape[0])

    prd = jax.jit(jax.shard_map(
        lambda forest, X: fr.predict(fcfg, forest, X),
        mesh=mesh, in_specs=(fspec, P(axis, None)), out_specs=P(axis),
        check_vma=False))

    return DataParallelForest(init_fn, update_fn, update_window_fn,
                              lambda dpstate, X: prd(dpstate["forest"], X),
                              sync_every, exchange)


def build_data_parallel_reference(fcfg, n_shards: int, sync_every: int = 1):
    """Single-device oracle of :func:`build_data_parallel_forest`.

    The SAME protocol with the shards run one after another on one
    device instead of across a mesh axis — every local step runs the
    identical per-shard program (:func:`_dp_block`) on the identical
    slices, and the sync boundary goes through the very same cached jit
    (:func:`_dp_sync_jit`).  The sharded trainer is pinned bitwise
    against this at every sync boundary (tests/test_dp.py): the mesh
    placement is an execution choice, not a semantics change.
    """
    from repro.core import forest as fr

    assert fcfg.tree.split_backend != "oracle"

    local = jax.jit(functools.partial(_dp_block, _dp_local_shard, fcfg))
    window = jax.jit(functools.partial(_dp_block, _dp_local_window, fcfg))

    def init_fn(key):
        return init_data_parallel(fcfg, key, n_shards)

    def _shardwise(block, dpstate, X, y):
        """Run ``block`` on each shard's contiguous rows (the rows
        ``P(axis)`` gives mesh device i) and restack the shard axis."""
        B = y.shape[-1]
        assert B % n_shards == 0, (B, n_shards)
        b = B // n_shards
        outs = [block(dpstate["forest"],
                      jax.tree.map(lambda a: a[i:i + 1], dpstate["delta"]),
                      dpstate["keys"][i:i + 1],
                      X[..., i * b:(i + 1) * b, :], y[..., i * b:(i + 1) * b])
                for i in range(n_shards)]
        return jax.tree.map(lambda *a: jnp.concatenate(a), *outs)

    exchange = _no_exchange()

    def _synced(dpstate, delta, keys, step):
        forest, aux = _dp_sync_jit(fcfg)(dpstate["forest"], delta)
        exchange["dp_syncs"] += 1             # one device: nothing moves
        return {"forest": forest,
                "delta": _dp_init_delta(fcfg, n_shards),
                "keys": keys, "step": step}, aux

    def update_fn(dpstate, X, y):
        delta, keys = _shardwise(local, dpstate, X, y)
        step = dpstate["step"] + 1
        if step % sync_every:
            return dict(dpstate, delta=delta, keys=keys, step=step), None
        return _synced(dpstate, delta, keys, step)

    def update_window_fn(dpstate, Xw, yw):
        delta, keys = _shardwise(window, dpstate, Xw, yw)
        return _synced(dpstate, delta, keys,
                       dpstate["step"] + Xw.shape[0])

    def predict_fn(dpstate, X):
        return fr.predict(fcfg, dpstate["forest"], X)

    return DataParallelForest(init_fn, update_fn, update_window_fn,
                              predict_fn, sync_every, exchange)

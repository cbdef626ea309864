"""Frozen serving snapshots: the forest's read-optimized twin (DESIGN.md §5.5).

A live :mod:`repro.core.hoeffding` / :mod:`repro.core.forest` state is
write-optimized: fixed ``cfg.max_nodes`` capacity, allocation-ordered
node ids, QO tables and drift windows riding along — none of which the
read path needs.  :func:`freeze` packs a trained state into a
:class:`Snapshot` built for the paper's stated destination (real-time
prediction streams):

* **breadth-first reindex** — nodes renumber level by level, so a
  routing sweep touches a contiguous, front-loaded id range (ply d only
  ever selects ids below level d+1's end) and the hot top of every tree
  shares cache lines;
* **realized trim** — capacity drops from ``cfg.max_nodes`` to the
  nodes actually allocated (bucketed to a power of two so repeated
  freezes of a growing forest reuse compiled programs), and the stored
  ``depth`` is the deepest *realized* leaf, not ``cfg.max_depth`` — the
  routing sweep runs exactly as many plies as the trained tree needs;
* **pre-gathered read state** — leaf means (the predictor) and the
  forest's vote weights (carried by ``forest.update``) are baked in;
  QO tables, target stats and windows are dropped, shrinking serving
  state by ~C·F per node.

:func:`predict_snapshot` serves a snapshot through the §2.6 batched
routing engine with donated, cached jits bucketed on (batch, ply count)
— repeated calls at any request size hit compiled programs, never
retrace.  Predictions are bit-identical to the live state's
``predict`` on every backend: routing decisions are preserved by the
reindex (per-node feature/threshold ride along), gathered means are the
same f32 values, and the forest vote reuses
:func:`repro.core.forest._vote_combine` verbatim.
:func:`repro.train.sharding.build_sharded_serving` wraps the same body
in a batch-axis ``shard_map`` — the read-side complement of the
tree-axis training shard.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops

__all__ = ["Snapshot", "SnapshotValidationError", "freeze",
           "validate_snapshot", "predict_snapshot", "clear_jit_caches"]


@dataclass(frozen=True)
class Snapshot:
    """Dense breadth-first serving layout (a registered pytree).

    Arrays carry a (T, Mr) tree axis even for a single tree (T = 1,
    ``single=True``): ``feature``/``is_leaf`` i32/bool, ``threshold``
    f32, ``child`` (T, Mr, 2) i32 (-1 at leaves), ``leaf_mean`` (T, Mr)
    f32, ``vote_w`` (T,) f32 (ones for a single tree).  ``depth`` (the
    realized ply count) and ``single`` are static aux data, so a
    Snapshot passes through jit/shard_map whole.

    ``version`` / ``step`` are scalar i32 *leaves*, not aux data: a
    publisher stamps every freeze with a monotonically increasing
    version and the trainer step it froze at, and because they ride as
    array leaves (i) re-publishing never changes the treedef — cached
    serving jits and ``build_sharded_serving`` builds stay warm across
    versions — and (ii) they round-trip through
    :class:`repro.checkpoint.ckpt.Checkpointer` by *value*, so staleness
    and rollback tests pin snapshot identity instead of comparing whole
    pytrees.
    """
    feature: jax.Array
    threshold: jax.Array
    child: jax.Array
    is_leaf: jax.Array
    leaf_mean: jax.Array
    vote_w: jax.Array
    depth: int
    single: bool
    version: jax.Array | int = 0
    step: jax.Array | int = 0


jax.tree_util.register_pytree_node(
    Snapshot,
    lambda s: ((s.feature, s.threshold, s.child, s.is_leaf, s.leaf_mean,
                s.vote_w, s.version, s.step), (s.depth, s.single)),
    lambda aux, ch: Snapshot(*ch[:6], *aux, *ch[6:]))


class SnapshotValidationError(ValueError):
    """A Snapshot violates the serving invariants (torn/corrupt model)."""


def validate_snapshot(snap: Snapshot) -> Snapshot:
    """Check the serving invariants; raise :class:`SnapshotValidationError`.

    The publish gate of the continuous-serving engine (DESIGN.md §5.6):
    every snapshot must satisfy, per tree,

    * finite thresholds and in-range feature ids on internal nodes;
    * children ids inside ``[0, Mr)``, each strictly greater than its
      parent's id and claimed by exactly one parent, root never a child
      — the BFS level-order contract :func:`_bfs_reindex` establishes;
    * ``-1`` children at leaves (pad rows are self-contained leaves);
    * finite leaf means and finite, non-negative vote weights;
    * non-negative ``version`` / ``step`` stamps.

    A host-side O(T·Mr) numpy pass — called once per freeze/publish,
    never on the per-request path.  Returns ``snap`` unchanged so
    callers can gate inline: ``publish(validate_snapshot(s))``.  Each
    call is one ``serve.validate`` profiler span.
    """
    with jax.profiler.TraceAnnotation("serve.validate"):
        feat = np.asarray(snap.feature)
        thr = np.asarray(snap.threshold)
        child = np.asarray(snap.child)
        is_leaf = np.asarray(snap.is_leaf)
        mean = np.asarray(snap.leaf_mean)
        vote_w = np.asarray(snap.vote_w)
        T, Mr = feat.shape

        def bad(msg):
            raise SnapshotValidationError(
                f"snapshot v{int(np.asarray(snap.version))} "
                f"(step {int(np.asarray(snap.step))}): {msg}")

        if not (np.isfinite(vote_w).all() and (vote_w >= 0).all()):
            bad("vote weights must be finite and non-negative")
        if not np.isfinite(mean).all():
            bad("leaf means must be finite")
        if int(np.asarray(snap.version)) < 0 or int(np.asarray(snap.step)) < 0:
            bad("version/step stamps must be non-negative")
        for t in range(T):
            internal = ~is_leaf[t]
            if not np.isfinite(thr[t][internal]).all():
                bad(f"tree {t}: non-finite threshold on an internal node")
            if internal.any() and (feat[t][internal] < 0).any():
                bad(f"tree {t}: negative feature id on an internal node")
            ch = child[t][internal]                       # (n_internal, 2)
            if (child[t][~internal] != -1).any():
                bad(f"tree {t}: leaf rows must carry -1 children")
            if internal.any():
                if ch.min() < 0 or ch.max() >= Mr:
                    bad(f"tree {t}: child id out of range [0, {Mr})")
                parents = np.nonzero(internal)[0]
                if (ch <= parents[:, None]).any():
                    bad(f"tree {t}: child id <= parent id breaks the BFS "
                        f"level-order contract")
                flat = ch.reshape(-1)
                if len(np.unique(flat)) != len(flat) or (flat == 0).any():
                    bad(f"tree {t}: a node is claimed by two parents (or the "
                        f"root is a child)")
    return snap


def _bfs_reindex(feature, threshold, child, is_leaf, mean, Mr: int):
    """One tree's numpy arrays -> breadth-first arrays of capacity Mr.

    Walks the realized tree from the root (unallocated capacity is
    unreachable by construction and simply dropped).  Pad rows are
    self-contained leaves (mean 0) that routing can never reach.
    Returns the reindexed arrays + the realized depth.
    """
    order, node_depth = [0], [0]
    new_id = {0: 0}
    head = 0
    while head < len(order):
        u = order[head]
        head += 1
        if not is_leaf[u]:
            for c in child[u]:
                new_id[int(c)] = len(order)
                order.append(int(c))
                node_depth.append(node_depth[new_id[u]] + 1)
    n = len(order)
    assert n <= Mr, (n, Mr)
    f = np.zeros(Mr, np.int32)
    thr = np.zeros(Mr, np.float32)
    ch = np.full((Mr, 2), -1, np.int32)
    lf = np.ones(Mr, bool)
    mu = np.zeros(Mr, np.float32)
    for i, u in enumerate(order):
        f[i], thr[i], lf[i] = feature[u], threshold[u], is_leaf[u]
        mu[i] = mean[u] if is_leaf[u] else 0.0
        if not is_leaf[u]:
            ch[i] = [new_id[int(child[u][0])], new_id[int(child[u][1])]]
    return f, thr, ch, lf, mu, (max(node_depth) if n else 0)


def freeze(state, *, version: int = 0, step: int = 0,
           timings: dict | None = None) -> Snapshot:
    """Pack a trained tree or forest state into a serving Snapshot.

    ``state``: a :func:`repro.core.hoeffding.init_state` pytree (single
    tree) or a :func:`repro.core.forest.init_forest` pytree (detected by
    its ``"trees"`` key; the carried ``vote_w`` is read for free).  A
    host-side packing step — arrays must be concrete (freeze at the
    train/serve boundary, not inside a jit) — whose planes all go
    through the host, so the snapshot lives on the default device
    wherever the state lies (a data-parallel forest is replicated over
    its mesh).  Capacity is trimmed to the
    realized node count (power-of-two bucketed, min 8) and ``depth`` to
    the deepest realized leaf across members.

    ``version``/``step``: the publisher's identity stamps (monotone
    version counter, trainer step frozen at) — scalar i32 leaves on the
    returned snapshot.  Every freeze runs :func:`validate_snapshot`
    before returning, so a snapshot that ever reaches a serving engine
    is structurally valid by construction; the engine's publish path
    re-validates after its fault-injection hooks (the rollback gate).

    The packing is the profiler span ``serve.freeze``, with the children
    ``serve.freeze.fetch`` (the device-to-host reads, which first wait
    for every step still queued on the device), ``serve.freeze.reindex``
    and ``serve.freeze.upload``; the validation after it is its own
    ``serve.validate`` span.  ``timings``: a dict that, when given,
    receives the seconds of those three children under ``"fetch"``,
    ``"reindex"`` and ``"upload"``.
    """
    if "trees" in state:
        trees, vote_w, single = state["trees"], state["vote_w"], False
    else:
        trees = jax.tree.map(lambda a: a[None], state)
        vote_w, single = jnp.ones((1,), jnp.float32), True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("serve.freeze"):
        with jax.profiler.TraceAnnotation("serve.freeze.fetch"):
            feat = np.asarray(trees["feature"])
            thr = np.asarray(trees["threshold"])
            child = np.asarray(trees["child"])
            is_leaf = np.asarray(trees["is_leaf"])
            mean = np.asarray(trees["ystats"]["mean"])
            n_nodes = np.asarray(trees["n_nodes"])
            # through the host like every plane: a data-parallel forest's
            # weights are replicated over its mesh, and a snapshot lives
            # on one device
            vote_w = np.asarray(vote_w, np.float32)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.freeze.reindex"):
            Mr = 8
            while Mr < int(n_nodes.max()):
                Mr *= 2
            packed = [_bfs_reindex(feat[t], thr[t], child[t], is_leaf[t],
                                   mean[t], Mr)
                      for t in range(feat.shape[0])]
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.freeze.upload"):
            stack = lambda i: jnp.asarray(np.stack([p[i] for p in packed]))
            snap = Snapshot(
                feature=stack(0), threshold=stack(1), child=stack(2),
                is_leaf=stack(3), leaf_mean=stack(4),
                vote_w=jnp.asarray(vote_w),
                depth=max(p[5] for p in packed), single=single,
                version=jnp.asarray(version, jnp.int32),
                step=jnp.asarray(step, jnp.int32))
        t3 = time.perf_counter()
    if timings is not None:
        timings.update(fetch=t1 - t0, reindex=t2 - t1, upload=t3 - t2)
    return validate_snapshot(snap)


def _predict_impl(feature, threshold, child, is_leaf, leaf_mean, vote_w, X,
                  *, plies: int, backend: str, single: bool):
    """Route -> gather -> (vote): the whole read path, one fused body."""
    from repro.core.forest import _vote_combine
    leaf = kops.forest_route(feature, threshold, child, is_leaf, X,
                             depth=plies, backend=backend)
    member = jnp.take_along_axis(leaf_mean, leaf, axis=1)        # (T, B)
    if single:
        return member[0]
    return _vote_combine(member, vote_w, None)


@kops.register_jit_cache
@functools.lru_cache(maxsize=None)
def _jit_predict(backend: str, plies: int, single: bool):
    """Keyed handle for one (backend, ply-bucket) serving program (the
    ``_cache_size()``/``cache_info()`` regression hook); delegates to
    the shared :func:`repro.kernels.ops._dispatch` with ``donate_x`` —
    the X buffer is donated so XLA can reuse it for the sweep's
    node-state temporaries; :func:`predict_snapshot` guarantees the
    donated buffer is engine-owned (its pad copy, or an explicit device
    copy).  XLA:CPU cannot alias donated buffers (it would only warn per
    compile), so donation engages on TPU only — the shared factory's
    donation policy."""
    return kops._dispatch(_predict_impl, donate_x=True, plies=plies,
                          backend=backend, single=single)


def predict_snapshot(snap: Snapshot, X, *,
                     backend: str | None = None) -> jax.Array:
    """Serve a frozen snapshot: X (B, F) -> (B,) f32 predictions.

    Bit-identical to ``hoeffding.predict`` / ``forest.predict`` on the
    live state that was frozen, on every backend.  Concrete requests pad
    to their batch-ladder bucket and dispatch through donated cached
    jits keyed on (backend, realized-depth bucket) — a steady request
    stream never recompiles (``_jit_predict(...)._cache_size()`` is the
    regression hook).  The ladder and ply rounding are the tuned
    ``forest_route`` schedule knobs (the predict program IS a routing
    sweep plus a gather), so one tuning entry steers route and serve
    together.  Only an engine-owned buffer is ever donated: the padded
    copy when padding happened, else (TPU only) a defensive device copy
    of X — the caller's array is never consumed out from under a later
    reuse.  Under an enclosing trace the body inlines.  Each call is one
    ``serve.predict`` profiler span.
    """
    with jax.profiler.TraceAnnotation("serve.predict"):
        backend = kops.resolve_backend(backend)
        X = jnp.asarray(X, jnp.float32)
        tabs = (snap.feature, snap.threshold, snap.child, snap.is_leaf,
                snap.leaf_mean, snap.vote_w)
        if kops._is_traced(*tabs, X):
            return _predict_impl(*tabs, X, plies=snap.depth, backend=backend,
                                 single=snap.single)
        T, Mr = snap.feature.shape
        p = kops.tuned("forest_route", backend,
                       kops._shape_class_route(T, Mr, int(X.shape[1])))
        X, B, padded = kops.pad_rows(X, 128, p["batch_ladder"])
        if not padded and jax.default_backend() == "tpu":
            X = jnp.copy(X)     # donate our copy, not the caller's buffer
        out = _jit_predict(backend, kops.depth_bucket(snap.depth,
                                                      p["ply_round"]),
                           snap.single)(*tabs, X)
        return out[:B] if padded else out


def clear_jit_caches() -> None:
    """Drop the cached serving jits (test hook; resets ``_cache_size``).
    Delegates to the shared :func:`repro.kernels.ops.clear_jit_caches`
    hook (this module's factory is registered there), so one call resets
    the whole process."""
    kops.clear_jit_caches()

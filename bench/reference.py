"""Plain reference of the online-bagged QO Hoeffding forest regressor.

The semantics the benchmark holds the program to, written out once in
straightforward ``jax.numpy`` and kept with the benchmark, so no change to
the program can move them.  It imports nothing of the program under test.

It covers the deployment path the cells run: online bagging with
Poisson(lam) weights drawn from per-member keys, random feature subspaces,
the Quantization Observer's dense bin tables (``floor((x - origin) / r)``
shifted to the middle bin and clipped), the grace-period attempt schedule,
the Hoeffding ratio test with its tie break, child allocation in pairs
with statistics inherited from the split halves and radii from the parent's
bins, the ADWIN-style drift swap of the worst signalling member, and the
inverse-error vote.  Every table is scanned in full (no compaction) and
every leaf of every member is routed by a plain per-ply walk.

``dtype`` sets the precision of every statistic and of the inputs:
``float32`` is the reference, a lower one is the control that the
comparison has to reject.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Forest:
    """Sizes and constants of one forest deployment."""
    n_trees: int
    max_nodes: int
    n_features: int
    n_bins: int
    grace_period: float = 200.0
    delta: float = 1e-4
    tau: float = 0.05
    max_depth: int = 12
    r0: float = 0.05
    sigma_k: float = 2.0
    lam: float = 6.0
    subspace: float = 0.7
    vote_power: float = 4.0
    drift_alpha: float = 0.5
    drift_decay: float = 0.9
    drift_kappa: float = 3.0
    drift_min_batches: int = 8

    @property
    def subspace_k(self) -> int:
        return max(1, int(round(self.subspace * self.n_features)))


# --------------------------------------------------------------------------
# (n, mean, M2) statistics
# --------------------------------------------------------------------------

def _zeros(shape, dtype):
    z = jnp.zeros(shape, dtype)
    return {"n": z, "mean": z, "m2": z}


def _merge(a, b):
    n = a["n"] + b["n"]
    safe = jnp.where(n > 0, n, 1.0)
    d = b["mean"] - a["mean"]
    mean = (a["n"] * a["mean"] + b["n"] * b["mean"]) / safe
    m2 = a["m2"] + b["m2"] + d * d * (a["n"] * b["n"]) / safe
    return {"n": n, "mean": jnp.where(n > 0, mean, 0.0),
            "m2": jnp.where(n > 0, m2, 0.0)}


def _variance(s):
    d = s["n"] - 1.0
    return jnp.where(d > 0, s["m2"] / jnp.where(d > 0, d, 1.0), 0.0)


def _observe(s, y, w):
    n = s["n"] + w
    safe = jnp.where(n > 0, n, 1.0)
    d = y - s["mean"]
    mean = s["mean"] + w * d / safe
    return {"n": n, "mean": mean, "m2": s["m2"] + w * d * (y - mean)}


def _segment_stats(v, seg, num, w):
    n = jax.ops.segment_sum(w, seg, num)
    sy = jax.ops.segment_sum(w * v, seg, num)
    mean = jnp.where(n > 0, sy / jnp.where(n > 0, n, 1.0), 0.0)
    m2 = jax.ops.segment_sum(w * (v - mean[seg]) ** 2, seg, num)
    return {"n": n, "mean": mean, "m2": jnp.where(n > 0, m2, 0.0)}


# --------------------------------------------------------------------------
# state
# --------------------------------------------------------------------------

def _draw_mask(key, F, k):
    perm = jax.random.permutation(key, F)
    return jnp.zeros((F,), bool).at[perm[:k]].set(True)


def init(cfg: Forest, key, dtype=jnp.float32) -> dict:
    """Fresh forest: T single-root members, every leaf with the tree axis
    first.  ``key`` is a raw uint32 PRNG key."""
    T, M, F, C = cfg.n_trees, cfg.max_nodes, cfg.n_features, cfg.n_bins
    keys = jax.random.split(key, T + 1)
    masks = jax.vmap(functools.partial(_draw_mask, F=F, k=cfg.subspace_k))(
        keys[1:])
    trees = {
        "feature": jnp.zeros((T, M), jnp.int32),
        "threshold": jnp.zeros((T, M), dtype),
        "child": jnp.full((T, M, 2), -1, jnp.int32),
        "is_leaf": jnp.zeros((T, M), bool).at[:, 0].set(True),
        "depth": jnp.zeros((T, M), jnp.int32),
        "ystats": _zeros((T, M), dtype),
        "ao_sum_x": jnp.zeros((T, M, F, C), dtype),
        "ao_y": _zeros((T, M, F, C), dtype),
        "ao_radius": jnp.full((T, M, F), cfg.r0, dtype),
        "ao_origin": jnp.zeros((T, M, F), dtype),
        "seen_since_attempt": jnp.zeros((T, M), dtype),
        "n_nodes": jnp.ones((T,), jnp.int32),
    }
    return {"trees": trees, "feat_mask": masks,
            "keys": jax.random.split(keys[0], T),
            "err_win": _zeros((T,), dtype),
            "err_ewma": jnp.zeros((T,), dtype),
            "vote_w": jnp.zeros((T,), dtype),
            "resets": jnp.zeros((T,), jnp.int32)}


# --------------------------------------------------------------------------
# read path
# --------------------------------------------------------------------------

def route(trees, X, plies: int):
    """(T, B) leaf id of every row in every member: a plain walk, one ply
    at a time (x <= threshold goes left; a NaN goes right)."""
    T = trees["feature"].shape[0]
    B = X.shape[0]
    node = jnp.zeros((T, B), jnp.int32)
    rows = jnp.arange(B)[None, :]
    take = lambda a: jnp.take_along_axis(a, node, axis=1)
    for _ in range(plies):
        f = take(trees["feature"])
        go_left = X[rows, f] <= take(trees["threshold"])
        nxt = jnp.where(go_left, take(trees["child"][..., 0]),
                        take(trees["child"][..., 1]))
        node = jnp.where(take(trees["is_leaf"]), node, nxt)
    return node


def _vote(yhat, w):
    return (w[:, None] * yhat).sum(0) / jnp.maximum(w.sum(), 1e-12)


def predict(cfg: Forest, state, X):
    """(B,) vote-weighted mean of the members' leaf means."""
    X = jnp.asarray(X, state["trees"]["threshold"].dtype)
    leaf = route(state["trees"], X, cfg.max_depth)
    yhat = jnp.take_along_axis(state["trees"]["ystats"]["mean"], leaf, 1)
    return _vote(yhat, state["vote_w"])


# --------------------------------------------------------------------------
# write path
# --------------------------------------------------------------------------

def _poisson_cdf(lam: float, tail: float = 1e-7):
    """[P(X <= 0), P(X <= 1), ...] up to 1 - tail."""
    cdf, p, k, c = [], math.exp(-lam), 0, math.exp(-lam)
    while c < 1.0 - tail and k < 64:
        cdf.append(c)
        k += 1
        p *= lam / k
        c += p
    cdf.append(c)
    return cdf


def _absorb(trees, gl, X, y, w):
    """Fold the routed batch into every (leaf, feature) bin table."""
    T, M, F, C = trees["ao_sum_x"].shape
    N = T * M
    r = trees["ao_radius"].reshape(N, F)[gl]
    o = trees["ao_origin"].reshape(N, F)[gl]
    bins = jnp.clip(jnp.floor((X - o) / r).astype(jnp.int32) + C // 2,
                    0, C - 1)
    seg = ((gl[:, None] * F + jnp.arange(F)[None, :]) * C + bins).reshape(-1)
    wr, yr = jnp.repeat(w, F), jnp.repeat(y, F)
    acc = jax.ops.segment_sum(jnp.stack([wr, wr * yr, wr * X.reshape(-1)], 1),
                              seg, N * F * C)
    n, sy, sx = acc[:, 0], acc[:, 1], acc[:, 2]
    mean = jnp.where(n > 0, sy / jnp.where(n > 0, n, 1.0), 0.0)
    m2 = jax.ops.segment_sum(wr * (yr - mean[seg]) ** 2, seg, N * F * C)
    shape = (T, M, F, C)
    tile = {"n": n.reshape(shape), "mean": mean.reshape(shape),
            "m2": jnp.where(n > 0, m2, 0.0).reshape(shape)}
    return _merge(trees["ao_y"], tile), trees["ao_sum_x"] + sx.reshape(shape)


def _query(ao_y, ao_sum_x, attempt):
    """Best boundary of every (leaf, feature) table: variance reduction of
    each cut between consecutive occupied bins, cut at the midpoint of
    their prototypes.  Returns (merit, threshold), -inf merit where the
    leaf does not attempt or no boundary exists."""
    n, mean, m2 = ao_y["n"], ao_y["mean"], ao_y["m2"]
    C = n.shape[-1]
    occ = n > 0
    grand = (n * mean).sum(-1, keepdims=True) / jnp.maximum(
        n.sum(-1, keepdims=True), 1.0)
    mu = mean - grand
    Nl = jnp.cumsum(n, -1)
    SYl = jnp.cumsum(n * mu, -1)
    SQl = jnp.cumsum(m2 + n * mu * mu, -1)
    Nt, SYt, SQt = Nl[..., -1:], SYl[..., -1:], SQl[..., -1:]

    def var(NN, SY, SQ):
        m = jnp.maximum(SQ - SY * SY / jnp.where(NN > 0, NN, 1.0), 0.0)
        return jnp.where(NN > 1, m / jnp.where(NN > 1, NN - 1.0, 1.0), 0.0)

    ntot = jnp.maximum(Nt, 1.0)
    vr = var(Nt, SYt, SQt) - (Nl / ntot) * var(Nl, SYl, SQl) \
        - ((Nt - Nl) / ntot) * var(Nt - Nl, SYt - SYl, SQt - SQl)
    idx = jnp.arange(C)
    last = jax.lax.cummax(jnp.where(occ, idx, -1), axis=n.ndim - 1)
    first = jax.lax.cummin(jnp.where(occ, idx, C), axis=n.ndim - 1,
                           reverse=True)
    nxt = jnp.concatenate([first[..., 1:], jnp.full(first[..., :1].shape, C)],
                          -1)
    ok = (last >= 0) & (nxt < C) & attempt[..., None, None]
    proto = jnp.where(occ, ao_sum_x / jnp.where(occ, n, 1.0), 0.0)
    cand = 0.5 * (jnp.take_along_axis(proto, jnp.maximum(last, 0), -1)
                  + jnp.take_along_axis(proto, jnp.minimum(nxt, C - 1), -1))
    score = jnp.where(ok, vr, -jnp.inf)
    best = jnp.argmax(score, -1)
    return (jnp.max(score, -1),
            jnp.take_along_axis(cand, best[..., None], -1)[..., 0])


def _split_tree(cfg: Forest, tr, merit, thr_all, attempt, feat_mask):
    """Hoeffding decision and child allocation for one member."""
    M = cfg.max_nodes
    merit = jnp.where(jnp.isnan(merit), -jnp.inf, merit)
    merit = jnp.where(feat_mask[None, :], merit, -jnp.inf)
    best_f = jnp.argmax(merit, axis=1)
    top2 = jax.lax.top_k(merit, 2)[0]
    vr1, vr2 = top2[:, 0], top2[:, 1]
    n_leaf = jnp.maximum(tr["ystats"]["n"], 1.0)
    eps = jnp.sqrt(jnp.log(1.0 / cfg.delta) / (2.0 * n_leaf))
    ratio = jnp.where(vr1 > 0, jnp.maximum(vr2, 0.0) / vr1, 1.0)
    want = attempt & ((ratio < 1.0 - eps) | (eps < cfg.tau)) \
        & jnp.isfinite(vr1) & (vr1 > 0) \
        & (jnp.isfinite(merit).sum(1) >= 2)
    best_c = thr_all[jnp.arange(M), best_f]

    k = jnp.cumsum(want.astype(jnp.int32)) - 1
    base = tr["n_nodes"] + 2 * k
    can = want & (base + 1 < M)
    lidx = jnp.where(can, jnp.arange(M), M)
    c0i, c1i = jnp.where(can, base, M), jnp.where(can, base + 1, M)
    kids = jnp.concatenate([c0i, c1i])

    st = dict(tr)
    st["feature"] = st["feature"].at[lidx].set(best_f, mode="drop")
    st["threshold"] = st["threshold"].at[lidx].set(best_c, mode="drop")
    st["child"] = st["child"].at[lidx].set(jnp.stack([base, base + 1], 1),
                                           mode="drop")
    st["child"] = st["child"].at[kids].set(-1, mode="drop")
    st["is_leaf"] = st["is_leaf"].at[lidx].set(False, mode="drop") \
        .at[kids].set(True, mode="drop")
    st["seen_since_attempt"] = st["seen_since_attempt"].at[
        jnp.concatenate([lidx, kids])].set(0.0, mode="drop")
    st["depth"] = st["depth"].at[kids].set(jnp.tile(tr["depth"] + 1, 2),
                                           mode="drop")

    # children inherit the statistics of the split halves of the winning
    # feature's bins
    rows = jnp.arange(M)
    bn = tr["ao_y"]["n"][rows, best_f]
    bmean = tr["ao_y"]["mean"][rows, best_f]
    bm2 = tr["ao_y"]["m2"][rows, best_f]
    occ = bn > 0
    proto = jnp.where(occ, tr["ao_sum_x"][rows, best_f]
                      / jnp.where(occ, bn, 1.0), jnp.inf)
    left_m = (occ & (proto <= best_c[:, None])).astype(bn.dtype)
    right_m = occ.astype(bn.dtype) - left_m

    def side(mask):
        nn = (mask * bn).sum(-1)
        sy = (mask * bn * bmean).sum(-1)
        mean = jnp.where(nn > 0, sy / jnp.where(nn > 0, nn, 1.0), 0.0)
        m2 = (mask * bm2).sum(-1) \
            + (mask * bn * (bmean - mean[:, None]) ** 2).sum(-1)
        return {"n": nn, "mean": mean, "m2": jnp.where(nn > 0, m2, 0.0)}

    left, right = side(left_m), side(right_m)
    st["ystats"] = jax.tree.map(
        lambda a, l, r: a.at[kids].set(jnp.concatenate([l, r]), mode="drop"),
        st["ystats"], left, right)

    # child quantization: radius sigma_x / k and origin mean_x of the
    # parent's per-feature prototypes
    on = tr["ao_y"]["n"]
    pr = jnp.where(on > 0, tr["ao_sum_x"] / jnp.maximum(on, 1.0), 0.0)
    n_f = on.sum(-1)
    mean_x = (on * pr).sum(-1) / jnp.maximum(n_f, 1.0)
    var_x = (on * (pr - mean_x[..., None]) ** 2).sum(-1) \
        / jnp.maximum(n_f - 1.0, 1.0)
    child_r = jnp.maximum(jnp.sqrt(jnp.maximum(var_x, 1e-12)) / cfg.sigma_k,
                          1e-6)
    st["ao_radius"] = st["ao_radius"].at[kids].set(jnp.tile(child_r, (2, 1)),
                                                   mode="drop")
    st["ao_origin"] = st["ao_origin"].at[kids].set(jnp.tile(mean_x, (2, 1)),
                                                   mode="drop")
    st["ao_sum_x"] = st["ao_sum_x"].at[kids].set(0.0, mode="drop")
    st["ao_y"] = jax.tree.map(lambda a: a.at[kids].set(0.0, mode="drop"),
                              st["ao_y"])
    st["n_nodes"] = tr["n_nodes"] + 2 * can.sum().astype(jnp.int32)
    st["seen_since_attempt"] = jnp.where(attempt & ~can, 0.0,
                                         st["seen_since_attempt"])
    return st


def learn_members(cfg: Forest, trees, feat_mask, X, y, w):
    """Route, fold statistics in, attempt splits: every member at once.
    ``w``: (T, B) bagging weights.  Returns the trees and the number of
    leaves that attempted a split."""
    T, M = cfg.n_trees, cfg.max_nodes
    leaf = route(trees, X, cfg.max_depth)
    gl = (jnp.arange(T, dtype=jnp.int32)[:, None] * M + leaf).reshape(-1)
    batch_leaf = jax.tree.map(
        lambda a: a.reshape(T, M),
        _segment_stats(jnp.tile(y, T), gl, T * M, w.reshape(-1)))
    trees = dict(trees, ystats=_merge(trees["ystats"], batch_leaf),
                 seen_since_attempt=trees["seen_since_attempt"]
                 + batch_leaf["n"])
    ao_y, ao_sum_x = _absorb(trees, gl, jnp.tile(X, (T, 1)), jnp.tile(y, T),
                             w.reshape(-1))
    trees = dict(trees, ao_y=ao_y, ao_sum_x=ao_sum_x)
    attempt = trees["is_leaf"] \
        & (trees["seen_since_attempt"] >= cfg.grace_period) \
        & (trees["depth"] < cfg.max_depth) \
        & (trees["n_nodes"][:, None] + 1 < M)
    merit, thr = _query(trees["ao_y"], trees["ao_sum_x"], attempt)
    return jax.vmap(functools.partial(_split_tree, cfg))(
        trees, merit, thr, attempt, feat_mask), attempt.sum()


def _vote_weights(cfg: Forest, err_win, err_ewma):
    return jnp.where(err_win["n"] > 0,
                     (1.0 / (err_ewma + 1e-6)) ** cfg.vote_power, 0.0)


def step(cfg: Forest, state, X, y):
    """Learn one batch, test then train: returns (state, prequential
    forest MSE of the batch, leaves that attempted a split)."""
    dtype = state["trees"]["threshold"].dtype
    X = jnp.asarray(X, dtype)
    y = jnp.asarray(y, dtype).reshape(-1)
    T, B = cfg.n_trees, y.shape[0]
    trees = state["trees"]
    leaf = route(trees, X, cfg.max_depth)
    yhat = jnp.take_along_axis(trees["ystats"]["mean"], leaf, 1)
    member_mse = ((yhat - y[None, :]) ** 2).sum(1) / B
    forest_mse = ((_vote(yhat, state["vote_w"]) - y) ** 2).sum() / B

    split = jax.vmap(functools.partial(jax.random.split, num=3))(
        state["keys"])
    keys, wkeys, mkeys = split[:, 0], split[:, 1], split[:, 2]
    cdf = jnp.asarray(_poisson_cdf(cfg.lam), jnp.float32)
    w = jax.vmap(lambda k: (jax.random.uniform(k, (B,))[:, None] >= cdf)
                 .sum(-1))(wkeys).astype(dtype)
    trees, attempts = learn_members(cfg, trees, state["feat_mask"], X, y, w)

    alpha = cfg.drift_alpha
    ref = state["err_win"]
    ewma = jnp.where(ref["n"] < 0.5, member_mse,
                     (1.0 - alpha) * state["err_ewma"] + alpha * member_mse)
    sd = jnp.sqrt(jnp.maximum(_variance(ref), 1e-12))
    signal = (ref["n"] >= cfg.drift_min_batches) \
        & (ewma > ref["mean"] + cfg.drift_kappa * sd)
    worst = jnp.argmax(jnp.where(signal, ewma, -jnp.inf))
    drift = signal & (jnp.arange(T) == worst)
    decayed = {"n": cfg.drift_decay * ref["n"], "mean": ref["mean"],
               "m2": cfg.drift_decay * ref["m2"]}
    observed = _observe(decayed, member_mse, 1.0)
    win = jax.tree.map(lambda o, r: jnp.where(signal, r, o), observed, ref)

    fresh = init(cfg, jax.random.PRNGKey(0), dtype)["trees"]
    trees = jax.tree.map(
        lambda a, f: jnp.where(drift.reshape((T,) + (1,) * (a.ndim - 1)),
                               f, a), trees, fresh)
    masks = jax.vmap(functools.partial(
        _draw_mask, F=cfg.n_features, k=cfg.subspace_k))(mkeys)
    err_win = jax.tree.map(lambda a: jnp.where(drift, 0.0, a), win)
    err_ewma = jnp.where(drift, 0.0, ewma)
    return {"trees": trees,
            "feat_mask": jnp.where(drift[:, None], masks, state["feat_mask"]),
            "keys": keys, "err_win": err_win, "err_ewma": err_ewma,
            "vote_w": _vote_weights(cfg, err_win, err_ewma),
            "resets": state["resets"] + drift.astype(jnp.int32)}, \
        forest_mse, attempts

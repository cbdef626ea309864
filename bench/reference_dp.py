"""Plain reference of the data-parallel forest protocol.

The semantics the four-chip cell holds the program to, written out in
straightforward ``jax.numpy`` on top of the one-chip reference
(``reference.py``), whose route, absorb, query and split it reuses.  It
imports nothing of the program under test.

The protocol is Ben-Haim & Tom-Tov's (2010, JMLR 11): W workers each
summarise their share of the stream, and each round a master merges the
summaries and decides the splits.  Here a worker is a shard: per global
batch it draws its own Poisson(lam) bagging weights, routes its rows
through the shared (frozen) trees, adds the members' prequential squared
errors and absorbs the rows into a delta of leaf and bin statistics.  At
a sync the master merges the deltas (in any order; the merge is exact),
folds them into the forest, attempts splits on the merged tables and
refreshes the error windows and vote weights; every worker then routes
against the new forest.

Keys: ``split(key) -> (kf, kd)``; the forest is ``reference.init`` of
``kf``, and ``kd`` splits into one key per (shard, member), each split in
two every global batch (carried key, weight key).

Where this departs from the published algorithm, and from the one-chip
forest (``reference.step``):

* the workers' summaries are the Quantization Observer's tables on a
  grid every worker shares, merged exactly by Chan's formula, where the
  source compresses each histogram to a fixed number of bins;
* no split happens between syncs, and no member is swapped for drift:
  members are frozen between merges (the stream is stationary);
* the short error window ``err_ewma`` is the merged running mean of the
  prequential errors, since per-worker EWMAs cannot be merged in order;
* a worker's prequential error is over its raw rows, unweighted.

``dtype`` sets the precision of every statistic and of the inputs, as
in ``reference.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import reference as R


def init(cfg: R.Forest, key, shards: int, dtype=jnp.float32) -> dict:
    """The forest and the (shards, T, 2) worker keys."""
    kf, kd = jax.random.split(key)
    keys = jax.random.split(kd, shards * cfg.n_trees).reshape(
        shards, cfg.n_trees, 2)
    return {"forest": R.init(cfg, kf, dtype), "keys": keys}


def zero_delta(cfg: R.Forest, dtype=jnp.float32) -> dict:
    """A worker's statistics since the last sync, all empty."""
    T, M, F, C = cfg.n_trees, cfg.max_nodes, cfg.n_features, cfg.n_bins
    return {"ystats": R._zeros((T, M), dtype),
            "ao_y": R._zeros((T, M, F, C), dtype),
            "ao_sum_x": jnp.zeros((T, M, F, C), dtype),
            "err": R._zeros((T,), dtype)}


def poisson_weights(cfg: R.Forest, keys, rows: int):
    """Split each (T, 2) worker key in two and draw Poisson(lam) weights
    for ``rows`` rows from the second half: ((T, 2) keys, (T, rows))."""
    split = jax.vmap(functools.partial(jax.random.split, num=2))(keys)
    cdf = jnp.asarray(R._poisson_cdf(cfg.lam), jnp.float32)
    w = jax.vmap(lambda k: (jax.random.uniform(k, (rows,))[:, None] >= cdf)
                 .sum(-1))(split[:, 1])
    return split[:, 0], w


def local(cfg: R.Forest, forest, delta, keys, X, y):
    """One worker's global batch: weights, route, errors, absorb.  Returns
    the worker's (delta, keys)."""
    trees = forest["trees"]
    dtype = trees["threshold"].dtype
    X = jnp.asarray(X, dtype)
    y = jnp.asarray(y, dtype).reshape(-1)
    T, M = cfg.n_trees, cfg.max_nodes
    keys, w = poisson_weights(cfg, keys, y.shape[0])
    w = w.astype(dtype)
    leaf = R.route(trees, X, cfg.max_depth)
    gl = (jnp.arange(T, dtype=jnp.int32)[:, None] * M + leaf).reshape(-1)
    batch_leaf = jax.tree.map(
        lambda a: a.reshape(T, M),
        R._segment_stats(jnp.tile(y, T), gl, T * M, w.reshape(-1)))
    yhat = jnp.take_along_axis(trees["ystats"]["mean"], leaf, 1)
    sq = (yhat - y[None, :]) ** 2
    mean = sq.mean(1)
    err = {"n": jnp.full((T,), sq.shape[1], dtype), "mean": mean,
           "m2": ((sq - mean[:, None]) ** 2).sum(1)}
    ao_y, ao_sum_x = R._absorb(
        dict(trees, ao_y=delta["ao_y"], ao_sum_x=delta["ao_sum_x"]),
        gl, jnp.tile(X, (T, 1)), jnp.tile(y, T), w.reshape(-1))
    return {"ystats": R._merge(delta["ystats"], batch_leaf),
            "ao_y": ao_y, "ao_sum_x": ao_sum_x,
            "err": R._merge(delta["err"], err)}, keys


def merge(a, b):
    """Two workers' deltas as one (Chan's merge; the sums add)."""
    return {"ystats": R._merge(a["ystats"], b["ystats"]),
            "ao_y": R._merge(a["ao_y"], b["ao_y"]),
            "ao_sum_x": a["ao_sum_x"] + b["ao_sum_x"],
            "err": R._merge(a["err"], b["err"])}


def sync(cfg: R.Forest, forest, deltas):
    """The master's round: merge the workers' deltas, fold them into the
    forest, attempt splits on the merged tables, refresh the error
    windows and vote weights."""
    d = functools.reduce(merge, deltas)
    trees = forest["trees"]
    trees = dict(trees,
                 ystats=R._merge(trees["ystats"], d["ystats"]),
                 seen_since_attempt=trees["seen_since_attempt"]
                 + d["ystats"]["n"],
                 ao_y=R._merge(trees["ao_y"], d["ao_y"]),
                 ao_sum_x=trees["ao_sum_x"] + d["ao_sum_x"])
    attempt = trees["is_leaf"] \
        & (trees["seen_since_attempt"] >= cfg.grace_period) \
        & (trees["depth"] < cfg.max_depth) \
        & (trees["n_nodes"][:, None] + 1 < cfg.max_nodes)
    merit, thr = R._query(trees["ao_y"], trees["ao_sum_x"], attempt)
    trees = jax.vmap(functools.partial(R._split_tree, cfg))(
        trees, merit, thr, attempt, forest["feat_mask"])
    err_win = R._merge(forest["err_win"], d["err"])
    err_ewma = jnp.where(err_win["n"] > 0, err_win["mean"], 0.0)
    return dict(forest, trees=trees, err_win=err_win, err_ewma=err_ewma,
                vote_w=R._vote_weights(cfg, err_win, err_ewma))


def poisson_mass(cfg: R.Forest, keys, rows: int, steps: int):
    """(T,) int32: every Poisson weight the workers draw in global batches
    1..steps, summed over workers and rows, per member.  Draws only; no
    statistic is touched."""
    D, T = keys.shape[:2]

    def body(_, carry):
        k, total = carry
        k, w = poisson_weights(cfg, k, rows)
        return k, total + w.sum(-1)

    _, total = jax.lax.fori_loop(
        0, steps, body, (keys.reshape(D * T, 2), jnp.zeros(D * T, jnp.int32)))
    return total.reshape(D, T).sum(0)

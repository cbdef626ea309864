"""How ``correct`` is decided in the data-parallel cell: the program against
the plain protocol of ``reference_dp.py``, stepped through the same
global batches from the same seed.

The numbers are those of ``correct.py`` (``state_gap``,
``final_state_gap`` and ``predict_gap``, on the replicated forest), with
``final_state_gap`` taken at a fixed global step inside the window, and
one more that covers every step after it:

* ``mass_gap``: the worst member of |absorbed weight - drawn weight| over
  the drawn weight, where the absorbed weight is the forest's total bin
  mass (every row a member absorbs lands in one bin of each feature's
  table, and no table is ever cleared) at the window's last published
  step, and the drawn weight is the sum of the Poisson weights the
  reference draws for that member over every row of every shard up to
  that step.  A delta lost or a batch learned twice moves it; rounding
  does not (the counts are integers, and float32 adds them exactly below
  2**24 a bin).  A shard given another shard's rows leaves it as it is:
  a shard's weights come from its own keys, whatever rows it holds.

The limits are in ``limits_dp.json``; ``PERF.md`` gives the readings
they were set from.
"""
from __future__ import annotations

import json
import os

import numpy as np

import correct
import reference_dp as RD
from harness import BENCH, Check


def limits() -> dict:
    with open(os.path.join(BENCH, "limits_dp.json")) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


def checks(values: dict) -> list:
    lim = limits()
    return [Check(k, float(v), float(lim[k])) for k, v in values.items()]


def global_batch(stream, shards: int, g: int):
    """Global batch ``g``: stream batches ``shards*g .. shards*g+shards-1``
    stacked, shard d's rows d-th."""
    parts = [stream.batch(shards * g + d) for d in range(shards)]
    return (np.concatenate([X for X, _ in parts]),
            np.concatenate([y for _, y in parts]))


def mass_gap(bin_mass, drawn) -> float:
    """Worst member of |bin mass - drawn weight| / drawn weight."""
    got = np.asarray(bin_mass, np.float64)
    want = np.asarray(drawn, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(want, 1.0)))


class ReferenceRunDP:
    """The plain protocol stepped one global batch at a time on the
    default device.  (Spread over the chips it is slower: a copy between
    v5e chips runs through host memory.)

    ``drop``, ``duplicate`` and ``skip`` plant the faults the checks have
    to catch, for the control (``control_dp.py``): the shard ``drop``'s
    delta left out of the merge at global batch ``at``; shard 1 given
    shard 0's rows (``duplicate``); the sync after global batch ``at``
    skipped, its deltas carried to the next (``skip``)."""

    def __init__(self, config: dict, stream, seed: int, dtype=None, *,
                 drop=None, duplicate=False, skip=False, at: int = 2):
        import jax
        import jax.numpy as jnp
        self.cfg = correct.reference_forest(config)
        self.stream = stream
        self.shards = int(config["shards"])
        self.sync_every = int(config["dp_sync_every"])
        self.dtype = dtype or jnp.float32
        key = jax.random.PRNGKey(seed % 2 ** 32)
        st = RD.init(self.cfg, key, self.shards, self.dtype)
        self.forest, self.keys0 = st["forest"], st["keys"]
        self.keys = list(st["keys"])
        self.zero = RD.zero_delta(self.cfg, self.dtype)
        self.deltas = [self.zero] * self.shards
        self.steps = 0
        self.drop, self.duplicate, self.skip, self.at = \
            drop, duplicate, skip, at
        with jax.default_matmul_precision("highest"):
            self._local = jax.jit(
                lambda f, d, k, X, y: RD.local(self.cfg, f, d, k, X, y))
            self._sync = jax.jit(
                lambda f, ds: RD.sync(self.cfg, f, ds))
            self._predict = jax.jit(
                lambda f, X: RD.R.predict(self.cfg, f, X))
            self._mass = jax.jit(
                lambda k, steps: RD.poisson_mass(
                    self.cfg, k, self.stream.batch_rows, steps))

    def _step(self) -> None:
        import jax
        g, D = self.steps, self.shards
        batches = [self.stream.batch(D * g + d) for d in range(D)]
        if self.duplicate:
            batches[1] = batches[0]
        with jax.default_matmul_precision("highest"):
            for d, (X, y) in enumerate(batches):
                self.deltas[d], self.keys[d] = self._local(
                    self.forest, self.deltas[d], self.keys[d], X, y)
            self.steps += 1
            if self.steps % self.sync_every:
                return
            if self.skip and self.steps == self.at:
                return
            deltas = [dl for d, dl in enumerate(self.deltas)
                      if not (d == self.drop and self.steps == self.at)]
            self.forest = self._sync(self.forest, deltas)
        self.deltas = [self.zero] * self.shards

    def advance_to(self, g: int) -> None:
        if g < self.steps:
            raise ValueError(f"reference at step {self.steps}, asked {g}")
        while self.steps < g:
            self._step()

    def norms(self) -> dict:
        import jax
        return correct.leaf_norms(jax.device_get(self.forest))

    def predict(self, X) -> np.ndarray:
        return np.asarray(self._predict(self.forest, X), np.float64)

    def bin_mass(self) -> np.ndarray:
        """(T,) the forest's total bin mass per member (feature 0)."""
        n = np.asarray(self.forest["trees"]["ao_y"]["n"][:, :, 0, :],
                       np.float64)
        return n.sum(axis=(1, 2))

    def drawn_mass(self, g: int) -> np.ndarray:
        """(T,) the Poisson weights drawn for each member in global
        batches 1..g, over every shard and row."""
        return np.asarray(self._mass(self.keys0, g), np.int64)

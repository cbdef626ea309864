"""How ``correct`` is decided: the program against the plain reference.

The reference (``reference.py``) starts from the same seed and learns the
same batches; it takes nothing the program made.  The numbers compared:

* ``state_gap``: after each of the first steps the window's own call
  took, the worst leaf of the forest state by the gap between the
  program's norm and the reference's, over the larger of the reference's
  norm of that leaf and its median leaf norm;
* ``predict_gap``: the widest gap between what the program answers and
  what the reference forest at the same step answers, over the mean
  magnitude of the reference's answers.  Train cells ask the last
  published snapshot about a probe set; the serve cell takes a sample of
  its served requests, drawn from the seed, with the largest and the
  newest among them;
* ``engine_failures`` and ``unanswered``: counts that must stay 0.

The limits are in ``limits.json``; ``PERF.md`` gives the readings they
were set from.
"""
from __future__ import annotations

import json
import os

import numpy as np

from harness import BENCH, Check
import reference as R


def limits() -> dict:
    with open(os.path.join(BENCH, "limits.json")) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


def reference_forest(config: dict) -> R.Forest:
    f = config["forest"]
    keep = {k: f[k] for k in R.Forest.__dataclass_fields__}
    return R.Forest(**keep)


class ReferenceRun:
    """The reference forest stepped through the stream, one batch at a
    time, on the default device."""

    def __init__(self, config: dict, stream, seed: int, dtype=None):
        import jax
        import jax.numpy as jnp
        self.cfg = reference_forest(config)
        self.stream = stream
        self.dtype = dtype or jnp.float32
        self.state = R.init(self.cfg, jax.random.PRNGKey(seed % 2 ** 32),
                            self.dtype)
        self.steps = 0
        self.attempts = []          # leaves that attempted, per step
        self._step = jax.jit(
            lambda s, X, y: R.step(self.cfg, s, X, y)[::2])
        self._predict = jax.jit(lambda s, X: R.predict(self.cfg, s, X))

    def advance_to(self, s: int) -> None:
        if s < self.steps:
            raise ValueError(f"reference at step {self.steps}, asked {s}")
        while self.steps < s:
            self.state, n = self._step(self.state,
                                       *self.stream.batch(self.steps))
            self.attempts.append(n)
            self.steps += 1

    def attempted(self, first: int, last: int) -> int:
        """Leaves that attempted a split in steps ``first+1 .. last``."""
        return int(sum(np.asarray(a) for a in self.attempts[first:last]))

    def resets(self) -> int:
        return int(np.sum(np.asarray(self.state["resets"])))

    def predict(self, X) -> np.ndarray:
        """Answers for the rows of X, padded to a power of two (at least
        128 rows) so that a few programs serve every request size."""
        B = X.shape[0]
        Bp = max(128, 1 << (B - 1).bit_length())
        Xp = np.zeros((Bp, X.shape[1]), np.float32)
        Xp[:B] = X
        return np.asarray(self._predict(self.state, Xp), np.float64)[:B]

    def norms(self) -> dict:
        import jax
        return leaf_norms(jax.device_get(self.state))


def leaf_norms(state) -> dict:
    """{leaf path: float64 2-norm} of a forest state pytree."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(a, np.float64).ravel())) for p, a in flat}


def norm_gap(got: dict, want: dict) -> float:
    """Worst leaf of |‖got‖ - ‖want‖| / max(‖want‖, median ‖want‖),
    over the reference's leaves (a leaf the program lacks reads 1)."""
    med = float(np.median(list(want.values())))
    worst = 0.0
    for k, w in want.items():
        g = got.get(k)
        gap = 1.0 if g is None else abs(g - w) / max(w, med, 1e-30)
        if not np.isfinite(gap):
            return float("inf")
        worst = max(worst, gap)
    return worst


def prediction_gap(got, want) -> float:
    got = np.concatenate([np.asarray(g, np.float64).ravel() for g in got])
    want = np.concatenate([np.asarray(w, np.float64).ravel() for w in want])
    if got.shape != want.shape:
        return float("inf")
    scale = max(float(np.mean(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want), initial=0.0) / scale)


def checks(values: dict) -> list:
    lim = limits()
    return [Check(k, float(v), float(lim[k])) for k, v in values.items()]

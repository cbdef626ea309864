"""The labelled stream and the request rows, made from ``--seed``.

One general generator reads the configuration's ``stream`` entry:

* ``function: "friedman1"``: Friedman (1991) #1, ``x ~ U[0, 1]^F`` with
  ``y = 10 sin(pi x0 x1) + 20 (x2 - 0.5)^2 + 10 x3 + 5 x4 + N(0, noise_sd)``
  (features 5.. carry no signal);
* ``drift``: ``{"kind": "global_recurring_abrupt", "positions": [p0, p1]}``
  swaps the informative features from row ``p0`` of the stream on and
  swaps them back at row ``p1`` (Ikonomovska et al. 2011, FriedmanDrift's
  GRA concept: ``10 sin(pi x3 x5) + 20 (x1 - 0.5)^2 + 10 x0 + 5 x2``).
  A batch that holds a drift point is labelled row by row.

Batch ``s`` depends on ``(seed, s)`` alone, so the reference regenerates
exactly the rows the program learned, in any order.
"""
from __future__ import annotations

import numpy as np

_CONCEPTS = {
    0: (0, 1, 2, 3, 4),      # the stationary concept
    1: (3, 5, 1, 0, 2),      # GRA: informative positions swapped
}


def _friedman1(X: np.ndarray, concept: int) -> np.ndarray:
    a, b, c, d, e = _CONCEPTS[concept]
    return (10.0 * np.sin(np.pi * X[:, a] * X[:, b])
            + 20.0 * (X[:, c] - 0.5) ** 2 + 10.0 * X[:, d] + 5.0 * X[:, e])


class Stream:
    """Deterministic labelled batches of ``batch_rows`` rows."""

    def __init__(self, config: dict, seed: int):
        self.seed = int(seed)
        self.batch_rows = int(config["batch_rows"])
        self.n_features = int(config["forest"]["n_features"])
        st = config["stream"]
        if st["function"] != "friedman1":
            raise ValueError(f"unknown stream function {st['function']!r}")
        self.noise_sd = float(st["noise_sd"])
        drift = st.get("drift")
        self.positions = None
        if drift is not None:
            if drift["kind"] != "global_recurring_abrupt":
                raise ValueError(f"unknown drift kind {drift['kind']!r}")
            p0, p1 = (int(p) for p in drift["positions"])
            if not 0 <= p0 < p1:
                raise ValueError("drift positions must rise: [p0, p1]")
            self.positions = (p0, p1)

    def concept(self, rows: np.ndarray) -> np.ndarray:
        """The concept (0 or 1) of each stream row index in ``rows``."""
        if self.positions is None:
            return np.zeros(rows.shape, np.int64)
        p0, p1 = self.positions
        return ((rows >= p0) & (rows < p1)).astype(np.int64)

    def batch(self, s: int):
        """(X, y) of batch ``s``: float32 (B, F) and (B,)."""
        rng = np.random.default_rng([self.seed, 0, s])
        X = rng.uniform(0.0, 1.0, (self.batch_rows, self.n_features))
        c = self.concept(s * self.batch_rows + np.arange(self.batch_rows))
        y = np.where(c == 1, _friedman1(X, 1), _friedman1(X, 0)) \
            + rng.normal(0.0, self.noise_sd, self.batch_rows)
        return X.astype(np.float32), y.astype(np.float32)

    def request_rows(self, k: int, rows: int, tag: int = 1) -> np.ndarray:
        """Feature rows of request ``k`` (unlabelled, same distribution);
        ``tag`` keeps other sets of rows (warm-up, probes) apart."""
        rng = np.random.default_rng([self.seed, tag, k])
        return rng.uniform(0.0, 1.0, (rows, self.n_features)) \
            .astype(np.float32)

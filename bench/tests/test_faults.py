"""A run with the timed path broken underneath has to come out with
``correct`` false: the trainer's step returns its state unchanged; it
learns half of each batch; an answer is altered where it is produced.
A sound run at the same size comes out true."""
from __future__ import annotations

import pytest

from conftest import run_tiny


def test_sound_train_run_is_correct():
    r = run_tiny("friedman1.train")
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


def test_sound_serve_run_is_correct():
    r = run_tiny("friedman1.serve")
    assert r["correct"], r["checks"]


@pytest.fixture
def engine_mod():
    from repro.core import engine
    return engine


@pytest.mark.parametrize("cell", ["friedman1.train", "friedman_drift.train",
                                  "friedman1.serve"])
def test_state_left_unchanged_is_caught(monkeypatch, engine_mod, cell):
    monkeypatch.setattr(engine_mod.ServingEngine, "_train_step",
                        lambda self, batch: self._state)
    r = run_tiny(cell)
    assert not r["correct"]
    assert r["checks"]["state_gap"]["value"] > \
        r["checks"]["state_gap"]["limit"]


@pytest.mark.parametrize("cell", ["friedman1.train", "friedman_drift.train",
                                  "friedman1.serve"])
def test_half_batch_left_out_is_caught(monkeypatch, engine_mod, cell):
    def half(self, batch):
        X, y = batch
        h = X.shape[0] // 2
        return engine_mod._learn(self._model_cfg, self._state, X[:h], y[:h])

    monkeypatch.setattr(engine_mod.ServingEngine, "_train_step", half)
    r = run_tiny(cell)
    assert not r["correct"]
    assert r["checks"]["state_gap"]["value"] > \
        r["checks"]["state_gap"]["limit"]


@pytest.mark.parametrize("cell", ["friedman1.train", "friedman_drift.train",
                                  "friedman1.serve"])
def test_altered_answer_is_caught(monkeypatch, engine_mod, cell):
    orig = engine_mod.sv.predict_snapshot

    def altered(snap, X, **kw):
        return orig(snap, X, **kw).at[0].add(10.0)

    monkeypatch.setattr(engine_mod.sv, "predict_snapshot", altered)
    r = run_tiny(cell)
    assert not r["correct"]
    assert r["checks"]["predict_gap"]["value"] > \
        r["checks"]["predict_gap"]["limit"]

"""Names the program writes into a profiler trace: the forest step's
stage scopes in the op metadata of ``engine._learn``, the engine's and
the serving path's host spans, and the publish-time counters.

The benchmark reads all three (``bench/program_trace.py``); these tests
pin them on the CPU."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.checkpoint.ckpt import Checkpointer
from repro.core import engine as eng
from repro.core import faults as fl
from repro.core import forest as fr
from repro.core import hoeffding as ht

STAGES = ("forest.test", "forest.route", "forest.absorb", "forest.attempt",
          "forest.drift")
F, B = 4, 128


def _learn_op_names(backend: str) -> list:
    """The op names of the main program of ``engine._learn`` for a tiny
    forest, as its lowering records them (``jit(_learn)/<scope>/...``)."""
    tcfg = ht.HTRConfig(n_features=F, max_nodes=15, n_bins=8,
                        split_backend=backend)
    fcfg = fr.ForestConfig(tree=tcfg, n_trees=2, subspace=0.5)
    state = fr.init_forest(fcfg, jax.random.PRNGKey(0))
    text = eng._learn.lower(fcfg, state, jnp.zeros((B, F)),
                            jnp.zeros(B)).as_text(debug_info=True)
    return re.findall(r'loc\("(jit\(_learn\)/[^"]*)"', text)


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_every_op_of_the_step_is_under_one_stage_scope(backend):
    names = _learn_op_names(backend)
    assert names
    for name in names:
        scopes = [p for p in name.split("/") if p in STAGES]
        assert len(scopes) == 1, name
    assert {n.split("/")[1] for n in names} == set(STAGES)


def test_kernel_calls_sit_in_their_stage():
    names = _learn_op_names("interpret")
    absorb = [n for n in names if "qo_update_leaves_pallas" in n]
    route = [n for n in names if "qo_route_pallas" in n]
    assert absorb and route
    assert all(n.split("/")[1] == "forest.absorb" for n in absorb)
    # the prequential test and the training route each route the batch
    assert {n.split("/")[1] for n in route} == {"forest.test",
                                                "forest.route"}


def _stream(step):
    rng = np.random.default_rng(step)
    X = rng.normal(0, 1, (B, F)).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(2.0 * (X[:, 0] > 0), jnp.float32)


def _engine(tmp_path, injector=None):
    tcfg = ht.HTRConfig(n_features=F, max_nodes=15, n_bins=8,
                        grace_period=40, max_depth=4, r0=0.3)
    fcfg = fr.ForestConfig(tree=tcfg, n_trees=2, subspace=0.5)
    return eng.ServingEngine(
        fcfg, fr.init_forest(fcfg, jax.random.PRNGKey(0)), _stream,
        cfg=eng.EngineConfig(sync_every=2),
        checkpointer=Checkpointer(str(tmp_path / "ckpt")),
        injector=injector)


def _host_span_tree(path: str) -> list:
    """(parent, name) of every ``engine.*`` / ``serve.*`` span on the
    host plane, the parent being the innermost such span that encloses
    it on the same thread (None at the top)."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, -e.duration_ns,
                           e.name.split("#")[0]) for e in line.events
                          if e.name.startswith(("engine.", "serve."))))
            stack = []
            for s, neg_d, name in evs:
                while stack and stack[-1][0] <= s:
                    stack.pop()
                out.append((stack[-1][1] if stack else None, name))
                stack.append((s - neg_d, name))
    return out


def test_host_spans_nest_as_documented(tmp_path):
    inj = fl.FaultInjector()
    e = _engine(tmp_path, inj)
    e.train_once()                          # compile outside the trace
    e.train_once()
    e.submit(np.zeros((16, F), np.float32))
    e.serve_once()
    inj.arm("trainer.step", fl.Kill(), after=2)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        e.train_once()
        e.train_once()                      # publishes
        e.submit(np.ones((16, F), np.float32))
        e.serve_once()
        e.train_once()                      # killed: recovers, publishes
    finally:
        jax.profiler.stop_trace()
    assert inj.fired("trainer.step") == 1
    path, = (tmp_path / "trace").rglob("*.xplane.pb")
    tree = _host_span_tree(str(path))
    pairs = set(tree)
    assert {
        (None, "engine.train_once"),
        ("engine.train_once", "engine.publish"),
        ("engine.publish", "serve.freeze"),
        ("serve.freeze", "serve.freeze.fetch"),
        ("serve.freeze", "serve.freeze.reindex"),
        ("serve.freeze", "serve.freeze.upload"),
        ("engine.publish", "serve.validate"),
        ("engine.publish", "engine.swap"),
        ("engine.publish", "engine.checkpoint"),
        ("engine.train_once", "engine.recover"),
        ("engine.recover", "engine.publish"),
        (None, "engine.submit"),
        (None, "engine.serve_once"),
        ("engine.serve_once", "engine.pack"),
        ("engine.serve_once", "serve.predict"),
    } <= pairs
    # every span has its documented parent, and nothing else does
    allowed = {
        "engine.publish": {"engine.train_once", "engine.recover"},
        "serve.validate": {"engine.publish"},
        "serve.freeze": {"engine.publish"},
        "serve.freeze.fetch": {"serve.freeze"},
        "engine.pack": {"engine.serve_once"},
    }
    for parent, name in tree:
        assert parent in allowed.get(name, {parent}), (parent, name)
    # freeze validates its snapshot and the publish validates it again
    assert sum(n == "serve.validate" for _, n in tree) \
        == 2 * sum(n == "engine.publish" for _, n in tree) == 4
    assert sum(n == "engine.train_once" for _, n in tree) == 3


def test_publish_counters_split_wait_from_host_work(tmp_path):
    e = _engine(tmp_path)
    m0 = e.metrics()                       # the constructor published v1
    assert m0["publish_host_s"] > 0 and m0["publish_wait_s"] > 0
    e.train_once()                         # no publish: no change
    m1 = e.metrics()
    assert (m1["publish_host_s"], m1["publish_wait_s"]) \
        == (m0["publish_host_s"], m0["publish_wait_s"])
    e.train_once()                         # publishes
    m2 = e.metrics()
    assert m2["publish_host_s"] > m1["publish_host_s"]
    assert m2["publish_wait_s"] > m1["publish_wait_s"]
    # a snapshot handed to publish() directly is host work alone
    snap = e.snapshot_for_version(e.published_version)
    assert e.publish(dataclasses.replace(snap, version=jnp.int32(99)))
    m3 = e.metrics()
    assert m3["publish_wait_s"] == m2["publish_wait_s"]
    assert m3["publish_host_s"] > m2["publish_host_s"]
    assert m3["publishes"] == m2["publishes"] + 1

"""The control (the reference in bfloat16 in the program's place) fails
the limits at a size the CPU holds, as it does on the chip at the cells'
own size; and the float32 reference agrees with the program's own jnp
forest."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from conftest import tiny_cell


@pytest.mark.parametrize("cell", ["friedman1.train", "friedman_drift.train",
                                  "friedman1.serve"])
def test_control_fails_the_limits(cell):
    import control
    import correct
    c = tiny_cell(cell)
    got = control.readings(c, seed=2 ** 31 + 11, steps=24)
    lim = correct.limits()
    assert any(got[k] > lim[k] for k in ("state_gap", "predict_gap")), got


@pytest.mark.parametrize("kind", ["half_batch", "altered_answer"])
def test_faults_in_the_programs_place_fail_the_limits(kind):
    import control
    import correct
    got = control.readings(tiny_cell("friedman1.train"), seed=2 ** 31 + 13,
                           steps=12, kind=kind)
    lim = correct.limits()
    assert any(got[k] > lim[k] for k in ("state_gap", "predict_gap")), got


def test_reference_follows_the_program_jnp_forest():
    import correct
    import program
    from repro.core import forest as fr
    from streams import Stream
    c = tiny_cell("friedman_drift.train")
    cfg = dict(c.config)
    cfg["forest"] = dict(cfg["forest"])
    stream = Stream(cfg, 5)
    fcfg = program.forest_config(cfg)
    fcfg = fcfg.__class__(**{**fcfg.__dict__, "tree": fcfg.tree.__class__(
        **{**fcfg.tree.__dict__, "split_backend": "jnp"})})
    state = fr.init_forest(fcfg, program.forest_key(5))
    learn = jax.jit(lambda s, X, y: fr.update(fcfg, s, X, y)[0])
    ref = correct.ReferenceRun(cfg, stream, 5)
    for s in range(20):
        state = learn(state, *stream.batch(s))
        ref.advance_to(s + 1)
        assert correct.norm_gap(correct.leaf_norms(state), ref.norms()) < 1e-6
    np.testing.assert_array_equal(np.asarray(state["trees"]["n_nodes"]),
                                  np.asarray(ref.state["trees"]["n_nodes"]))

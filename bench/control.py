"""The control: the plain reference computed in bfloat16, put in the
program's place, read by the same numbers that decide ``correct``; and the
faults a run must catch, planted in the reference put in the program's
place.

    python3 bench/control.py --workload <cell> --seeds 101,102,103 \
        [--steps N] [--kind bf16|half_batch|altered_answer]

For each seed it steps the float32 reference and the one in the program's
place through the same stream and prints the numbers a run would compare:
``state_gap`` over the first ``check_steps`` steps, ``final_state_gap``
at step ``--steps``, and ``predict_gap``
of the answers at every publish from the end of the set-up to step
``--steps`` (probe rows, or request rows drawn as the serve mix draws
them).  ``--kind`` picks what stands in the program's place:

* ``bf16``: the reference in bfloat16 (the control);
* ``half_batch``: the float32 reference learning only the first half of
  every batch;
* ``altered_answer``: the float32 reference with its first answer of
  every request moved by 10 (two thirds of the mean answer; a sound
  run's split decided one step apart moves a few answers by up to
  about 0.1 of it).

Each limit in ``limits.json`` has to lie below what the control prints.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


KINDS = ("bf16", "half_batch", "altered_answer")


class _HalfStream:
    """The stream with the second half of every batch left out."""

    def __init__(self, stream):
        self.stream = stream

    def batch(self, s: int):
        X, y = self.stream.batch(s)
        h = X.shape[0] // 2
        return X[:h], y[:h]


def _in_place(cfg: dict, stream, seed: int, kind: str):
    """What stands in the program's place for ``kind``."""
    import jax.numpy as jnp

    import correct
    if kind == "bf16":
        return correct.ReferenceRun(cfg, stream, seed, dtype=jnp.bfloat16)
    if kind == "half_batch":
        return correct.ReferenceRun(cfg, _HalfStream(stream), seed)
    if kind == "altered_answer":
        run = correct.ReferenceRun(cfg, stream, seed)
        exact = run.predict
        run.predict = lambda X: exact(X) + 10.0 * (np.arange(len(X)) == 0)
        return run
    raise ValueError(f"unknown kind {kind!r}; one of {KINDS}")


def readings(cell: harness.Cell, seed: int, steps: int,
             kind: str = "bf16") -> dict:
    import correct
    from streams import Stream
    cfg, mix = cell.config, cell.traffic
    stream = Stream(cfg, seed)
    ref = correct.ReferenceRun(cfg, stream, seed)
    low = _in_place(cfg, stream, seed, kind)
    gaps = []
    for s in range(1, mix["check_steps"] + 1):
        ref.advance_to(s)
        low.advance_to(s)
        gaps.append(correct.norm_gap(low.norms(), ref.norms()))
    sync = cfg["engine"]["sync_every"]
    got, want = [], []
    for k, s in enumerate(range(mix["prefix_batches"], steps + 1, sync)):
        ref.advance_to(s)
        low.advance_to(s)
        X = stream.request_rows(k, mix.get("probe_rows", 256), tag=6)
        got.append(low.predict(X))
        want.append(ref.predict(X))
    ref.advance_to(steps)
    low.advance_to(steps)
    return {"state_gap": max(gaps),
            "final_state_gap": correct.norm_gap(low.norms(), ref.norms()),
            "predict_gap": correct.prediction_gap(got, want)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--kind", choices=KINDS, default="bf16")
    args = ap.parse_args(argv)
    harness.configure_jax()
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(cell.chips)
    harness.say(f"{args.kind} for {cell.name} on {devs[0].device_kind}")
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "kind": args.kind,
                          "readings": readings(cell, seed, args.steps,
                                               args.kind)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

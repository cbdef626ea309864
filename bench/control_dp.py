"""The control of the data-parallel cell: the plain protocol computed in
bfloat16, or with a fault planted, put in the program's place and read
by the numbers that decide ``correct``.

    python3 bench/control_dp.py --seeds 101,102,103 [--steps N] \
        [--kind bf16|dropped_delta|duplicated_rows|skipped_sync]

For each seed it steps the float32 protocol (``correct_dp.ReferenceRunDP``)
and the one in the program's place through the same global batches and
prints ``state_gap`` over the first ``check_steps`` global steps,
``final_state_gap`` and ``predict_gap`` (probe rows) at ``--steps``
(``compare_step`` by default), and ``mass_gap`` there.  ``--kind``
picks what stands in the program's place:

* ``bf16``: the protocol in bfloat16 (the control);
* ``dropped_delta``: shard 3's delta left out of the merge after global
  batch 2;
* ``duplicated_rows``: shard 1 learning shard 0's rows;
* ``skipped_sync``: the sync after global batch 2 skipped, its deltas
  carried to the next.

Each limit in ``limits_dp.json`` has to lie below what one of them
prints.  It needs one chip: only the reference runs.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

KINDS = ("bf16", "dropped_delta", "duplicated_rows", "skipped_sync")


def _in_place(cfg: dict, stream, seed: int, kind: str):
    import jax.numpy as jnp

    from correct_dp import ReferenceRunDP
    if kind == "bf16":
        return ReferenceRunDP(cfg, stream, seed, dtype=jnp.bfloat16)
    if kind == "dropped_delta":
        return ReferenceRunDP(cfg, stream, seed, drop=cfg["shards"] - 1)
    if kind == "duplicated_rows":
        return ReferenceRunDP(cfg, stream, seed, duplicate=True)
    if kind == "skipped_sync":
        return ReferenceRunDP(cfg, stream, seed, skip=True)
    raise ValueError(f"unknown kind {kind!r}; one of {KINDS}")


def readings(cell: harness.Cell, seed: int, kind: str,
             steps: int | None = None) -> dict:
    import correct
    import correct_dp
    from streams import Stream
    cfg, mix = cell.config, cell.traffic
    stream = Stream(cfg, seed)
    ref = correct_dp.ReferenceRunDP(cfg, stream, seed)
    low = _in_place(cfg, stream, seed, kind)
    gaps = []
    for s in range(1, mix["check_steps"] + 1):
        ref.advance_to(s)
        low.advance_to(s)
        gaps.append(correct.norm_gap(low.norms(), ref.norms()))
    at = steps or mix["compare_step"]
    ref.advance_to(at)
    low.advance_to(at)
    probe = stream.request_rows(0, mix["probe_rows"], tag=3)
    return {"state_gap": max(gaps),
            "final_state_gap": correct.norm_gap(low.norms(), ref.norms()),
            "predict_gap": correct.prediction_gap([low.predict(probe)],
                                                  [ref.predict(probe)]),
            "mass_gap": correct_dp.mass_gap(low.bin_mass(),
                                            ref.drawn_mass(at))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="friedman1_dp4.train_dp")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--kind", choices=KINDS, default="bf16")
    args = ap.parse_args(argv)
    harness.configure_jax()
    cell = harness.load_cell(args.workload)
    devs = harness.require_chips(1)
    harness.say(f"{args.kind} for {cell.name} on {devs[0].device_kind}")
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "kind": args.kind,
                          "readings": readings(cell, seed, args.kind,
                                               args.steps)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

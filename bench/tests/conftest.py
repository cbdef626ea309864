"""Tiny cells on the CPU: the harness's look for a chip is skipped, and
the rest of a run (set-up, window, checks, result line) is driven at a
size the CPU holds."""
from __future__ import annotations

import copy
import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import harness  # noqa: E402

TINY_FOREST = {"n_trees": 4, "max_nodes": 63, "n_features": 10, "n_bins": 16}

#: cells whose files are under bench/ but which BENCHMARK.json does not
#: list: the serve cell waits there until serving stops compiling a
#: program for every new batch size (PERF.md, Open questions)
UNLISTED = {"friedman1.serve": ("configs/friedman1.json", "serve")}


def load_cell(name: str) -> harness.Cell:
    if name not in UNLISTED:
        return harness.load_cell(name)
    config, traffic = UNLISTED[name]
    with open(os.path.join(BENCH, config)) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        mix = json.load(f)
    return harness.Cell(name=name, chips=1, config=cfg, traffic=mix,
                        end_to_end=[], per_layer=[])


def tiny_cell(name: str, trace: bool = False) -> harness.Cell:
    cell = load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["forest"].update(TINY_FOREST)
    cfg["batch_rows"] = 512
    if "drift" in cfg["stream"]:
        cfg["stream"]["drift"]["positions"] = [2560, 4096]
    mix = dict(cell.traffic)
    mix["prefix_batches"] = 4
    if "rate_per_s" in mix:
        mix.update(rate_per_s=20.0, rows_max=1024, answer_wait_s=30)
    mix["probe_rows"] = mix.get("probe_rows", 256) and 256
    return harness.Cell(name=cell.name, chips=1, config=cfg, traffic=mix,
                        end_to_end=cell.end_to_end, per_layer=cell.per_layer)


def run_tiny(name: str, seed: int = 2 ** 31 + 7, seconds: float = 1.5):
    """Drive one tiny run on the CPU; returns the result line's object."""
    import jax

    import run
    cell = tiny_cell(name)
    ctx = run.Context(cell=cell, seed=seed, seconds=seconds, trace=False,
                      t_start=0.0, devs=jax.devices()[:1],
                      counter=harness.CompileCounter())
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.report(ctx, cell.driver.run(ctx))
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])

"""Record ``bench/testdata/scoped_train.xplane.pb`` on a TPU.

A forest of 2 trees x 63 nodes (friedman1's other settings, 1,024-row
batches) learns 4 batches through the engine before the window, which
compiles every program and ends on a publish, then 4 batches inside the
``bench.window`` annotation, the last of them ending on a publish.

    python3 bench/tests/record_scoped_train.py

Prints what ``bench/program_trace.py`` reads from the recorded file.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import program  # noqa: E402
import program_trace  # noqa: E402
from streams import Stream  # noqa: E402

OUT = os.path.join(BENCH, "testdata", "scoped_train.xplane.pb")
SEED, STEPS = 13, 4


def main() -> int:
    harness.configure_jax()
    harness.require_chips(1)
    cfg = copy.deepcopy(harness.load_cell("friedman1.train").config)
    cfg["forest"].update(n_trees=2, max_nodes=63)
    cfg["batch_rows"] = 1024
    sync = cfg["engine"]["sync_every"]
    stream = Stream(cfg, SEED)
    eng = program.build_engine(cfg, SEED, stream.batch)
    for _ in range(sync):
        eng.train_once()
    with harness.Window(trace=True) as win:
        for _ in range(STEPS):
            eng.train_once()
    shutil.copyfile(win.xplane, OUT)
    win.cleanup()
    print(json.dumps({"file": OUT, "bytes": os.path.getsize(OUT),
                      "window_s": win.seconds,
                      **program_trace.breakdown(OUT)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

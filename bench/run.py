"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds everything from ``--seed``, warms up, measures for ``--seconds``,
checks what the window produced against the plain reference, and prints
one JSON object as the last line of standard output.  With ``--trace 0``
its metrics are the cell's end-to-end metrics; with ``--trace 1`` the
window is traced and the metrics are the cell's per-layer ones.  With no
TPU, or fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from harness import BenchError, say  # noqa: E402


@dataclass
class Context:
    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devs: list
    counter: harness.CompileCounter


def layer_metrics(ctx, outcome) -> tuple:
    """The cell's per-layer metrics from the traced window, and the
    breakdown of where the device time and the idle time went."""
    import trace_reduce
    win = outcome.window
    red = trace_reduce.reduce(win.xplane, n_devices=len(ctx.devs),
                              spans=outcome.layer_ctx["spans"],
                              window=(win.t0, win.t1))
    lctx = dict(outcome.layer_ctx, trace=red, config=ctx.cell.config,
                peaks=harness.peaks(ctx.devs[0].device_kind))
    metrics = {}
    for m in ctx.cell.per_layer:
        value = harness.metric_reader(m["name"]).read(lctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics, red


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        cache = harness.configure_jax()
        devs = harness.require_chips(cell.chips)
        import program
        if program.kernel_backend() != "pallas":
            raise BenchError(f"kernel backend "
                             f"{program.kernel_backend()!r}, not 'pallas'")
        if program.tuning_installed():
            raise BenchError("a tuning table is installed")
    except (BenchError, ImportError, OSError, KeyError) as e:
        say(f"no result: {type(e).__name__}: {e}")
        return 2
    say(f"cell {cell.name} seed {args.seed} on {devs[0].device_kind} x "
        f"{len(devs)}; compile cache {cache}")
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=T_START, devs=devs,
                  counter=harness.CompileCounter())
    outcome = cell.driver.run(ctx)
    return report(ctx, outcome)


def report(ctx, outcome) -> int:
    device = dict(outcome.device)
    result = {"correct": all(c.ok for c in outcome.checks),
              "attempted": outcome.attempted, "failed": outcome.failed}
    if ctx.trace:
        metrics, red = layer_metrics(ctx, outcome)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
        outcome.notes.append(f"trace: device busy {red.busy_s:.6f} s of "
                             f"{red.window_s:.6f} s")
    else:
        units = {m["name"]: m["unit"] for m in ctx.cell.end_to_end}
        values = dict(outcome.end_to_end, setup_s=outcome.setup_s)
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}
    if outcome.window is not None:
        outcome.window.cleanup()
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    for line in outcome.notes:
        print(line, flush=True)
    for c in outcome.checks:
        say(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

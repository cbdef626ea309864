"""Device milliseconds per train step of one stage of the forest step:
``stage_ms_per_step.<stage>`` for the stages the program names with
``jax.named_scope`` (``forest.test``, ``.route``, ``.absorb``,
``.attempt``, ``.drift``).  Each instant of the window's device time
belongs to the innermost op running and that op to the stage in its
name stack (``bench/program_trace.py``), so the stages and the unscoped
rest add up to the busy time.  None where the trace holds no stage scope
(a program that names none).  Moves ``train_rows_per_s``.

The harness loads this file once for each metric name, as the module
``bench_metric_<name, dots as underscores>``: the stage is the last part
of that name."""
import program_trace

STAGE = __name__.rsplit("_", 1)[-1]


def read(ctx):
    steps = ctx.get("steps", 0)
    path = program_trace.trace_of(ctx.get("trace"))
    if path is None or steps <= 0 or STAGE not in program_trace.STAGES:
        return None
    stages = program_trace.device_stages(path)
    if not stages:
        return None
    return 1e3 * stages[STAGE] / steps

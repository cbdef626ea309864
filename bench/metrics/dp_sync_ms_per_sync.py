"""Device milliseconds per sync on the root device under the scopes
``dp.merge`` (the shard deltas merged into one) and ``dp.apply`` (the
merged delta folded into the forest, the split attempts on the merged
tables, the error windows and vote weights), each instant of its time
to the innermost op running, over the runs of the sync program the trace
holds (``bench/dp_trace.py``).  The other chips wait through it.  None
where no op carries either scope (a program that names none).  Moves
``train_rows_per_s``."""
import dp_trace
import program_trace


def read(ctx):
    path = program_trace.trace_of(ctx.get("trace"))
    if path is None:
        return None
    runs = dp_trace.sync_runs(path)
    scopes = dp_trace.root_scopes(path)
    if runs <= 0 or not scopes:
        return None
    return 1e3 * sum(scopes.values()) / runs

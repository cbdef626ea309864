"""Milliseconds per sync of the data-parallel exchange: the copy of the
shard deltas to the root device and of the merged forest back
(``dp.gather`` plus ``dp.broadcast``).

Read from the host spans (``bench/dp_trace.py``): the copies run through
host memory and the spans last until they are done, while the device
trace records no event for them.  None where the trace holds neither
span (a program that names no exchange).  Moves ``train_rows_per_s``:
until the forest is back, no chip starts its next local step."""
import dp_trace
import program_trace


def read(ctx):
    syncs = ctx.get("syncs", 0)
    path = program_trace.trace_of(ctx.get("trace"))
    if path is None or syncs <= 0:
        return None
    spans = [e - s for s, e, name in dp_trace.host_spans(path)
             if name in (dp_trace.GATHER, dp_trace.BROADCAST)]
    if not spans:
        return None
    return 1e-6 * sum(spans) / syncs

"""Host milliseconds of a publish while the device has nothing queued:
the mean, over the ``engine.publish`` spans of the window, of the span
less the part its ``serve.freeze.fetch`` child covers (the reads that
first wait for every queued step).  Read from the host plane of the
trace file (``bench/program_trace.py``), which shares the device's
clock.  None where the trace holds no publish span (a program that
writes none).  Moves ``train_rows_per_s``: the device idles through it."""
import program_trace


def read(ctx):
    path = program_trace.trace_of(ctx.get("trace"))
    if path is None:
        return None
    pubs = program_trace.publishes(path)
    if not pubs:
        return None
    return 1e3 * sum(span - fetch for span, fetch in pubs) / len(pubs)

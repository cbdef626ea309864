"""Device milliseconds of the split query kernel ``qo_query_batched`` per
train step in the traced window: the attempt stage's cost, which grows
with the attempting leaves and vanishes once every tree is full.  Moves
``train_rows_per_s``."""

KERNEL = "qo_query_batched_pallas"


def read(ctx):
    red = ctx.get("trace")
    steps = ctx.get("steps", 0)
    if red is None or steps <= 0 or red.kernel_calls.get(KERNEL, 0) == 0:
        return None
    return 1e3 * red.kernel_seconds(KERNEL) / steps

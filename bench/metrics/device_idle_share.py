"""Share of the traced window in which no operation ran on the device
(averaged over the chips used).  One reader serves every metric named
``device_idle_share.<kind>``: ``.train`` moves ``train_rows_per_s`` (an
idle stretch is time no row is absorbed)."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)

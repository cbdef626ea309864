"""Share of its roofline that the absorb kernel ``qo_update_leaves``
reaches: the least time the chip needs for the kernel's necessary work,
``max(flops / peak_flops, bytes / peak_bytes)``, over the kernel's device
time in the trace, summed over the calls in the traced window.

The necessary work of one call is counted from the shapes the forest
hands the kernel: the tree axis folds into the table axis (T*M tables of
F features and C bins) and every member sees the whole batch (T*B rows).
Only the work any implementation must do counts: binning and the
payload per row and feature, one Chan merge per bin; one read of every
row and one read and write of the four table planes.  So the share cannot
flatter a wasteful schedule, and it cannot pass 100% unless the kernel
ran faster than the chip's published peaks allow."""

KERNEL = "qo_update_leaves_pallas"


def necessary_work(n_tables: int, F: int, C: int, rows: int):
    """(flops, bytes) of absorbing ``rows`` routed rows into ``n_tables``
    (leaf) tables of F features x C bins, float32 throughout."""
    flops = 12 * rows * F + 18 * n_tables * F * C
    bytes_ = 4 * (rows * (F + 3) + 2 * 4 * n_tables * F * C)
    return flops, bytes_


def share(seconds: float, calls: int, n_tables: int, F: int, C: int,
          rows: int, peak_flops: float, peak_bytes: float) -> float:
    flops, bytes_ = necessary_work(n_tables, F, C, rows)
    least = calls * max(flops / peak_flops, bytes_ / peak_bytes)
    return 100.0 * least / seconds


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    seconds, calls = red.kernel_seconds(KERNEL), red.kernel_calls.get(KERNEL, 0)
    if seconds <= 0 or calls == 0:
        return None
    f = ctx["config"]["forest"]
    T, M, F, C = f["n_trees"], f["max_nodes"], f["n_features"], f["n_bins"]
    p = ctx["peaks"]
    return share(seconds, calls, T * M, F, C, T * ctx["config"]["batch_rows"],
                 p["flops_per_s"], p["hbm_bytes_per_s"])

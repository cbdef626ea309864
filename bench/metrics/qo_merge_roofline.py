"""Share of its roofline that the merge kernel ``qo_merge_pallas``
reaches: the least time the chip needs for the kernel's necessary work,
``max(flops / peak_flops, bytes / peak_bytes)``, over the kernel's device
time in the trace, summed over the calls in the traced window.

A sync of D shard deltas merges them pairwise, a level at a time (one
kernel call per level, ceil(log2 D) levels, D - 1 table-set merges in
all), and then merges the result into the forest's tables (one call):
D merges of T*M tables of F features x C bins over ceil(log2 D) + 1
calls.  Necessary per merged bin: read two sets of the four float32
planes (n, mean, M2, sum_x) and write one, and the Chan merge's 13
operations.  Lane padding and packing count for nothing, so the share
cannot flatter them."""
import math

KERNEL = "qo_merge_pallas"


def necessary_work(bins: int):
    """(flops, bytes) of Chan-merging two table sets of ``bins`` bins:
    n = na + nb; d = mb - ma; mean = (na ma + nb mb) / n; M2 = M2a + M2b
    + d^2 na nb / n; sum_x = sxa + sxb."""
    return 13 * bins, 3 * 4 * 4 * bins


def share(seconds: float, calls: float, shards: int, bins: int,
          peak_flops: float, peak_bytes: float) -> float:
    per_sync = math.ceil(math.log2(shards)) + 1 if shards > 1 else 1
    flops, bytes_ = necessary_work(shards * bins)
    least = calls / per_sync * max(flops / peak_flops, bytes_ / peak_bytes)
    return 100.0 * least / seconds


def read(ctx):
    red = ctx.get("trace")
    if red is None:
        return None
    seconds = red.kernel_seconds(KERNEL)
    calls = red.kernel_calls.get(KERNEL, 0)
    if seconds <= 0 or calls == 0:
        return None
    cfg = ctx["config"]
    f = cfg["forest"]
    bins = f["n_trees"] * f["max_nodes"] * f["n_features"] * f["n_bins"]
    p = ctx["peaks"]
    return share(seconds, calls, cfg.get("shards", 1), bins,
                 p["flops_per_s"], p["hbm_bytes_per_s"])

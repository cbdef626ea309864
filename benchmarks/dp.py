"""Data-parallel stream scale-out benchmark (DESIGN.md §4.1) -> BENCH_dp.json.

Races the SAME global stream through ``build_data_parallel_forest`` on a
1-device and a 4-device mesh — same config, same batches, same sync
cadence, measured INTERLEAVED in the same run with a per-side best-of
(the repo's standard load-noise armor) — and reports amortized
per-instance throughput of whole sync windows (``update_window``: S
local batches in one dispatch + the merge collective).

D devices are forced host-platform devices, so the run must own its
``XLA_FLAGS`` before JAX initializes: :func:`run` spawns a worker
subprocess (the test_sharding.py idiom).

**Devices own their cores.**  Real accelerator devices do not share
each other's compute, but forced host devices all draw on one XLA CPU
thread pool — unpinned, the D = 1 baseline silently spreads across
every host core and the race measures the shared pool, not the
protocol.  The worker therefore pins CPU affinity per round (every
``/proc/self/task`` tid): the D = 1 baseline takes its best round over
EACH core separately (shared hosts steal cores asymmetrically; racing
it on a fixed core would let a noisy neighbor inflate the ratio), the
D-shard meshes run on ``min(D, cpu_count)`` cores.

**Read the ratio against the same-run host ceiling.**  The nominal
``speedup_vs_D1`` ceiling is ``min(D, cpu_count)``, but shared-host
MEMORY bandwidth caps it first: on this container two fully independent
single-core copies of the same program aggregate only ~1.2-1.35x one
copy, so no data-parallel execution of this workload can beat that
here, whatever the protocol costs.  The worker therefore also races a
D = 2 mesh — two shards, two cores, no oversubscription — as the
measured same-run ceiling proxy, and reports D4's ``ceiling_frac =
speedup_D4 / speedup_D2``: how much of the host's attainable scaling
the 4-shard protocol captures (observed ~0.8-1.0; the remaining gap is
4-on-2 oversubscription plus the per-shard table-sized delta work —
the wall ratio itself is hardware-bound).  On >= 4 real cores or
devices with commensurate bandwidth the same program has the full 4x
of headroom.  A microbench of the sync's merge op (``ops.forest_merge``
over the forest's folded T·M table axis) rides along.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

D = 4
T, M, F, C = 4, 63, 8, 64
BATCH = 16384        # global rows per local step (BATCH/D per shard)
SYNC_EVERY = 8       # local steps per sync window
ROUNDS, REPS = 5, 1  # interleaved best-of: ROUNDS x (REPS windows/side)


def _pin_all_threads(cpus) -> None:
    """Set CPU affinity of EVERY thread in this process (XLA's pool
    threads already exist by measurement time, so pinning only the
    caller would leave them roaming).  No-op off Linux (no /proc, no
    sched_setaffinity): the race still runs, it just measures the
    shared-pool behavior the docstring warns about."""
    if not hasattr(os, "sched_setaffinity") or not os.path.isdir(
            "/proc/self/task"):
        return
    for tid in os.listdir("/proc/self/task"):
        try:
            os.sched_setaffinity(int(tid), cpus)
        except OSError:  # thread exited between listdir and the call
            pass


def _worker() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import forest as fr
    from repro.core import hoeffding as ht
    from repro.data import synth
    from repro.launch.mesh import make_mesh_auto
    from repro.train import sharding as sh

    tree = ht.HTRConfig(n_features=F, max_nodes=M, n_bins=C,
                        grace_period=200, max_depth=8, r0=0.25)
    cfg = fr.ForestConfig(tree=tree, n_trees=T)
    X, y = synth.piecewise_regression(SYNC_EVERY * BATCH, n_features=F,
                                      seed=17)
    Xw = jnp.asarray(X).reshape(SYNC_EVERY, BATCH, F)
    yw = jnp.asarray(y).reshape(SYNC_EVERY, BATCH)

    meshes = (1, 2, D)
    dp, st = {}, {}
    for d in meshes:
        mesh = make_mesh_auto((d,), ("data",))
        dp[d] = sh.build_data_parallel_forest(cfg, mesh, "data",
                                              sync_every=SYNC_EVERY)
        s = dp[d].init(jax.random.PRNGKey(0))
        s, _ = dp[d].update_window(s, Xw, yw)        # warmup (compiles)
        jax.block_until_ready(s["forest"]["trees"]["ystats"]["n"])
        st[d] = s

    def window(d):
        s = st[d]
        t0 = time.perf_counter()
        for _ in range(REPS):
            s, _ = dp[d].update_window(s, Xw, yw)
        jax.block_until_ready(s["forest"]["trees"]["ystats"]["n"])
        st[d] = s
        return (time.perf_counter() - t0) / REPS

    # devices own their cores: the D=1 baseline races on EACH core
    # (best-of — asymmetric neighbor steal must not pick its core for
    # it), sharded meshes on min(D, nproc) cores
    n_cores = os.cpu_count() or 1
    wide = set(range(min(D, n_cores)))
    best = {d: float("inf") for d in meshes}
    try:
        for _ in range(ROUNDS):                      # interleaved race
            for core in sorted(wide):
                _pin_all_threads({core})
                best[1] = min(best[1], window(1))
            for d in meshes[1:]:
                _pin_all_threads(wide)
                best[d] = min(best[d], window(d))
    finally:
        _pin_all_threads(set(range(n_cores)))

    rows = SYNC_EVERY * BATCH
    rep = {
        str(d): {"us_per_instance": best[d] / rows * 1e6,
                 "instances_per_s": rows / best[d],
                 "n_nodes": int(np.asarray(
                     st[d]["forest"]["trees"]["n_nodes"]).max())}
        for d in meshes
    }
    print(json.dumps({
        "D1": rep["1"], "D2": rep["2"], "D4": rep[str(D)],
        "speedup_vs_D1": best[1] / best[D],
        "ceiling_speedup_D2": best[1] / best[2],
        "ceiling_frac": best[2] / best[D],
        "n_cores": n_cores,
        "config": {"T": T, "M": M, "F": F, "C": C, "batch": BATCH,
                   "sync_every": SYNC_EVERY, "shards": D},
    }))


def _merge_microbench():
    """us/call of the §4.1 merge op over the folded T·M table axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    mk = lambda: ({"n": jnp.asarray(rng.integers(0, 9, (T * M, F, C))
                                    .astype(np.float32)),
                   "mean": jnp.asarray(rng.normal(size=(T * M, F, C))
                                       .astype(np.float32)),
                   "m2": jnp.abs(jnp.asarray(rng.normal(size=(T * M, F, C))
                                             .astype(np.float32)))},
                  jnp.asarray(rng.normal(size=(T * M, F, C))
                              .astype(np.float32)))
    a, b = mk(), mk()
    out = ops.forest_merge(*a, *b)                    # warm the cached jit
    jax.block_until_ready(out[1])
    reps = 50
    t0 = time.perf_counter()
    for _ in range(reps):
        out = ops.forest_merge(*a, *b)
    jax.block_until_ready(out[1])
    return (time.perf_counter() - t0) / reps * 1e6


def run() -> dict:
    import jax

    if jax.default_backend() == "tpu":
        # the worker needs its own JAX runtime, and this process already
        # holds the chip: a child would fail or hang waiting for it
        raise RuntimeError(
            "benchmarks.dp races forced host devices in a child process; "
            "on a TPU host the parent holds the chip. The four-chip "
            "data-parallel path runs as `python chip_smoke.py --chips 4`")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.dp", "--worker"],
        capture_output=True, text=True, env=env, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(f"dp bench worker failed:\n{out.stderr[-3000:]}")
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    rep["merge_us_per_call"] = _merge_microbench()
    return rep


def to_rows(rep: dict):
    c = rep["config"]
    tag = f"T={c['T']} B={c['batch']} sync_every={c['sync_every']}"
    cores = rep.get("n_cores")
    return [
        ("dp_update_D1", rep["D1"]["us_per_instance"],
         f"{tag} single-device baseline (same run, best single core)"),
        ("dp_update_D2", rep["D2"]["us_per_instance"],
         f"{tag} speedup_vs_D1={rep['ceiling_speedup_D2']:.3f} — the "
         f"same-run host-parallelism ceiling proxy (2 shards, 2 cores)"),
        (f"dp_update_D{c['shards']}", rep["D4"]["us_per_instance"],
         f"{tag} speedup_vs_D1={rep['speedup_vs_D1']:.3f} "
         f"ceiling_frac={rep['ceiling_frac']:.3f} (devices-own-cores "
         f"race on {cores} cores; see docs/benchmarks.md)"),
        ("dp_forest_merge", rep["merge_us_per_call"],
         f"N={c['T'] * c['M']} tables F={c['F']} C={c['C']} "
         f"(the sync's folded-axis Chan merge, ops.forest_merge)"),
    ]


if __name__ == "__main__":
    if "--worker" in sys.argv:
        _worker()
    else:
        print(json.dumps(run(), indent=1))

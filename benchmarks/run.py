"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure + the system-level benches.
Prints ``name,us_per_call,derived`` CSV rows (harness contract) and dumps
the full JSON report to benchmarks/report.json (a run artifact,
gitignored — the committed trajectory lives in the BENCH_*.json files).

``--only SECTION [SECTION...]`` runs a subset (see ``SECTIONS``);
``--profile`` captures a bounded ``jax.profiler`` trace (one dispatch
per kernel family, written to ``profile_trace/`` at the repo root) and
harvests per-op compiled flops/bytes into ``BENCH_profile.fresh.json``
— both gitignored CI artifacts, see docs/benchmarks.md §How to profile.
"""
from __future__ import annotations

import argparse
import json
import os

import jax.numpy as jnp

from repro.models import layers as L

L.set_compute_dtype(jnp.float32)  # CPU container cannot execute bf16 dots

from benchmarks import (aos, dp, engine, false_splits, forest,  # noqa: E402
                        kernels, query_sweep, roofline, serve, tree)
from benchmarks import sketch as sketch_bench  # noqa: E402
from benchmarks.bench_io import REPO_ROOT, write_bench  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402


def _sec_aos(report, csv, args):
    rep = aos.run(full=args.full)
    report["aos"] = {k: v for k, v in rep.items() if k != "rows"}
    report["aos_rows"] = rep["rows"]
    by_ao = {}
    for r in rep["rows"]:
        by_ao.setdefault(r["ao"], []).append(r)
    for ao_name, rows in sorted(by_ao.items()):
        obs = sum(r["observe_s"] for r in rows) / len(rows)
        qry = sum(r["query_s"] for r in rows) / len(rows)
        merit = sum(r["merit"] for r in rows) / len(rows)
        elems = sum(r["elements"] for r in rows) / len(rows)
        csv.append((f"ao_observe_{ao_name}", obs * 1e6,
                    f"elements={elems:.0f}"))
        csv.append((f"ao_query_{ao_name}", qry * 1e6,
                    f"merit={merit:.4f}"))


def _sec_tree(report, csv, args):
    trep = tree.run()
    report["tree"] = trep
    rows = [
        ("hoeffding_tree_update", 1e6 / trep["kernel"]["instances_per_s"],
         f"mse_ratio={trep['kernel']['mse_ratio']:.4f}"
         f" speedup_vs_oracle={trep['kernel_speedup_vs_oracle']:.3f}"
         f" mse_rel_diff={trep['mse_rel_diff_vs_oracle']:.5f}"),
        ("hoeffding_tree_update_oracle",
         1e6 / trep["oracle"]["instances_per_s"],
         f"mse_ratio={trep['oracle']['mse_ratio']:.4f}"),
    ]
    csv.extend(rows)
    write_bench("BENCH_tree.json", rows)


def _sec_forest(report, csv, args):
    frep = forest.run()
    report["forest"] = frep
    preq = frep["prequential"]
    rows = [
        ("forest_update_vmapped",
         1e6 / frep["vmapped"]["instances_per_s"],
         f"T={frep['n_trees']}"
         f" speedup_vs_loop={frep['speedup_vs_loop']:.3f}"),
        ("forest_update_loop", 1e6 / frep["loop"]["instances_per_s"],
         f"T={frep['n_trees']} per-tree python loop baseline"),
        # accuracy-only row: us_per_call deliberately 0 so the timing is
        # not double-counted with the forest_update_vmapped row above
        ("forest_prequential_drift", 0.0,
         f"forest_mse={preq['forest_mse']:.3f}"
         f" best_member_mse={preq['best_member_mse']:.3f}"
         f" beats_best_member={preq['forest_beats_best_member']}"
         f" drift_resets={preq['drift_resets']}"),
    ]
    csv.extend(rows)
    write_bench("BENCH_forest.json", rows)


def _sec_serve(report, csv, args):
    srep = serve.run()
    report["serve"] = srep
    rows = serve.to_rows(srep)
    csv.extend(rows)
    write_bench("BENCH_serve.json", rows)


def _sec_engine(report, csv, args):
    erep = engine.run()
    report["engine"] = erep
    rows = engine.to_rows(erep)
    csv.extend(rows)
    write_bench("BENCH_engine.json", rows)


def _sec_dp(report, csv, args):
    # own subprocess for the forced-host-device XLA flags (§4.1)
    drep = dp.run()
    report["dp"] = drep
    rows = dp.to_rows(drep)
    csv.extend(rows)
    write_bench("BENCH_dp.json", rows)


def _sec_splits(report, csv, args):
    fsrep = false_splits.run()
    report["false_splits"] = fsrep
    rows = false_splits.to_rows(fsrep)
    csv.extend(rows)
    write_bench("BENCH_splits.json", rows)


def _sec_sketch(report, csv, args):
    skrep = sketch_bench.run()
    report["sketch"] = skrep
    rows = sketch_bench.to_rows(skrep)
    csv.extend(rows)
    write_bench("BENCH_sketch.json", rows)


def _profiled_kernels(report):
    """Per-op compiled-cost harvest + a BOUNDED profiler trace (one
    dispatch per family): the ``--profile`` artifacts (gitignored).
    The trace deliberately does NOT wrap the bench run itself — the
    profiler buffers every event in host memory, and minutes of
    tuner-race dispatches are an OOM, not a trace."""
    from repro.kernels import ops as kops
    from repro.perf import profile as pprof
    from repro.perf.tune import make_workloads

    w = make_workloads()
    backend = kops.resolve_backend(None)
    named = {
        "forest_update": (
            lambda *a: kops.forest_update(*a, backend=backend), w["update"]),
        "forest_best_splits": (
            lambda *a: kops.forest_best_splits(*a, backend=backend),
            w["query"]),
        "forest_route": (
            lambda *a: kops.forest_route(*a, depth=w["depth"],
                                         backend=backend), w["route"]),
        "forest_merge": (
            lambda *a: kops.forest_merge(*a, backend=backend), w["merge"]),
    }
    costs = pprof.profile_ops(
        named, logdir=os.path.join(REPO_ROOT, "profile_trace"))
    report["profile"] = costs
    pprof.write_report(costs, os.path.join(REPO_ROOT,
                                           "BENCH_profile.fresh.json"))
    return kernels.run()


def _sec_kernels(report, csv, args):
    krep = _profiled_kernels(report) if args.profile else kernels.run()
    report["kernels"] = krep
    rows = kernels.to_rows(krep)
    csv.extend(rows)
    write_bench("BENCH_kernels.json", rows)


def _sec_query(report, csv, args):
    qrep = query_sweep.run()
    report["query_sweep"] = qrep
    rows = query_sweep.to_rows(qrep)
    csv.extend(rows)
    write_bench("BENCH_query.json", rows)


def _sec_roofline(report, csv, args):
    rrep = roofline.run()
    report["roofline"] = rrep
    rows = roofline.to_rows(rrep)
    csv.extend(rows)
    write_bench("BENCH_roofline.json", rows)


SECTIONS = {
    "aos": _sec_aos,
    "tree": _sec_tree,
    "forest": _sec_forest,
    "serve": _sec_serve,
    "engine": _sec_engine,
    "dp": _sec_dp,
    "splits": _sec_splits,
    "sketch": _sec_sketch,
    "kernels": _sec_kernels,
    "query": _sec_query,
    "roofline": _sec_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full paper grid (sizes to 50k, 10 seeds)")
    ap.add_argument("--skip-aos", action="store_true")
    ap.add_argument("--only", nargs="+", choices=sorted(SECTIONS),
                    default=None, help="run only these sections")
    ap.add_argument("--profile", action="store_true",
                    help="bounded profiler trace (one dispatch per kernel "
                         "family) + per-op compiled costs")
    args = ap.parse_args()
    configure_compile_cache(REPO_ROOT)

    names = args.only or list(SECTIONS)
    if args.skip_aos and "aos" in names:
        names.remove("aos")

    report = {}
    csv = []
    for name in names:
        SECTIONS[name](report, csv, args)

    out_path = os.path.join(os.path.dirname(__file__), "report.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, default=float)

    print("name,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the very first two lines — before ANY other import — because jax
locks the device count on first init:
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs import SHAPES, get_arch, get_shape  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.train import steps as ST  # noqa: E402

# TPU v5e-like roofline constants (assignment spec)
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u32": 4, "s32": 4,
               "u16": 2, "s16": 2, "u8": 1, "s8": 1, "pred": 1,
               "s64": 8, "u64": 8, "c64": 8, "c128": 16,
               "f8e4m3fn": 1, "f8e5m2": 1}

SHAPE_RE = re.compile(r"\b(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|u64|s64|u32|s32|"
                      r"u16|s16|u8|s8|pred|c64|c128)\[([0-9,]*)\]")

_COLL_LINE = re.compile(
    r"=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in the HLO.

    The result shape is what travels per device for all-gather/all-to-all;
    for all-reduce it is ~2x on a ring (ignored — constant factor).  Async
    ``-start`` forms are counted once; ``-done`` lines don't match (no
    shape between '=' and the op keyword matters — they still parse, so we
    explicitly skip them).
    """
    per_kind = {}
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done.(" in line:
            continue
        m = _COLL_LINE.search(line)
        if not m:
            continue
        kind = m.group(2)
        bytes_ = 0
        for dt, dims in SHAPE_RE.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            bytes_ += n * DTYPE_BYTES.get(dt, 4)
        per_kind[kind] = per_kind.get(kind, 0) + bytes_
    return per_kind


def model_flops(cfg, shape):
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D per generated token decode
    (N = active params)."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch  # one decode step


def should_skip(cfg, shape) -> str:
    """Returns a reason string if this cell is a designed skip, else ''."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return "full attention at 524k ctx (quadratic) — designed skip per assignment"
    return ""


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             kv_chunk=512, microbatch=0, remat=True):
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    skip = should_skip(cfg, shape)
    result = {"arch": arch, "shape": shape_name,
              "mesh": "2x16x16" if multi_pod else "16x16"}
    if skip:
        result["status"] = "skipped"
        result["reason"] = skip
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        if shape.kind == "train":
            fn, in_sh, _, shapes = ST.build_train_step(
                cfg, shape, mesh, microbatch=microbatch, remat=remat,
                kv_chunk=kv_chunk, with_monitor=True, donate=False)
            pshapes, oshapes, bshapes, mshape = shapes
            with mesh:
                lowered = fn.lower(pshapes, oshapes, bshapes, mshape)
        elif shape.kind == "prefill":
            prefill_jit, _, shapes = ST.build_serve_steps(
                cfg, shape, mesh, kv_chunk=kv_chunk)
            pshapes, cache_shapes, prefill_shapes, _ = shapes
            with mesh:
                lowered = prefill_jit.lower(pshapes, prefill_shapes, cache_shapes)
        else:  # decode
            _, decode_jit, shapes = ST.build_serve_steps(
                cfg, shape, mesh, kv_chunk=kv_chunk)
            pshapes, cache_shapes, _, dec = shapes
            with mesh:
                lowered = decode_jit.lower(pshapes, dec["token"], cache_shapes,
                                           dec["pos"])
        compiled = lowered.compile()
    except Exception as e:  # a failure here is a bug in our sharding
        result["status"] = "FAILED"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
        return result

    from repro.launch import hlocost
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    # trip-count-aware walk (cost_analysis counts scan bodies once)
    walked = hlocost.analyze(hlo)
    coll = walked["collectives"]
    coll_total = walked["collective_bytes"]

    flops = walked["flops"]
    bytes_ = walked["bytes"]
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_ / HBM_BW
    t_coll = coll_total / ICI_BW
    mf = model_flops(cfg, shape)

    result.update({
        "status": "ok",
        "compile_s": round(time.time() - t0, 1),
        "chips": chips,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_,
        "collective_bytes_per_chip": coll_total,
        "collective_breakdown": coll,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_coll)], key=lambda kv: kv[1])[0],
        "model_flops_total": mf,
        "useful_flops_ratio": (mf / chips) / flops if flops else 0.0,
        "roofline_fraction": (mf / chips / PEAK_FLOPS)
            / max(t_compute, t_memory, t_coll)
            if max(t_compute, t_memory, t_coll) > 0 else 0.0,
        "raw_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "memory_analysis": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        },
    })
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="/root/repo/dryrun_results.json")
    ap.add_argument("--kv-chunk", type=int, default=512)
    ap.add_argument("--append", action="store_true")
    args = ap.parse_args()

    archs = sorted(configs.ARCHS) if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    if args.append and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = (arch, shape, "2x16x16" if mp else "16x16")
                if key in done:
                    continue
                print(f"=== {arch} x {shape} x {key[2]} ===", flush=True)
                r = run_cell(arch, shape, mp, kv_chunk=args.kv_chunk)
                print(json.dumps({k: v for k, v in r.items()
                                  if k not in ("traceback", "collective_breakdown",
                                               "memory_analysis")}),
                      flush=True)
                results.append(r)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_fail = sum(1 for r in results if r["status"] == "FAILED")
    print(f"\n{len(results)} cells, {n_fail} failures")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

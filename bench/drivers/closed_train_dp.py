"""The data-parallel trainer, closed loop: ``ServingEngine.train_once`` is
fed the next global batch (one stream batch per shard: global batch g
holds stream batches shards*g .. shards*g+shards-1, shard d's rows d-th)
as soon as it returns; the trainer merges and attempts every
``dp_sync_every`` global batches, and the engine publishes every
``sync_every``.  No requests.

Set-up learns ``prefix_batches`` global batches through the same call
(the first ``check_steps`` of them compared with the reference) and ends
on a publish.  The window runs until ``seconds`` have passed, and at
least to ``compare_step``, and then on to the next publish, so it ends on
a global batch the devices have finished: ``train_rows_per_s`` is every
row absorbed in the window over the window's whole length.

At ``compare_step``, a publish inside the window, the driver keeps
references to the trainer's forest and to the snapshot published from
it; both are immutable, so nothing is read back in the window.  After
it they are compared with the plain protocol (``correct_dp.py``) at that
step, and the forest's bin mass at the window's last publish with the
Poisson weights the reference draws up to there.
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import numpy as np

import correct
import correct_dp
import program
import program_dp
from harness import BenchError, Outcome, Spans, Window, device_info, say
from streams import Stream


def run(ctx) -> Outcome:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    D, sync = cfg["shards"], cfg["engine"]["sync_every"]
    prefix, k_check = mix["prefix_batches"], mix["check_steps"]
    at = mix["compare_step"]
    if prefix % sync or k_check > prefix or at % sync or at <= prefix:
        raise ValueError("prefix_batches and compare_step must end on a "
                         "publish, and the prefix hold the checked steps")
    try:
        program_dp.sharding_module()
    except BenchError as e:
        say(f"no result: {e}")
        sys.exit(2)
    stream = Stream(cfg, ctx.seed)
    rows_per_step = D * cfg["batch_rows"]
    eng = program_dp.build_engine(
        cfg, ctx.seed, functools.partial(correct_dp.global_batch, stream, D),
        ctx.devs[:D])
    early = []
    for s in range(prefix):
        eng.train_once()
        if s < k_check:
            early.append(correct.leaf_norms(
                program_dp.trainer_forest(eng)))
    leaves0 = program_dp.leaf_count(program_dp.trainer_forest(eng))
    ex0 = program_dp.exchange(eng)
    spans = Spans(ctx.trace)
    setup_s = time.perf_counter() - ctx.t_start
    compiles0 = ctx.counter.count
    steps, held = 0, None
    with Window(ctx.trace) as win:
        while True:
            g = prefix + steps + 1
            publish = g % sync == 0
            with spans.span("train_once.publish" if publish
                            else "train_once"):
                eng.train_once()
            steps += 1
            if g == at:
                held = (program_dp.trainer_forest(eng),
                        eng.snapshot_for_version(eng.published_version))
            if publish and g >= at \
                    and time.perf_counter() - win.t0 >= ctx.seconds:
                break
    window_compiles = ctx.counter.count - compiles0
    rows = steps * rows_per_step
    failures = program.engine_failures(eng)
    ex1 = program_dp.exchange(eng)
    device = device_info(ctx.devs[:D])
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in ctx.devs[:D]]

    _, final_step = program.published_step(eng)
    forest = program_dp.trainer_forest(eng)
    leaves1 = program_dp.leaf_count(forest)
    mass = program_dp.bin_mass(forest)
    held_forest, held_snap = held
    probe = stream.request_rows(0, mix["probe_rows"], tag=3)
    answered = program.predict_snapshot(held_snap, probe)
    held_norms = correct.leaf_norms(held_forest)
    held_step = int(np.asarray(held_snap.step))
    del eng, forest, held, held_forest, held_snap
    gc.collect()

    t_ref = time.perf_counter()
    ref = correct_dp.ReferenceRunDP(cfg, stream, ctx.seed)
    gaps = []
    for s, got in enumerate(early):
        ref.advance_to(s + 1)
        gaps.append(correct.norm_gap(got, ref.norms()))
    ref.advance_to(held_step)
    fgap = correct.norm_gap(held_norms, ref.norms())
    pgap = correct.prediction_gap([answered], [ref.predict(probe)])
    drawn = ref.drawn_mass(final_step)
    say(f"reference: {held_step} global steps and the weights of "
        f"{final_step} in {time.perf_counter() - t_ref:.3f} s")
    exch = {k: ex1[k] - ex0[k] for k in ex1}
    return Outcome(
        attempted=steps, failed=failures,
        end_to_end={"train_rows_per_s": rows / win.seconds},
        checks=correct_dp.checks({
            "state_gap": max(gaps), "final_state_gap": fgap,
            "predict_gap": pgap,
            "mass_gap": correct_dp.mass_gap(mass, drawn),
            "engine_failures": failures}),
        device=device, setup_s=setup_s,
        notes=[f"compiles in the window: {window_compiles}",
               f"window: {steps} global steps of {D} x "
               f"{cfg['batch_rows']} rows, {rows} rows, "
               f"{win.seconds:.6f} s; compared at step {held_step}, "
               f"last published step {final_step}",
               f"exchange in the window: {exch['dp_syncs']} syncs, "
               f"{exch['dp_exchange_bytes']} bytes, host "
               f"{exch['dp_exchange_s']:.6f} s in dp.gather and "
               f"dp.broadcast",
               f"forest in the window: leaves {leaves0} -> {leaves1}; "
               f"bin mass per member "
               f"{mass.astype(np.int64).tolist()}, drawn "
               f"{drawn.tolist()}",
               f"peak bytes per device: {peaks}"],
        layer_ctx={"steps": steps, "syncs": exch["dp_syncs"],
                   "spans": spans},
        window=win)

"""Trainer only, closed loop: ``ServingEngine.train_once`` is fed the next
stream batch as soon as it returns, and publishes every ``sync_every``
steps as a deployment does.  No requests.

Set-up learns ``prefix_batches`` through the same call (the first
``check_steps`` of them are compared with the reference, and so is the
whole state at the last step of the window) and ends on a publish, which
waits for the device.  The window runs until ``seconds``
have passed and then on to the next publish, so it ends on a step the
device has finished: ``train_rows_per_s`` is every row absorbed in the
window over the window's whole length.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import correct
import program
from harness import Outcome, Spans, Window, device_info, say
from streams import Stream


def run(ctx) -> Outcome:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    sync = cfg["engine"]["sync_every"]
    prefix, k_check = mix["prefix_batches"], mix["check_steps"]
    if prefix % sync or k_check > prefix:
        raise ValueError("prefix_batches must end on a publish and hold "
                         "the checked steps")
    stream = Stream(cfg, ctx.seed)
    eng = program.build_engine(cfg, ctx.seed, stream.batch)
    early = []
    for s in range(prefix):
        eng.train_once()
        if s < k_check:
            early.append(correct.leaf_norms(program.trainer_state(eng)))
    counts0 = program.forest_counts(eng)
    spans = Spans(ctx.trace)
    setup_s = time.perf_counter() - ctx.t_start
    compiles0 = ctx.counter.count
    steps = 0
    with Window(ctx.trace) as win:
        while True:
            publish = (prefix + steps + 1) % sync == 0
            with spans.span("train_once.publish" if publish
                            else "train_once"):
                eng.train_once()
            steps += 1
            if publish and time.perf_counter() - win.t0 >= ctx.seconds:
                break
    window_compiles = ctx.counter.count - compiles0
    rows = steps * cfg["batch_rows"]
    failures = program.engine_failures(eng)
    device = device_info(ctx.devs)

    counts1 = program.forest_counts(eng)
    final = eng.snapshot_for_version(eng.published_version)
    final_step = int(np.asarray(final.step))
    probe = stream.request_rows(0, mix["probe_rows"], tag=3)
    answered = program.predict_snapshot(final, probe)
    last = correct.leaf_norms(program.trainer_state(eng))
    del eng, final
    gc.collect()

    t_ref = time.perf_counter()
    ref = correct.ReferenceRun(cfg, stream, ctx.seed)
    gaps = []
    for s, got in enumerate(early):
        ref.advance_to(s + 1)
        gaps.append(correct.norm_gap(got, ref.norms()))
    ref.advance_to(final_step)
    pgap = correct.prediction_gap([answered], [ref.predict(probe)])
    fgap = correct.norm_gap(last, ref.norms())
    attempts = ref.attempted(prefix, final_step)
    say(f"reference: {final_step} steps in "
        f"{time.perf_counter() - t_ref:.3f} s")
    return Outcome(
        attempted=steps, failed=failures,
        end_to_end={"train_rows_per_s": rows / win.seconds},
        checks=correct.checks({"state_gap": max(gaps),
                               "final_state_gap": fgap,
                               "predict_gap": pgap,
                               "engine_failures": failures}),
        device=device, setup_s=setup_s,
        notes=[f"compiles in the window: {window_compiles}",
               f"window: {steps} steps, {rows} rows, {win.seconds:.6f} s; "
               f"published step {final_step}",
               f"forest in the window: leaves {counts0['leaves']} -> "
               f"{counts1['leaves']}, member resets "
               f"{counts1['resets'] - counts0['resets']} (reference: "
               f"{ref.resets()} since step 0), leaves that attempted a "
               f"split {attempts} (reference)"],
        layer_ctx={"steps": steps, "spans": spans},
        window=win)

"""Trainer plus open-loop requests on one engine.

Three threads share one ``ServingEngine``: the trainer calls
``train_once`` back to back (publishing every ``sync_every`` steps), the
server calls ``serve_once`` whenever the queue holds rows (the same two
methods ``ServingEngine.start`` loops), and the load generator submits
each request when it is due, whatever the engine is doing.

``serve_p95_ms`` times each request from when it was due to when its
ticket resolved; a shed or unanswered request counts as missing every
limit.  ``snapshot_age_p95_s`` is, per served request, its resolution time
minus the time the stream handed the trainer the newest batch inside the
snapshot version that answered it.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

import arrivals
import correct
import program
from harness import (Outcome, Spans, Window, device_info, percentile,
                     say)
from streams import Stream


def _ladder(max_rows: int, lo: int = 128):
    b, out = lo, [lo]
    while b < max_rows:
        b *= 2
        out.append(b)
    return out


def run(ctx) -> Outcome:
    cfg, mix = ctx.cell.config, ctx.cell.traffic
    sync = cfg["engine"]["sync_every"]
    prefix, k_check = mix["prefix_batches"], mix["check_steps"]
    stream = Stream(cfg, ctx.seed)
    handed = {}

    def stream_fn(s):
        handed[s] = time.perf_counter()
        return stream.batch(s)

    eng = program.build_engine(cfg, ctx.seed, stream_fn)
    step_of = {}        # published version -> the trainer step it froze
    early = []
    for s in range(prefix):
        eng.train_once()
        if s < k_check:
            early.append(correct.leaf_norms(program.trainer_state(eng)))
    if prefix % sync:
        raise ValueError("prefix_batches must end on a publish")
    step_of.update([program.published_step(eng)])

    # warm the serving programs of every batch bucket on the grown model
    max_pack = max(cfg["engine"]["max_batch_rows"], mix["rows_max"])
    for b in _ladder(max_pack):
        for rows in (b, b - 1):
            eng.submit(stream.request_rows(rows, rows, tag=5))
            while eng.serve_once():
                pass

    sched = arrivals.schedule(mix, ctx.seconds, ctx.seed)
    reqs = [stream.request_rows(k, rows) for k, (_, rows) in
            enumerate(sched)]
    spans = Spans(ctx.trace)
    stop = threading.Event()

    def trainer():
        while not stop.is_set():
            with spans.span("train_once"):
                eng.train_once()
            step_of.update([program.published_step(eng)])

    def server():
        while not stop.is_set():
            t0 = time.perf_counter()
            if eng.serve_once():
                spans.add("serve_once", t0, time.perf_counter())
            else:
                time.sleep(0.0005)

    threads = [threading.Thread(target=trainer, name="bench-trainer"),
               threading.Thread(target=server, name="bench-server")]
    tickets, due_at, sent_at, depth = [], [], [], []
    setup_s = time.perf_counter() - ctx.t_start
    compiles0 = ctx.counter.count
    steps0 = len(handed)
    with Window(ctx.trace) as win:
        for t in threads:
            t.start()
        try:
            for (due, _), X in zip(sched, reqs):
                target = win.t0 + due
                wait = target - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent_at.append(time.perf_counter())
                due_at.append(target)
                tickets.append(eng.submit(X))
                depth.append(eng.queued_rows)
            deadline = win.t0 + ctx.seconds + mix["answer_wait_s"]
            for t in tickets:
                t.wait(max(0.0, deadline - time.perf_counter()))
            win.close()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=600)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("an engine thread did not stop")
    window_compiles = ctx.counter.count - compiles0
    steps = len(handed) - steps0
    failures = program.engine_failures(eng)
    device = device_info(ctx.devs)
    last = correct.leaf_norms(program.trainer_state(eng))

    lat, age, waits, done = [], [], [], []
    served_spans = spans.spans.get("serve_once", [])
    starts = np.array([a for a, _ in served_spans])
    ends = np.array([b for _, b in served_spans])
    for t, due in zip(tickets, due_at):
        if t.status != "done" or t.t_done is None:
            lat.append(float("inf"))
            continue
        lat.append(t.t_done - due)
        done.append(t)
        s = step_of[t.version]
        age.append(t.t_done - handed[s - 1] if s > 0 else float("inf"))
        i = int(np.searchsorted(ends, t.t_done))
        if i < len(starts) and starts[i] <= t.t_done:
            waits.append(starts[i] - due)
    unanswered = sum(t.status == "queued" for t in tickets)
    shed = sum(t.status == "shed" for t in tickets)
    late = [s - d for s, d in zip(sent_at, due_at)]

    # a sample of answers drawn from the seed, with the largest and the
    # newest requests in it
    rng = np.random.default_rng([ctx.seed, 4])
    pick = set(rng.choice(len(done), min(mix["check_answers"], len(done)),
                          replace=False).tolist()) if done else set()
    if done:
        pick.add(int(np.argmax([t.rows for t in done])))
        pick.add(int(np.argmax([t.version for t in done])))
    sample = sorted((done[i] for i in pick), key=lambda t: t.version)
    sample = [(step_of[t.version], t.X, np.asarray(t.result))
              for t in sample]
    del eng, tickets, done
    gc.collect()

    t_ref = time.perf_counter()
    ref = correct.ReferenceRun(cfg, stream, ctx.seed)
    gaps = []
    for s, got in enumerate(early):
        ref.advance_to(s + 1)
        gaps.append(correct.norm_gap(got, ref.norms()))
    got, want = [], []
    for s, X, res in sample:
        ref.advance_to(s)
        got.append(res)
        want.append(ref.predict(X))
    pgap = correct.prediction_gap(got, want) if sample else float("inf")
    ref.advance_to(len(handed))
    fgap = correct.norm_gap(last, ref.norms())
    say(f"reference: {ref.steps} steps in "
        f"{time.perf_counter() - t_ref:.3f} s")
    n = len(sched)
    return Outcome(
        attempted=n, failed=shed + unanswered,
        end_to_end={"serve_p95_ms": percentile(lat, 95) * 1e3,
                    "snapshot_age_p95_s": percentile(age, 95)},
        checks=correct.checks({"state_gap": max(gaps),
                               "final_state_gap": fgap, "predict_gap": pgap,
                               "engine_failures": failures,
                               "unanswered": unanswered}),
        device=device, setup_s=setup_s,
        notes=[f"compiles in the window: {window_compiles}",
               f"load generator lateness p95: "
               f"{percentile(late, 95) * 1e3:.3f} ms (max "
               f"{max(late, default=0.0) * 1e3:.3f} ms)",
               f"window: {n} requests ({sum(r for _, r in sched)} rows) "
               f"due over {ctx.seconds} s, {shed} shed, {unanswered} "
               f"unanswered, {steps} train steps, {len(served_spans)} "
               f"serving batches; queued rows at submit, max of each half "
               f"{max(depth[:len(depth) // 2], default=0)}, "
               f"{max(depth[len(depth) // 2:], default=0)}; p50 "
               f"{percentile(lat, 50) * 1e3:.3f} ms"],
        layer_ctx={"steps": steps, "spans": spans,
                   "serve_s": [b - a for a, b in served_spans],
                   "queue_wait_s": waits},
        window=win)

"""Continuous-serving engine: zero-downtime snapshot hot-swap under load
(DESIGN.md §5.6).

After PR 4 the repo could *freeze* and *serve*; after PR 5 it could
*train at scale* — but nothing owned the lifecycle between the two.
:class:`ServingEngine` is that owner: one object that runs
train-and-serve concurrently and stays up through the faults a real
deployment throws at it.

**Admission queue.**  Requests arrive open-loop (ragged row counts,
bursty rates) through :meth:`ServingEngine.submit`, which hands back a
:class:`Ticket` immediately.  Admission is bounded by
``cfg.max_queue_rows``: a request that would overflow is SHED at the
door — its ticket resolves ``shed`` and the ``shed_requests`` /
``shed_rows`` counters advance — never silently dropped and never
allowed to grow the queue without bound (backpressure by load
shedding, the only graceful answer an open-loop process permits).
Admitted tickets are packed FIFO into serving batches of up to
``cfg.max_batch_rows`` rows; the batch then rides
:func:`repro.core.serve.predict_snapshot`, whose pow-2 padding lands it
exactly on the cached-jit batch buckets PR 4's dispatch keys on — many
small requests cost one dispatch, and a steady mix of request sizes
never recompiles.

**Atomic publish.**  The trainer periodically
:func:`repro.core.serve.freeze`\\ s its live state into a versioned
:class:`~repro.core.serve.Snapshot` and offers it to
:meth:`ServingEngine.publish`.  The publish path is the robustness
choke point: the candidate passes the fault-injection hook (where tests
corrupt/drop/delay it), then :func:`repro.core.serve.validate_snapshot`
(the rollback gate — an invalid snapshot is counted and DISCARDED, the
last good version keeps serving), then a monotone-version check, and
only then is it swapped in — a single reference assignment of an
immutable record, so a concurrent server thread sees either the old
snapshot or the new one, never a torn mix.  In-flight batches pinned
the old record before the swap and drain on it unharmed.

**Fault tolerance.**  A :class:`repro.core.faults.FaultInjector` hooks
``trainer.step`` / ``publish`` / ``ckpt.save``.  A trainer killed
mid-sync-window is caught, counted, and recovered: state restores from
the newest *valid* checkpoint (:meth:`Checkpointer.restore_latest`
skips corrupt ones), the stream rewinds to that step, and the restored
model is re-published immediately — so serving continues from a
validated snapshot throughout and fresh publishes resume within one
sync window of the restart.  A staleness watchdog tracks the age of the
published snapshot against the ``sync_every`` cadence and raises the
``stale`` flag (plus a ``stale_events`` counter) when freshness falls
``cfg.staleness_factor`` windows behind — surfacing silent publish
loss (dropped publishes, a wedged trainer) that no exception ever
reports.

The engine is a deterministic state machine first and threads second:
:meth:`train_once` / :meth:`serve_once` single-step the two loops (what
tests/test_engine.py drives), and :meth:`start` / :meth:`stop` run the
same methods on daemon threads for the open-loop deployment shape
(examples/engine_stream.py, benchmarks/engine.py).
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core import faults as fl
from repro.core import forest as fr
from repro.core import hoeffding as ht
from repro.core import serve as sv

__all__ = ["EngineConfig", "Ticket", "ServingEngine"]


@dataclass(frozen=True)
class EngineConfig:
    """Static engine knobs.

    sync_every:       trainer batches between freeze+publish boundaries
                      (the freshness cadence; ROADMAP's staleness knob).
    ckpt_every:       publishes between checkpoint saves (0 = never).
    max_queue_rows:   admission bound — rows queued beyond this are shed.
    max_batch_rows:   serving pack cap — queued tickets are concatenated
                      up to this many rows per dispatch (pow-2 bucketed
                      downstream by ``predict_snapshot``).
    keep_versions:    published snapshots retained for drain/rollback
                      audits (``snapshot_for_version``).
    staleness_factor: ``stale`` when the published snapshot's age exceeds
                      ``staleness_factor * sync_every`` trainer steps.
    backend:          kernel backend for serving (None = platform auto).
    """
    sync_every: int = 4
    ckpt_every: int = 1
    max_queue_rows: int = 8192
    max_batch_rows: int = 2048
    keep_versions: int = 4
    staleness_factor: float = 3.0
    backend: Optional[str] = None


class Ticket:
    """One admitted (or shed) request: a thread-safe future.

    ``status``: ``"queued" | "done" | "shed"``.  ``wait(timeout)``
    blocks until resolution; ``result`` is the (B,) f32 predictions,
    ``version`` the snapshot version that served them (the bit-identity
    pin: ``predict_snapshot(engine.snapshot_for_version(t.version), X)``
    must equal ``t.result`` exactly), ``latency_s`` the submit→resolve
    wall time.
    """

    __slots__ = ("X", "status", "result", "version", "t_submit", "t_done",
                 "_event")

    def __init__(self, X: np.ndarray):
        self.X = X
        self.status = "queued"
        self.result: Optional[np.ndarray] = None
        self.version: Optional[int] = None
        self.t_submit = time.perf_counter()
        self.t_done: Optional[float] = None
        self._event = threading.Event()

    @property
    def rows(self) -> int:
        return self.X.shape[0]

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    def _resolve(self, status: str, result=None, version=None):
        self.status = status
        self.result = result
        self.version = version
        self.t_done = time.perf_counter()
        self._event.set()


@functools.partial(jax.jit, static_argnums=0)
def _learn(cfg_model, state, X, y):
    """One trainer batch as ONE compiled program per batch shape: called
    eagerly, the update's attempt ``lax.cond`` would be traced and
    compiled anew on every batch."""
    if "trees" in state:
        return fr.update(cfg_model, state, X, y)[0]
    return ht.update(cfg_model, state, X, y)


class _OneDevice(NamedTuple):
    """A tree or forest config as the engine's trainer: ``_learn`` on
    one device, where every step is a sync, nothing moves between
    devices and the state is the model."""
    cfg: Any
    sync_every = 1

    def update(self, state, X, y):
        return _learn(self.cfg, state, X, y), {}

    def model(self, state):
        return state

    @property
    def exchange(self):
        return {"dp_syncs": 0, "dp_exchange_bytes": 0, "dp_exchange_s": 0.0}


class _Published:
    """Immutable published record — the single swapped reference.

    Readers grab ``engine._published`` ONCE per serving batch; because
    the record never mutates after construction, that one read pins a
    consistent (snapshot, version, step, wall-clock) tuple no matter
    when the publisher swaps the attribute underneath them.
    """

    __slots__ = ("snap", "version", "step", "wall")

    def __init__(self, snap: sv.Snapshot, version: int, step: int):
        self.snap = snap
        self.version = version
        self.step = step
        self.wall = time.monotonic()


class ServingEngine:
    """Concurrent train-and-serve over one model lineage.

    ``trainer``: what learns each batch.  A
    :class:`repro.core.forest.ForestConfig` (its ``"trees"``-keyed state)
    or a :class:`repro.core.hoeffding.HTRConfig` (single tree) trains on
    one device through one compiled program (``_learn``); a
    :class:`repro.train.sharding.DataParallelForest` trains one global
    batch over its mesh.  Either way the engine calls
    ``update(state, X, y) -> (state, aux)`` once a step and publishes
    ``model(state)``, the state :func:`repro.core.serve.freeze` packs,
    and reports the trainer's ``exchange`` counts in :meth:`metrics`.
    The engine's ``sync_every`` must be a multiple of the trainer's
    ``sync_every`` (``ValueError`` otherwise): a publish reads the forest
    only where the trainer has just merged it.
    ``state``: the initial trained-or-fresh trainer state.
    ``stream``: ``stream(step) -> (X, y) | None`` — a *deterministic*
    batch source indexed by trainer step (None = exhausted).  Indexing by
    step is what makes crash-recovery exact: after a restore to step s
    the trainer replays the stream from s, identically.
    ``checkpointer``: optional :class:`repro.checkpoint.ckpt.Checkpointer`
    — without one, recovery restarts from the in-memory state instead.
    ``injector``: optional :class:`repro.core.faults.FaultInjector`.

    The constructor publishes version 1 from the initial state, so the
    engine serves from its very first request — publish is a hot-SWAP,
    never a cold start.
    """

    def __init__(self, trainer, state, stream: Callable, *,
                 cfg: EngineConfig = EngineConfig(),
                 checkpointer=None, injector: Optional[fl.FaultInjector] = None):
        self.cfg = cfg
        self._model_cfg = trainer
        self._trainer = _OneDevice(trainer) \
            if isinstance(trainer, (fr.ForestConfig, ht.HTRConfig)) \
            else trainer
        if cfg.sync_every % self._trainer.sync_every:
            raise ValueError(
                f"engine sync_every {cfg.sync_every} is not a multiple of "
                f"the trainer's sync_every {self._trainer.sync_every}")
        self._state = state
        self._stream = stream
        self._ckpt = checkpointer
        self._injector = injector or fl.FaultInjector()

        self._trainer_step = 0
        self._queue: List[Ticket] = []
        self._queued_rows = 0
        self._q_lock = threading.Lock()
        self._q_event = threading.Event()
        self._pub_lock = threading.Lock()
        self._published: Optional[_Published] = None
        self._versions: Dict[int, sv.Snapshot] = {}
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._m_lock = threading.Lock()
        self._metrics = {
            "admitted_requests": 0, "admitted_rows": 0,
            "served_requests": 0, "served_rows": 0, "serve_batches": 0,
            "shed_requests": 0, "shed_rows": 0,
            "publishes": 0, "publish_failures": 0, "rollbacks": 0,
            "publishes_dropped": 0, "trainer_crashes": 0, "recoveries": 0,
            "ckpt_failures": 0, "stale_events": 0, "max_queue_rows_seen": 0,
            "publish_host_s": 0.0, "publish_wait_s": 0.0,
        }
        # the newest caught exception per recovery path, "Type: message"
        # (None until one happens): a counter alone hides what went wrong
        self._errors = {"last_trainer_error": None,
                        "last_publish_error": None,
                        "last_ckpt_error": None}
        self.publish_from_state()            # version 1: never cold-start
        assert self._published is not None

    # -- metrics ----------------------------------------------------------

    def _bump(self, **kv):
        with self._m_lock:
            for k, v in kv.items():
                self._metrics[k] += v

    def _note_error(self, key: str, exc: BaseException):
        with self._m_lock:
            self._errors[key] = f"{type(exc).__name__}: {exc}"

    def metrics(self) -> Dict[str, Any]:
        """Counter snapshot + the last caught trainer / publish / checkpoint
        exception (``last_*_error``) + the staleness watchdog's verdict.

        ``publish_wait_s`` sums the seconds publishes spent reading the
        live state back from the device (``serve.freeze.fetch``, which
        first waits for every step still queued there);
        ``publish_host_s`` the rest of every ``engine.publish`` span:
        host work while the device has nothing queued.  A data-parallel
        trainer's syncs add ``dp_syncs``, ``dp_exchange_bytes`` (deltas
        gathered plus forest broadcast) and ``dp_exchange_s`` (host
        seconds in the ``dp.gather`` and ``dp.broadcast`` spans)."""
        with self._m_lock:
            out = dict(self._metrics)
            out.update(self._errors)
        out.update(self._trainer.exchange)
        out.update(self.staleness())
        return out

    def staleness(self) -> Dict[str, Any]:
        """Snapshot age vs the ``sync_every`` cadence (the watchdog).

        ``age_steps`` = trainer steps since the published snapshot was
        frozen; ``stale`` flips when it exceeds
        ``staleness_factor * sync_every`` — the signature of dropped
        publishes or a wedged trainer, which no exception surfaces.
        """
        rec = self._published
        age_steps = self._trainer_step - rec.step
        limit = self.cfg.staleness_factor * self.cfg.sync_every
        return {
            "published_version": rec.version,
            "published_step": rec.step,
            "age_steps": age_steps,
            "age_s": time.monotonic() - rec.wall,
            "stale": age_steps > limit,
        }

    # -- publish path -----------------------------------------------------

    @property
    def published_version(self) -> int:
        return self._published.version

    def snapshot_for_version(self, version: int) -> sv.Snapshot:
        """A retained published snapshot by version (audit/bit-identity
        hook; the last ``cfg.keep_versions`` publishes are retained)."""
        return self._versions[version]

    @contextlib.contextmanager
    def _publishing(self):
        """The ``engine.publish`` span.  Yields a dict for
        :func:`repro.core.serve.freeze`'s ``timings``; its ``fetch``
        seconds go to ``publish_wait_s``, the span's other seconds to
        ``publish_host_s``."""
        timings = {"fetch": 0.0}
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("engine.publish"):
            yield timings
        wait = timings["fetch"]
        self._bump(publish_wait_s=wait,
                   publish_host_s=time.perf_counter() - t0 - wait)

    def publish_from_state(self) -> bool:
        """Freeze the live trainer state and offer it for publication."""
        with self._publishing() as timings:
            with self._pub_lock:
                version = (self._published.version + 1) \
                    if self._published else 1
            snap = sv.freeze(self._trainer.model(self._state),
                             version=version, step=self._trainer_step,
                             timings=timings)
            return self._publish(snap)

    def publish(self, snap: sv.Snapshot) -> bool:
        """Validate → atomically swap; False = rejected (rollback).

        The candidate first passes the ``publish`` fault site (tests
        corrupt/drop/delay it there), then the
        :func:`~repro.core.serve.validate_snapshot` invariants and a
        monotone-version gate.  Any failure leaves the previous snapshot
        serving (that IS the rollback — the reference never moved) and
        advances ``publish_failures`` / ``rollbacks``.  Success swaps
        one immutable record under ``_pub_lock`` and retains the
        version for audits.
        """
        with self._publishing():
            return self._publish(snap)

    def _publish(self, snap: sv.Snapshot) -> bool:
        """The body of :meth:`publish`, inside its span."""
        try:
            snap = self._injector.fire("publish", snap)
        except fl.DropSignal:
            self._bump(publishes_dropped=1)
            return False
        try:
            sv.validate_snapshot(snap)
            with jax.profiler.TraceAnnotation("engine.swap"), \
                    self._pub_lock:
                if (self._published is not None
                        and int(np.asarray(snap.version))
                        <= self._published.version):
                    raise sv.SnapshotValidationError(
                        f"version {int(np.asarray(snap.version))} is not "
                        f"past published v{self._published.version}")
                rec = _Published(snap, int(np.asarray(snap.version)),
                                 int(np.asarray(snap.step)))
                self._published = rec          # THE atomic hot-swap
                self._versions[rec.version] = snap
                while len(self._versions) > self.cfg.keep_versions:
                    del self._versions[min(self._versions)]
        except sv.SnapshotValidationError as e:
            self._note_error("last_publish_error", e)
            self._bump(publish_failures=1, rollbacks=1)
            return False
        self._bump(publishes=1)
        if self._ckpt is not None and self.cfg.ckpt_every \
                and self._metrics["publishes"] % self.cfg.ckpt_every == 0:
            self._checkpoint()
        return True

    def _checkpoint(self):
        try:
            with jax.profiler.TraceAnnotation("engine.checkpoint"):
                self._injector.fire("ckpt.save")
                self._ckpt.save(self._trainer_step, self._state,
                                blocking=True)
        except Exception as e:
            # a failed save must never take the trainer down: the last
            # good checkpoint is still on disk and restore skips torn ones
            self._note_error("last_ckpt_error", e)
            self._bump(ckpt_failures=1)

    # -- trainer ----------------------------------------------------------

    def train_once(self) -> bool:
        """One trainer batch (False = stream exhausted).

        Absorbs ``stream(step)``, advances the step, and at every
        ``sync_every`` boundary freezes + publishes.  Any exception out
        of the step — injected kill or organic — is caught, counted in
        ``trainer_crashes`` (its type and message kept as
        ``metrics()["last_trainer_error"]``), and answered with
        :meth:`recover`; the engine keeps serving the published snapshot
        throughout.  The call is the profiler span ``engine.train_once``
        with the step as its ``step`` argument.
        """
        with jax.profiler.TraceAnnotation("engine.train_once",
                                          step=self._trainer_step):
            batch = self._stream(self._trainer_step)
            if batch is None:
                return False
            try:
                self._injector.fire("trainer.step")
                self._state = self._train_step(batch)
                self._trainer_step += 1
                if self._trainer_step % self.cfg.sync_every == 0:
                    self.publish_from_state()
                elif self.staleness()["stale"]:
                    self._bump(stale_events=1)
            except Exception as e:
                self._note_error("last_trainer_error", e)
                self._bump(trainer_crashes=1)
                self.recover()
            return True

    def _train_step(self, batch):
        X, y = batch
        return self._trainer.update(self._state, X, y)[0]

    def recover(self):
        """Crash recovery: restore the newest valid checkpoint (or fall
        back to the in-memory state), rewind the stream to its step, and
        RE-PUBLISH immediately — a validated snapshot of the restored
        model goes live within one publish, and the normal cadence
        resumes from there (fresh publishes within one sync window)."""
        with jax.profiler.TraceAnnotation("engine.recover"):
            if self._ckpt is not None:
                try:
                    template = jax.eval_shape(lambda: self._state)
                    state, step = self._ckpt.restore_latest(
                        template, return_step=True)
                    self._state, self._trainer_step = state, step
                except FileNotFoundError:
                    pass                  # no valid checkpoint: keep memory
            self._bump(recoveries=1)
            self.publish_from_state()

    # -- admission + serving ----------------------------------------------

    def submit(self, X) -> Ticket:
        """Admit a request (or shed it) — never blocks on service.

        Admission is all-or-nothing per request: if the queue cannot
        hold the WHOLE request under ``max_queue_rows``, the ticket
        resolves ``shed`` immediately and the shed counters advance by
        exactly this request — the excess is counted, not dropped.  The
        call is the profiler span ``engine.submit``.
        """
        with jax.profiler.TraceAnnotation("engine.submit"):
            X = np.asarray(X, np.float32)
            assert X.ndim == 2, X.shape
            t = Ticket(X)
            with self._q_lock:
                if self._queued_rows + t.rows > self.cfg.max_queue_rows:
                    admitted = False
                else:
                    admitted = True
                    self._queue.append(t)
                    self._queued_rows += t.rows
                    depth = self._queued_rows
            if admitted:
                self._bump(admitted_requests=1, admitted_rows=t.rows)
                with self._m_lock:
                    if depth > self._metrics["max_queue_rows_seen"]:
                        self._metrics["max_queue_rows_seen"] = depth
                self._q_event.set()
            else:
                self._bump(shed_requests=1, shed_rows=t.rows)
                t._resolve("shed")
            return t

    @property
    def queued_rows(self) -> int:
        return self._queued_rows

    def serve_once(self) -> int:
        """Drain one packed batch; returns rows served (0 = queue empty).

        Pops FIFO tickets until the pack would exceed ``max_batch_rows``
        (always at least one), pins the published record with ONE read,
        serves the concatenated rows through ``predict_snapshot`` (pow-2
        bucketed, cached jit), and splits the predictions back per
        ticket.  Per-row predictions are independent of batch packing,
        so every ticket's rows are bit-identical to a standalone
        ``predict_snapshot`` on its pinned version.  The call is the
        profiler span ``engine.serve_once``, the pop and concatenation in
        it ``engine.pack``.
        """
        with jax.profiler.TraceAnnotation("engine.serve_once"):
            with jax.profiler.TraceAnnotation("engine.pack"):
                with self._q_lock:
                    if not self._queue:
                        self._q_event.clear()
                        return 0
                    batch, rows = [], 0
                    while self._queue and (not batch or rows
                                           + self._queue[0].rows
                                           <= self.cfg.max_batch_rows):
                        t = self._queue.pop(0)
                        batch.append(t)
                        rows += t.rows
                    self._queued_rows -= rows
                rec = self._published               # the one pinned read
                X = batch[0].X if len(batch) == 1 else \
                    np.concatenate([t.X for t in batch], axis=0)
            y = np.asarray(sv.predict_snapshot(rec.snap, X,
                                               backend=self.cfg.backend))
            off = 0
            for t in batch:
                t._resolve("done", y[off:off + t.rows], rec.version)
                off += t.rows
            self._bump(served_requests=len(batch), served_rows=rows,
                       serve_batches=1)
            return rows

    # -- threaded mode -----------------------------------------------------

    def start(self):
        """Run the trainer and server loops on daemon threads — the
        deployment shape.  Both loops are the single-step methods above
        in a while-loop, so threaded and stepped execution share every
        code path."""
        assert not self._threads, "engine already started"
        self._stop.clear()

        def _server():
            while not self._stop.is_set():
                if self.serve_once() == 0:
                    self._q_event.wait(timeout=0.005)

        def _trainer():
            while not self._stop.is_set():
                if not self.train_once():
                    break
                time.sleep(0)                  # yield to the server

        self._threads = [
            threading.Thread(target=_server, name="engine-server",
                             daemon=True),
            threading.Thread(target=_trainer, name="engine-trainer",
                             daemon=True),
        ]
        for t in self._threads:
            t.start()

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Stop the loops; ``drain=True`` first serves every queued
        ticket (in-flight requests complete on the published snapshot —
        zero-downtime includes shutdown)."""
        if drain:
            deadline = time.monotonic() + timeout
            while self._queued_rows and time.monotonic() < deadline:
                time.sleep(0.002)
        self._stop.set()
        self._q_event.set()
        for t in self._threads:
            t.join(timeout=timeout)
        self._threads = []
        while drain and self.serve_once():
            pass                                # whatever the race left

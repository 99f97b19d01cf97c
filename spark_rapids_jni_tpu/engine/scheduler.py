"""Multi-tenant query scheduler: admission control + fair-share
interleaving + per-session memory budgets.

Everything below the bridge was already concurrency-ready — fingerprints
are session-agnostic, the caches are lock-audited LRUs, trace ids join a
query's spans/profiles/bundles across connections (PRs 11-15).  This
module adds the missing policy layer for the interactive-concurrency
regime ("Accelerating Presto with GPUs" targets it):
WHO gets on the device, WHEN their chunks run, and HOW MUCH memory each
tenant may pin.

Three cooperating pieces, one ``Scheduler`` facade (``SCHEDULER``):

**SLO-aware admission.**  ``admit()`` bounds live sessions at
``SRJT_MAX_SESSIONS``.  Arrivals past the bound queue on a condition
variable up to ``SRJT_ADMISSION_QUEUE_S`` — except fingerprints whose
windowed SLO burn rate (``blackbox.slo_burn_for``, fed by the profile
store) is already at/over ``SRJT_ADMISSION_BURN``: those are shed
IMMEDIATELY when the server is saturated.  Queueing a query that has
already burned its error budget can only convert its breach into a
second breach plus queue delay for a tenant that still has budget —
shedding it is the cheaper failure for both.  Not FIFO by design.  A
shed raises the typed ``AdmissionRejectedError`` (utils/errors.py wire
taxonomy: the client re-raises it with trace_id + bundle pointer) and
records ``admission.shed`` in the flight-recorder ring.

**Fair-share interleaving.**  Admitted queries execute as cooperative
chunk streams; every chunk boundary already runs
``RecoveryPolicy.checkpoint()`` (cancel/deadline checks), and the
checkpoint now also calls ``QuerySession.gate()`` — deficit round-robin:
a session spends one credit per chunk and blocks (bounded waits, never a
deadlock: a round is forced after ``_FORCE_ROUND_S`` even if a
credit-holding session is stalled in a long device op) once its credits
run out, until every live session has drained its round and credits
replenish at ``quantum x weight``.  Weight follows the SLO class — a
tight-objective point query gets more chunks per round than a bulk scan
(``weight_for_objective``) — so a long scan cannot starve a point query,
and with a single live session the gate is a no-op fast path.

**Per-session memory budgets.**  ``SRJT_SESSION_BUDGET_BYTES`` caps a
session's observed chunk working set (charged from the executor's
existing per-chunk ``table_nbytes`` sites — zero added device syncs).
The budget feeds two places: the spilled-exchange rung clamps its
``hbm_budget_bytes`` to the session's remaining budget (one tenant's
spill ladder cannot size itself as if it owned the device), and the OOM
degradation ladder consults ``over_budget()`` BEFORE degrading — a
session within its own budget that hits RESOURCE_EXHAUSTED is feeling a
*neighbor's* allocation pressure, so the ladder retries the same rung
once (``engine.sched.neighbor_pressure``) instead of force-interpreting
an innocent tenant (engine/recovery.py).

A session that really waits — queued at admission, blocked at the gate —
is inside a span (``engine.sched.queue_wait`` / ``engine.sched.gate_wait``,
``op_scope(..., timed=True)``): in its query's summary and, under
``SRJT_TRACE=1``, on the profiler's clock.  One live session opens none.

Docs: docs/SERVING.md.  Counters: ``engine.sched.*`` (docs/METRICS.md).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Optional

from ..utils import blackbox, metrics
from ..utils.config import config
from ..utils.errors import AdmissionRejectedError
from ..utils.tracing import op_scope

#: chunks per weight unit per round — small enough that a point query
#: waits at most a few chunks behind a scan, large enough to amortize
#: the condvar handoff
_QUANTUM = 4
#: bounded gate wait between deficit re-checks (seconds)
_GATE_WAIT_S = 0.05
#: force a replenish round after this long even if some credit-holding
#: session never reached a chunk boundary (stalled in a device op) —
#: bounds worst-case starvation and makes deadlock structurally
#: impossible
_FORCE_ROUND_S = 0.25
#: admission burn-rate lookups hit the on-disk profile store; cache the
#: report briefly so a shed storm doesn't become a stat storm
_BURN_TTL_S = 1.0


def weight_for_objective(objective_ms) -> int:
    """Fair-share weight from an SLO objective: chunks per round scale
    inversely with the latency target, clamped to [1, 8].  No objective
    (or a slack one) means weight 1 — bulk work shares evenly."""
    if not objective_ms or objective_ms <= 0:
        return 1
    return max(1, min(8, int(2000.0 / float(objective_ms))))


class QuerySession:
    """One admitted query's scheduling identity: fair-share credits plus
    the device-memory budget ledger.  Created by ``Scheduler.admit`` and
    threaded to the executor via ``RecoveryPolicy(session=...)``."""

    __slots__ = ("sid", "trace_id", "fingerprint", "source_fingerprint",
                 "objective_ms", "weight", "budget_bytes",
                 "peak_chunk_bytes", "charged_chunks", "credits",
                 "queued_s", "_sched", "_lock")

    def __init__(self, sid: int, sched: "Scheduler", trace_id: str = "",
                 fingerprint: str = "", source_fingerprint: str = "",
                 objective_ms=None, budget_bytes: Optional[int] = None):
        self.sid = sid
        self.trace_id = trace_id
        self.fingerprint = fingerprint
        self.source_fingerprint = source_fingerprint
        self.objective_ms = objective_ms
        self.weight = weight_for_objective(objective_ms)
        self.budget_bytes = (config.session_budget_bytes
                             if budget_bytes is None else int(budget_bytes))
        self.peak_chunk_bytes = 0
        self.charged_chunks = 0
        self.credits = _QUANTUM * self.weight
        self.queued_s = 0.0
        self._sched = sched
        self._lock = threading.Lock()

    # -- memory budget ----------------------------------------------------

    def charge(self, nbytes: int) -> None:
        """Record a chunk's bytes against the session working set.

        Tracks the PEAK single-chunk footprint — the quantity the budget
        bounds: chunk buffers are transient, so the steady-state device
        claim of a streaming session is its largest chunk, not the sum."""
        with self._lock:
            self.charged_chunks += 1
            if nbytes > self.peak_chunk_bytes:
                self.peak_chunk_bytes = nbytes

    def over_budget(self) -> bool:
        """True when a budget is set and the session's peak chunk has
        exceeded it — this session earned its own OOM; degrade it."""
        return self.budget_bytes > 0 and \
            self.peak_chunk_bytes > self.budget_bytes

    def budget_remaining(self) -> Optional[int]:
        """Bytes of budget headroom (``None`` = unlimited); the spilled
        exchange clamps its HBM budget to this."""
        if self.budget_bytes <= 0:
            return None
        return max(0, self.budget_bytes - self.peak_chunk_bytes)

    # -- fair share -------------------------------------------------------

    def gate(self) -> None:
        """Chunk-boundary scheduling point (RecoveryPolicy.checkpoint)."""
        self._sched.gate(self)

    def release(self) -> None:
        self._sched.release(self)

    def snapshot(self) -> dict:
        with self._lock:
            return {"sid": self.sid, "trace_id": self.trace_id,
                    "fingerprint": self.fingerprint[:12],
                    "weight": self.weight, "credits": self.credits,
                    "budget_bytes": self.budget_bytes,
                    "peak_chunk_bytes": self.peak_chunk_bytes,
                    "charged_chunks": self.charged_chunks}


class Scheduler:
    """Admission controller + deficit-round-robin interleaver.

    All shared state (the live-session table and every session's
    credits) is guarded by one condition variable ``_cv`` — admission
    waits, gate waits and round replenishes are all wakeups on it."""

    def __init__(self):
        self._cv = threading.Condition()
        self._live: dict = {}          # sid -> QuerySession (under _cv)
        self._ids = itertools.count(1)
        self._rounds = 0
        self.admitted = 0
        self.queued = 0
        self.shed = 0
        self._burn_cache: dict = {}    # fp12 -> burn rate (under _cv)
        self._burn_stamp = 0.0

    # -- admission --------------------------------------------------------

    def _burn_rate(self, source_fingerprint: str):
        """Cached ``blackbox.slo_burn_for`` (lock held) — refreshed at
        most every ``_BURN_TTL_S`` so saturation doesn't stat-storm the
        profile store."""
        now = time.monotonic()
        if now - self._burn_stamp > _BURN_TTL_S:
            self._burn_cache = {}
            self._burn_stamp = now
        fp = (source_fingerprint or "")[:12]
        if fp not in self._burn_cache:
            try:
                self._burn_cache[fp] = blackbox.slo_burn_for(fp)
            except Exception:  # noqa: BLE001 — admission must not crash
                self._burn_cache[fp] = None
        return self._burn_cache[fp]

    def _shed(self, reason: str, fingerprint: str, trace_id: str,
              waited_s: float, live: int):
        """Reject at admission (lock held): count, record, raise typed."""
        self.shed += 1
        metrics.count("engine.sched.shed")
        blackbox.record("admission.shed", reason=reason,
                        fingerprint=fingerprint[:12], trace_id=trace_id,
                        waited_s=round(waited_s, 4), live=live)
        raise AdmissionRejectedError(
            f"admission rejected ({reason}): {live}/{config.max_sessions} "
            f"sessions live after {waited_s:.2f}s queued")

    def _shed_if_burning(self, src: str, fingerprint: str, trace_id: str,
                         t0: float):
        """Saturated + burning fingerprint => shed now (lock held)."""
        burn = self._burn_rate(src)
        if burn is not None and burn >= config.admission_burn:
            self._shed(f"slo-burn {burn:.2f}", fingerprint, trace_id,
                       time.monotonic() - t0, len(self._live))

    def _enter(self, fingerprint: str, src: str, trace_id: str,
               t0: float) -> tuple:
        """Take a free slot (lock held): the new session and how many
        are live with it."""
        session = QuerySession(
            next(self._ids), self, trace_id=trace_id,
            fingerprint=fingerprint, source_fingerprint=src,
            objective_ms=blackbox.slo_objective_for(src))
        session.queued_s = time.monotonic() - t0
        self._live[session.sid] = session
        self.admitted += 1
        metrics.count("engine.sched.admitted")
        metrics.gauge_set("engine.sched.live", len(self._live))
        return session, len(self._live)

    def _queue(self, fingerprint: str, src: str, trace_id: str,
               t0: float) -> tuple:
        """Wait for a slot up to ``SRJT_ADMISSION_QUEUE_S``, then shed."""
        deadline = t0 + config.admission_queue_s
        with self._cv:
            while len(self._live) >= config.max_sessions:
                self._shed_if_burning(src, fingerprint, trace_id, t0)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self._shed("queue-timeout", fingerprint, trace_id,
                               time.monotonic() - t0, len(self._live))
                self._cv.wait(min(remaining, _GATE_WAIT_S))
            return self._enter(fingerprint, src, trace_id, t0)

    def admit(self, fingerprint: str = "", source_fingerprint: str = "",
              trace_id: str = "") -> QuerySession:
        """Block until a session slot frees (bounded), or shed.

        Saturated + burning fingerprint => immediate shed; saturated
        otherwise => queue up to ``SRJT_ADMISSION_QUEUE_S`` then shed.
        Only an arrival that has to queue opens the span
        ``engine.sched.queue_wait`` (timed: ``engine.sched.queue_wait_s``);
        the span opens and closes outside ``_cv``."""
        t0 = time.monotonic()
        src = source_fingerprint or fingerprint
        with self._cv:
            saturated = len(self._live) >= config.max_sessions
            if saturated:
                self._shed_if_burning(src, fingerprint, trace_id, t0)
                self.queued += 1
                metrics.count("engine.sched.queued")
                live = len(self._live)
            else:
                session, live = self._enter(fingerprint, src, trace_id, t0)
        if saturated:
            with op_scope("engine.sched.queue_wait", timed=True, live=live,
                          trace_id=trace_id):
                session, live = self._queue(fingerprint, src, trace_id, t0)
        # how many plans run at once, the new one included: a gauge shows
        # the last value only, a histogram's sum / count the mean
        metrics.observe("engine.sched.live_sessions", live)
        return session

    def release(self, session: QuerySession) -> None:
        with self._cv:
            self._live.pop(session.sid, None)
            metrics.gauge_set("engine.sched.live", len(self._live))
            self._cv.notify_all()

    # -- deficit round-robin ----------------------------------------------

    def _new_round(self):
        """Replenish every live session's credits (lock held)."""
        self._rounds += 1
        metrics.count("engine.sched.rounds")
        for s in self._live.values():
            s.credits = _QUANTUM * s.weight
        self._cv.notify_all()

    def _spend(self, session: QuerySession, waited_s: float) -> bool:
        """Spend one chunk credit if the session may run now (lock held);
        False while its round is drained and others still hold credits.
        Starts a new round once every live session has drained, or after
        ``_FORCE_ROUND_S`` of waiting."""
        if session.sid not in self._live:
            return True  # released concurrently (cancel path)
        if session.credits <= 0 and (
                waited_s >= _FORCE_ROUND_S
                or all(s.credits <= 0 for s in self._live.values())):
            self._new_round()
        if session.credits <= 0:
            return False
        session.credits -= 1
        return True

    def gate(self, session: QuerySession) -> None:
        """Spend one chunk credit; block while the session's round is
        drained and others still hold credits.  Bounded waits plus the
        ``_FORCE_ROUND_S`` forced replenish keep this deadlock-free even
        when a credit holder stalls off a chunk boundary.

        Only a session that really blocks opens the span
        ``engine.sched.gate_wait`` (timed: ``engine.sched.gate_wait_s``),
        and the span opens and closes outside ``_cv``."""
        with self._cv:
            if len(self._live) <= 1:
                return  # single tenant: no contention, no bookkeeping
            if self._spend(session, 0.0):
                return
            live = len(self._live)
        with op_scope("engine.sched.gate_wait", timed=True, sid=session.sid,
                      live=live, trace_id=session.trace_id):
            t0 = time.monotonic()
            with self._cv:
                while not self._spend(session, time.monotonic() - t0):
                    self._cv.wait(_GATE_WAIT_S)

    # -- introspection ----------------------------------------------------

    def live_count(self) -> int:
        with self._cv:
            return len(self._live)

    def stats(self) -> dict:
        with self._cv:
            return {"live": len(self._live), "admitted": self.admitted,
                    "queued": self.queued, "shed": self.shed,
                    "rounds": self._rounds,
                    "max_sessions": config.max_sessions,
                    "sessions": [s.snapshot()
                                 for s in self._live.values()]}


#: process-wide scheduler (the bridge server's admission point)
SCHEDULER = Scheduler()

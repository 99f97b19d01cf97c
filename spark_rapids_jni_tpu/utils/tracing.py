"""Profiling scopes: the NVTX-range analog (SURVEY §5).

The reference wraps ops in NVTX ranges toggled by
``ai.rapids.cudf.nvtx.enabled`` (reference pom.xml:84,407) so Nsight shows
per-op spans.  The TPU equivalents:

- ``jax.named_scope`` — always on: names the HLO ops an op emits, so XLA
  dumps and profiler traces attribute work to engine ops (compile-time
  metadata, zero runtime cost).
- ``jax.profiler.TraceAnnotation`` — runtime spans on the host timeline,
  enabled by ``SRJT_TRACE=1`` (visible in Perfetto via ``profile()``);
  ``op_scope(name, timed=True, **stats)`` also times the span into the
  histogram ``<name>_s`` (wall seconds; under ``SRJT_TRACE=1`` the thread's
  CPU seconds beside them), tags the annotation with the query's trace id,
  and hands out the open span for late stats.
- ``profile(logdir)`` — capture a full device trace
  (``jax.profiler.trace``), the Nsight-session analog.
- ``count(name)`` / ``counters_snapshot()`` — lightweight named event
  counters (the metrics-registry analog); the engine plan cache reports
  hits/misses through these.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

import jax

from . import timeline
from .config import config


class op_scope:
    """Named scope + (when SRJT_TRACE=1) a host profiler annotation +
    (when SRJT_TIMELINE=1) a span in the in-process event timeline —
    one call site, three observability sinks on the same name.

    ``timed=True`` adds a fourth while ``SRJT_METRICS`` is on: the span's
    wall seconds (``perf_counter``) go into the histogram ``<name>_s`` —
    ``count`` and ``sum``, process-wide and in the query bound to this
    thread (``metrics.bind`` carries it onto helper threads) — and, under
    ``SRJT_TRACE=1``, its thread's CPU seconds (``thread_time``) with them
    as ``cpu_sum``, in the same ONE observation.  ``sum - cpu_sum`` is the
    time the thread was not running: the device for ``engine.sync_wait``,
    the worker for ``io.scan.decode``, the interpreter's lock for pure
    host work.

    ``stats`` (``chunk=3``, ``label="combine-sizing"``, ``bytes=...``) go
    to the annotation as event stats, together with ``trace_id`` of the
    bound query: the spans of one request share it on every thread, and
    all of them lie on the profiler's clock beside the device's ops.
    ``with op_scope(...) as sp:`` hands out the open span: ``sp.stat(**kv)``
    adds stats known only later (what a decode walked, how many groups a
    footer pruned) to the annotation and to the timeline's span; with both
    sinks off it does nothing.

    A plain class, no generator and no ``ExitStack``: some ten of these
    open per streamed chunk under the interpreter's lock."""

    __slots__ = ("name", "_timed", "_stats", "_scope", "_live", "_ann",
                 "_tl0", "_t0", "_c0")

    def __init__(self, name: str, timed: bool = False, **stats):
        self.name = name
        self._timed = timed
        self._stats = stats

    def __enter__(self) -> "op_scope":
        self._scope = scope = jax.named_scope(self.name)
        scope.__enter__()
        if config.trace or config.timeline \
                or (self._timed and config.metrics):
            self._open_sinks()
        else:
            self._live = False      # the common case: two attribute writes
        return self

    def _open_sinks(self) -> None:
        self._live = True
        self._ann = self._tl0 = self._t0 = None
        if config.trace:
            from . import blackbox  # lazy: blackbox -> metrics -> here
            # a caller outside any query scope (the bridge's dispatch)
            # names the trace itself; "" there means a client without one
            ann = dict(self._stats)
            trace_id = ann.pop("trace_id", "") or blackbox.current_trace()
            if trace_id:
                ann["trace_id"] = trace_id
            self._ann = jax.profiler.TraceAnnotation(self.name, **ann)
            self._ann.__enter__()
        if config.timeline:
            self._tl0 = time.perf_counter()
        if self._timed and config.metrics:
            # the CPU stretch lies inside the wall stretch: cpu <= wall.
            # Read only while a trace is kept: the thread's CPU clock is a
            # system call (6 us on the chip's host, 0.3 us elsewhere)
            self._t0 = time.perf_counter()
            self._c0 = time.thread_time() if config.trace else None

    def stat(self, **kv) -> None:
        """Add stats to the OPEN span, in whichever sinks are on."""
        if not self._live:
            return
        if self._ann is not None:
            self._ann.set_metadata(**kv)
        if self._tl0 is not None:
            self._stats.update(kv)

    def _close_sinks(self, exc_type, exc, tb) -> None:
        if self._t0 is not None:
            # wall and CPU seconds in ONE observation: one call, one lock
            cpu = None if self._c0 is None \
                else time.thread_time() - self._c0
            _observe(self.name + "_s", time.perf_counter() - self._t0, cpu)
        if self._tl0 is not None:
            timeline.complete(self.name, self._tl0,
                              time.perf_counter() - self._tl0, self._stats)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._live:
            self._close_sinks(exc_type, exc, tb)
        self._scope.__exit__(exc_type, exc, tb)
        return False


def _observe(name: str, seconds: float, cpu: float | None) -> None:
    """``metrics.observe``; that module imports this one, so the name is
    bound at the first timed span."""
    global _observe
    from .metrics import observe as _observe
    _observe(name, seconds, cpu)


def traced(name: str):
    """Decorator form of ``op_scope`` for op entry points."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with op_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


@contextlib.contextmanager
def profile(logdir: str):
    """Device+host trace capture; view in Perfetto/TensorBoard.

    Usage::

        with tracing.profile("/tmp/trace"):
            run_query(...)

    Creates ``logdir`` if missing, and degrades to a warning no-op when
    ``jax.profiler`` is unavailable or fails to start on this platform —
    the docs/OBSERVABILITY.md recipe must work on a clean checkout, not
    raise (the SRJT_TIMELINE path exists for exactly those shells).
    """
    from .config import logger
    os.makedirs(logdir, exist_ok=True)
    try:
        cm = jax.profiler.trace(logdir)
        cm.__enter__()
    except Exception as e:
        logger().warning(
            "jax.profiler unavailable (%s); profile(%r) is a no-op — "
            "use SRJT_TIMELINE=1 for the in-process timeline", e, logdir)
        yield
        return
    try:
        yield
    finally:
        cm.__exit__(None, None, None)


# -- named event counters --------------------------------------------------
#
# Process-wide monotonic counters keyed by dotted name (e.g.
# "engine.plan_cache.hit").  Cheap enough to leave on unconditionally;
# thread-safe because the bridge server increments from its serve thread
# while tests read snapshots from the main thread.

_counters: dict[str, int] = {}
_counters_lock = threading.Lock()


def count(name: str, n: int = 1) -> int:
    """Increment counter ``name`` by ``n``; returns the new value."""
    with _counters_lock:
        v = _counters.get(name, 0) + n
        _counters[name] = v
        return v


def counter_value(name: str) -> int:
    with _counters_lock:
        return _counters.get(name, 0)


def counters_snapshot(prefix: str = "") -> dict:
    """Copy of all counters whose name starts with ``prefix``."""
    with _counters_lock:
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def reset_counters(prefix: str = "") -> None:
    """Zero counters under ``prefix`` (tests isolate themselves with this)."""
    with _counters_lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            del _counters[k]


def restore_counters(snapshot: dict, prefix: str = "") -> None:
    """Put back a ``counters_snapshot(prefix)`` taken before a reset (the
    tail half of the ``metrics_isolation`` test fixture)."""
    with _counters_lock:
        for k in [k for k in _counters if k.startswith(prefix)]:
            del _counters[k]
        _counters.update(snapshot)

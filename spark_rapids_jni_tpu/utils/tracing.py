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
  histogram ``<name>_s`` and tags the annotation with the query's trace id.
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


@contextlib.contextmanager
def op_scope(name: str, timed: bool = False, **stats):
    """Named scope + (when SRJT_TRACE=1) a host profiler annotation +
    (when SRJT_TIMELINE=1) a span in the in-process event timeline —
    one call site, three observability sinks on the same name.

    ``timed=True`` adds a fourth while ``SRJT_METRICS`` is on: one
    ``perf_counter`` pair whose difference is observed as the histogram
    ``<name>_s``, process-wide and in the query bound to this thread
    (``metrics.bind`` carries it onto helper threads).

    ``stats`` (``chunk=3``, ``label="combine-sizing"``, ``bytes=...``) go
    to the annotation as event stats, together with ``trace_id`` of the
    bound query: the spans of one request share it on every thread, and
    all of them lie on the profiler's clock beside the device's ops."""
    with contextlib.ExitStack() as stack:
        stack.enter_context(jax.named_scope(name))
        if config.trace:
            from . import blackbox  # lazy: blackbox -> metrics -> here
            # a caller outside any query scope (the bridge's dispatch)
            # names the trace itself; "" there means a client without one
            ann = {**stats, "trace_id": stats.get("trace_id")
                   or blackbox.current_trace()}
            if not ann["trace_id"]:
                del ann["trace_id"]
            stack.enter_context(jax.profiler.TraceAnnotation(name, **ann))
        if config.timeline:
            stack.enter_context(timeline.span(name, stats))
        if not (timed and config.metrics):
            yield
            return
        from . import metrics  # lazy: metrics imports this module
        t0 = time.perf_counter()
        try:
            yield
        finally:
            metrics.observe(f"{name}_s", time.perf_counter() - t0)


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

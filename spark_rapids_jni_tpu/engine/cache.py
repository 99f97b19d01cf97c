"""Plan cache: fingerprint-keyed, optimized-once, jit-warm compiled plans.

The "serve heavy traffic" lever: a repeated query (same plan structure, new
execution) must not pay optimization again, and — because every op kernel
underneath is ``jax.jit``-compiled with shape-keyed caches — re-executing
the same optimized plan on same-shaped data hits XLA's dispatch caches
instead of recompiling.  ``PlanCache.get`` returns a ``CompiledPlan`` whose
first ``execute`` warms those jit caches; subsequent executes are dispatch-
only.  Hit/miss counts flow through ``utils.tracing`` counters
(``engine.plan_cache.hit`` / ``.miss``) and ``stats()`` for the bridge's
METRICS payload.

The key is the fingerprint of the *unoptimized* serialized plan: clients
submit logical plans, so two structurally identical submissions must hit
regardless of what the optimizer does to them.

``BUILD_CACHE`` is the third cache layer: prepared join build sides
(``ops.join.PreparedBuild`` — build hash + stable sort + r_order) keyed by
(join-node fingerprint, build shape-class), so a streamed probe join hashes
and sorts its dimension table once per execution — and not at all on a
repeat execution over the same-shaped build — instead of once per chunk.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional

from ..utils import metrics
from .executor import execute, lowering_flags
from .optimizer import optimize
from .physical import PhysicalPlan, lower
from .plan import PlanNode, Scan


class CompiledPlan:
    """An optimized plan, its physical plans and its execution entry
    point."""

    __slots__ = ("key", "plan", "optimized", "executions", "_physical")

    def __init__(self, key: str, plan: PlanNode, optimized: PlanNode):
        self.key = key
        self.plan = plan
        self.optimized = optimized
        self.executions = 0
        self._physical: dict = {}  # lowering flag values -> PhysicalPlan

    def physical(self) -> PhysicalPlan:
        """The optimized plan lowered under the live flags: once per flag
        tuple, so a repeat execution walks the plan for nothing (and a
        test that flips ``config`` between executions sees its flags)."""
        flags = lowering_flags()
        key = tuple(flags.values())
        hit = self._physical.get(key)
        if hit is None:
            hit = self._physical[key] = lower(self.optimized, **flags)
        return hit

    def execute(self, stats: Optional[dict] = None, cancel=None,
                session=None):
        self.executions += 1
        return execute(self.physical(), stats=stats, cancel=cancel,
                       session=session)


class PlanCache:
    """LRU map: plan fingerprint → ``CompiledPlan`` (thread-safe).

    Capacity defaults to ``SRJT_PLAN_CACHE`` (utils.config, env override);
    evictions are recorded alongside hits/misses in both ``stats()`` and
    the tracing counter registry (``engine.plan_cache.eviction``).
    """

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = None if maxsize is None else int(maxsize)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, CompiledPlan]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        # resolved per use, not at construction, so SRJT_PLAN_CACHE +
        # config.refresh() retunes live caches (bridge servers included)
        from ..utils.config import config
        return self._maxsize if self._maxsize is not None \
            else config.plan_cache

    def get(self, plan: PlanNode) -> CompiledPlan:
        key = plan.fingerprint()
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.plan_cache.hit")
                return hit
        # optimize outside the lock (reads file footers for schemas)
        compiled = CompiledPlan(key, plan, optimize(plan))
        with self._lock:
            racer = self._entries.get(key)
            if racer is not None:  # lost a concurrent-miss race: their entry
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.plan_cache.hit")
                return racer
            self.misses += 1
            metrics.count("engine.plan_cache.miss")
            self._entries[key] = compiled
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                metrics.count("engine.plan_cache.eviction")
            return compiled

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


class BuildCache:
    """LRU: (join fingerprint, build shape-class) -> ``PreparedBuild``.

    The join analog of ``SegmentCache``: the segment cache dedups compiled
    executables, this dedups the build-side prep (xxhash64 + stable sort)
    a streamed probe join would otherwise redo per chunk.  ``get`` is
    called once per chunk by the fused streaming loop — the first call
    misses and prepares, every later chunk (and every repeat execution
    with a same-shaped build) hits, so a stream of N chunks shows exactly
    ``hits == N - 1`` on a cold cache.  Counters flow through
    ``utils.tracing`` as ``engine.build_cache.{hit,miss,eviction}``;
    capacity from ``SRJT_BUILD_CACHE`` (utils.config, refresh()-tunable).
    """

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = None if maxsize is None else int(maxsize)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        from ..utils.config import config
        return self._maxsize if self._maxsize is not None \
            else config.build_cache

    def get(self, fingerprint: str, build_table, builder):
        """The prepared build for ``(fingerprint, shape_class(build))``,
        computing it via ``builder()`` on a miss."""
        from .segment import shape_class
        key = (fingerprint, shape_class(build_table))
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.build_cache.hit")
                return hit
        prepared = builder()  # hash+sort outside the lock (device work)
        with self._lock:
            racer = self._entries.get(key)
            if racer is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.build_cache.hit")
                return racer
            self.misses += 1
            metrics.count("engine.build_cache.miss")
            self._entries[key] = prepared
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                metrics.count("engine.build_cache.eviction")
            return prepared

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide prepared-build cache (the streamed-join prep layer)
BUILD_CACHE = BuildCache()


def data_version(plan: PlanNode):
    """Freshness key for the result-set cache: the sorted
    ``(path, mtime_ns, size)`` tuple over every ``Scan`` leaf.

    A rewritten input file changes its mtime (and usually size), so the
    composite key ``(plan fingerprint, data_version)`` misses — the cache
    never serves stale rows; it only skips re-reading data that has not
    moved.  Returns ``None`` (uncacheable) when any input can't be
    stat'ed — a vanishing file should fail in the scan, not be masked by
    a stale cached result.
    """
    paths = set()
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, Scan):
            paths.add(n.path)
        stack.extend(n.children())
    version = []
    for p in sorted(paths):
        try:
            st = os.stat(p)
        except OSError:
            return None
        version.append((p, st.st_mtime_ns, st.st_size))
    return tuple(version)


class ResultCache:
    """LRU: (plan fingerprint, data version) -> completed result table.

    The fourth — and cheapest — cache layer: where ``PlanCache`` skips
    optimization and ``SegmentCache`` skips compilation, this skips the
    *execution*.  Off by default (``SRJT_RESULT_CACHE=0``): serving
    deployments opt in, and plan-cache contract tests keep observing real
    executions.  Keys carry the input files' identity (``data_version``)
    so a repeat query is served only while its data is bit-identical on
    disk.  Counters ``engine.result_cache.{hit,miss,eviction}`` attribute
    per query like every other cache; capacity is entries, resolved per
    use so ``refresh()`` retunes live servers.

    ``get``/``put`` are split (unlike the builder-callback caches)
    because the execution between them runs under the caller's session,
    cancel token, and stats plumbing; a concurrent-miss race on ``put``
    keeps the first-stored result.
    """

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = None if maxsize is None else int(maxsize)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @property
    def maxsize(self) -> int:
        from ..utils.config import config
        return self._maxsize if self._maxsize is not None \
            else config.result_cache

    @property
    def enabled(self) -> bool:
        return self.maxsize > 0

    def get(self, fingerprint: str, version):
        """The cached result for ``(fingerprint, version)`` or ``None``;
        an unstattable ``version`` (None) never hits and never counts."""
        if version is None or not self.enabled:
            return None
        key = (fingerprint, version)
        with self._lock:
            hit = self._entries.get(key)
            if hit is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                metrics.count("engine.result_cache.hit")
                return hit
            self.misses += 1
            metrics.count("engine.result_cache.miss")
            return None

    def put(self, fingerprint: str, version, result) -> None:
        if version is None or not self.enabled or result is None:
            return
        key = (fingerprint, version)
        with self._lock:
            if key in self._entries:  # concurrent miss: first store wins
                self._entries.move_to_end(key)
                return
            self._entries[key] = result
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                metrics.count("engine.result_cache.eviction")

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "size": len(self._entries), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide result-set cache (the skip-the-execution layer)
RESULT_CACHE = ResultCache()

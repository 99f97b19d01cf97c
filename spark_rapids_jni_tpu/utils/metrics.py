"""Query-scoped metrics: spans, histograms, gauges over the flat counters.

``utils.tracing`` gives the process flat monotonic counters (the
metrics-registry analog of the reference's NVTX-range toggles); this module
adds the attribution layer the Spark RAPIDS plugin gets from per-operator
SQLMetrics: a ``QueryMetrics`` context that collects per-plan-node spans
(wall time, rows in/out, chunk count, padded-vs-live row waste, host-sync
count), per-query counter attribution, and lock-protected histograms and
gauges keyed by dotted name so concurrent queries never collide.

Two consumers sit on top (docs/OBSERVABILITY.md):

- ``engine.explain_analyze(plan)`` renders the optimized DAG annotated
  with the spans recorded here (the EXPLAIN ANALYZE analog).
- The bridge's ``OP_METRICS`` reply embeds ``snapshot()`` so JNI-side
  callers can poll counters + histograms + per-query summaries.

Collection is gated by ``SRJT_METRICS`` (default on): every entry point is
cheap dict/``perf_counter`` work — no device syncs — and with the flag off
each returns immediately, restoring the uninstrumented fast path.  The
pre-existing flat counters (``tracing.count``) stay on unconditionally, as
they always were.  ``SRJT_TRACE=1`` layers Perfetto ``TraceAnnotation``s
(``tracing.op_scope``) on top of the same span names, and
``op_scope(name, timed=True)`` observes its duration here as the histogram
``<name>_s`` (docs/OBSERVABILITY.md lists the span tree).

Threading: the active query context is a thread-local; code that fans work
out to helper threads (the chunked reader's prefetch producer) captures
``current()`` and re-enters it with ``bind(qm)`` so producer-side metrics
still attribute to the query that spawned them.  ``QueryMetrics`` carries
its own lock (its histograms ride the registry's: one ``observe`` takes one
lock for both), so attribution from any bound thread is safe.
"""

from __future__ import annotations

import contextlib
import itertools
import math
import threading
import time
from collections import deque

from . import timeline, tracing
from .config import config

# -- registries -------------------------------------------------------------
#
# Histograms and gauges mirror the tracing counter registry: process-wide,
# dotted-name keyed, one lock.  Histogram values bucket into powers of two
# (the chunk-row-bucket convention io/staging.py already uses), which keeps
# the bucket set tiny without pre-declaring ranges per metric.

_lock = threading.Lock()
_hists: dict[str, dict] = {}
_gauges: dict[str, float] = {}

#: live-query progress registry: qid -> the QueryMetrics itself.  Entries
#: register at QueryMetrics construction and leave at ``finish()``, so the
#: registry IS the set of in-flight queries — the bridge's OP_QUERY_STATUS
#: and ``progress_snapshot()`` read it from any thread while the query
#: runs.  Writes ride the per-query lock; no device work anywhere.
_progress: dict[int, "QueryMetrics"] = {}

#: completed-query summaries, newest last (the bridge's export window)
_RECENT_LIMIT = 32
_recent: "deque[dict]" = deque(maxlen=_RECENT_LIMIT)


class _Bound(threading.local):
    """The query bound to a thread; a class default, because a lookup that
    misses on a ``threading.local`` costs ten times one that hits."""
    q = None


_tls = _Bound()
_qids = itertools.count(1)


def enabled() -> bool:
    """Live SRJT_METRICS gate (config singleton, refresh()-tunable)."""
    return config.metrics


def _bucket_le(value: float) -> float:
    """Smallest power-of-two upper bound for ``value`` (0.0 for <= 0)."""
    v = float(value)
    if v <= 0.0:
        return 0.0
    m, e = math.frexp(v)        # v == m * 2**e, 0.5 <= m < 1: no rounding
    return math.ldexp(1.0, e - 1 if m == 0.5 else e)


def _hist_add(hists: dict, name: str, v: float, le: float,
              cpu: float | None = None) -> None:
    """One observation of ``v`` (a float; ``le``: its bucket).  ``cpu``: the
    observing thread's CPU seconds over the same stretch (a timed
    ``op_scope``), summed as ``cpu_sum`` beside ``sum``."""
    h = hists.get(name)
    if h is None:
        hists[name] = h = {"count": 1, "sum": v, "min": v, "max": v,
                           "buckets": {le: 1}}
        if cpu is not None:
            h["cpu_sum"] = cpu
        return
    h["count"] += 1
    h["sum"] += v
    if cpu is not None:
        h["cpu_sum"] = h.get("cpu_sum", 0.0) + cpu
    if v < h["min"]:
        h["min"] = v
    elif v > h["max"]:
        h["max"] = v
    b = h["buckets"]
    b[le] = b.get(le, 0) + 1


def _hist_percentiles(h: dict, qs=(0.5, 0.9, 0.99)) -> dict:
    """Derived p50/p90/p99 from the power-of-two buckets.

    A value in bucket ``le`` lies in ``(le/2, le]``, so a percentile
    interpolated linearly inside its bucket carries at most a 2x
    (one-bucket-width) error — tight enough to rank latency tails and
    device-load distributions without pre-declared bucket edges.  Results
    clamp to the observed [min, max], so a single-valued histogram reports
    that exact value at every percentile.
    """
    n = h["count"]
    if not n:
        return {f"p{int(q * 100)}": None for q in qs}
    items = sorted(h["buckets"].items())
    out = {}
    for q in qs:
        target = q * n
        cum = 0.0
        val = h["max"]
        for le, c in items:
            if cum + c >= target:
                if le <= 0:
                    val = 0.0
                else:
                    lo = le / 2.0
                    val = lo + (le - lo) * ((target - cum) / c)
                break
            cum += c
        out[f"p{int(q * 100)}"] = min(max(val, h["min"]), h["max"])
    return out


def _hist_dump(h: dict) -> dict:
    """JSON-friendly histogram copy: buckets as sorted [le, count] pairs
    plus ``sum``/``count`` (and the derived ``mean`` and p50/p90/p99) so
    consumers of the OP_METRICS reply compute averages and tails without
    re-deriving from power-of-two bucket midpoints."""
    out = {"count": h["count"], "sum": h["sum"],
           "mean": (h["sum"] / h["count"]) if h["count"] else None,
           "min": h["min"], "max": h["max"],
           **_hist_percentiles(h),
           "buckets": sorted([le, n] for le, n in h["buckets"].items())}
    if "cpu_sum" in h:      # a timed span's histogram (tracing.op_scope)
        out["cpu_sum"] = h["cpu_sum"]
    return out


def _hist_load(d: dict) -> dict:
    h = {"count": d["count"], "sum": d["sum"],
         "min": d["min"], "max": d["max"],
         "buckets": {float(le): n for le, n in d["buckets"]}}
    if "cpu_sum" in d:
        h["cpu_sum"] = d["cpu_sum"]
    return h


def q_error(est, actual) -> float | None:
    """Cardinality q-error: ``max(est/actual, actual/est)``, the symmetric
    misestimate factor the AQE literature scores planners by (1.0 =
    perfect).  Zeros clamp to 1 row so empty results stay finite — an
    est=1000 that saw 0 rows scores 1000x, not inf.  ``None`` estimate
    (unknown cardinality) returns None: un-scorable, counted separately
    by ``engine.estimate.unknown``."""
    if est is None:
        return None
    e = max(float(est), 1.0)
    a = max(float(actual or 0), 1.0)
    return round(max(e / a, a / e), 4)


# -- per-query context ------------------------------------------------------

_NODE_FIELDS = ("calls", "wall_s", "rows_in", "rows_out", "chunks",
                "padded_rows", "host_syncs", "bytes_in", "bytes_out",
                "wire_bytes")


class QueryMetrics:
    """One query's attribution: node spans, counters, histograms, timers.

    Node spans are keyed by the caller's choice (the executor uses
    ``id(node)`` within one optimized plan) and accumulate across calls —
    a per-chunk re-walk of the scan-dependent subtree adds one call per
    chunk to each node it touches, so span totals ARE the per-node chunk
    and row flow.
    """

    __slots__ = ("qid", "name", "t0", "wall_s", "stats", "counters",
                 "node_spans", "hists", "timers", "mem", "fingerprint",
                 "source_fingerprint", "outcome", "degradations",
                 "decisions", "progress", "trace_id", "_lock")

    def __init__(self, name: str = ""):
        self.qid = next(_qids)
        self.name = name or f"q{self.qid}"
        # end-to-end trace id (utils/blackbox.py query_scope): stamped by
        # the bridge server from the client's v2 frame header, so client
        # spans, server spans, and post-mortem bundles join on one id
        self.trace_id: str = ""
        self.t0 = time.perf_counter()
        self.wall_s: float | None = None
        self.stats: dict = {}
        self.counters: dict[str, int] = {}
        self.node_spans: dict = {}
        self.hists: dict[str, dict] = {}
        self.timers: dict[str, float] = {}
        self.mem: dict = {}  # device-memory telemetry (mem_sample)
        self.fingerprint: str = ""  # plan fingerprint (profile-store key)
        # pre-optimization fingerprint (AQE profile-history key: stable
        # across runs even when warming changes the optimized shape)
        self.source_fingerprint: str = ""
        self.outcome: dict = {}  # status/kind/error (engine/recovery.py)
        self.degradations: list = []  # ladder steps taken (step, cause)
        self.decisions: list = []  # optimizer ledger (plan._decisions)
        # live progress counters, published at chunk boundaries
        self.progress: dict = {"chunks_done": 0, "chunks_total": 0,
                               "rows": 0, "bytes": 0}
        self._lock = threading.Lock()
        with _lock:
            _progress[self.qid] = self

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def observe(self, name: str, value: float) -> None:
        v = float(value)
        with _lock:     # the histograms' one lock, this query's too
            _hist_add(self.hists, name, v, _bucket_le(v))

    def hist_sum(self, name: str) -> float:
        """What histogram ``name`` has summed up so far (0.0: nothing)."""
        with _lock:
            h = self.hists.get(name)
            return h["sum"] if h is not None else 0.0

    def add_time(self, name: str, dt: float) -> None:
        with self._lock:
            self.timers[name] = self.timers.get(name, 0.0) + dt

    def _span_record(self, key, label: str) -> dict:
        r = self.node_spans.get(key)
        if r is None:
            r = self.node_spans[key] = dict.fromkeys(_NODE_FIELDS, 0)
            r["wall_s"] = 0.0
            r["label"] = label
        return r

    def node_add(self, key, label: str, **fields) -> None:
        """Accumulate span fields (``_NODE_FIELDS``) onto node ``key``."""
        with self._lock:
            r = self._span_record(key, label)
            for k, v in fields.items():
                r[k] += v

    def node_set(self, key, label: str, **fields) -> None:
        """SET derived span fields on node ``key`` (no accumulation).

        For values that are not running sums — an Exchange's skew ratio,
        straggler share, or per-device row breakdown, computed once from
        the whole exchange — where ``node_add``'s ``+=`` would corrupt.
        Also re-stamps ``label``: the caller passing derived fields knows
        the node's real name, which beats whatever incidental recorder
        (a keyed host_sync) created the record first."""
        with self._lock:
            r = self._span_record(key, label)
            r["label"] = label
            r.update(fields)

    @contextlib.contextmanager
    def node_span(self, key, label: str):
        """Wall-clock span for one execution of node ``key``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.node_add(key, label, calls=1,
                          wall_s=time.perf_counter() - t0)

    def host_sync(self, n: int = 1, key=None, label: str = "") -> None:
        self.count("engine.host_sync", n)
        if key is not None:
            self.node_add(key, label, host_syncs=n)

    def mem_sample(self, snap: dict) -> None:
        """Fold one ``memory.telemetry_snapshot`` into the query's
        device-memory telemetry: last live-bytes + high-water."""
        live = int(snap.get("live_bytes") or 0)
        peak = snap.get("peak_bytes")
        with self._lock:
            m = self.mem
            m["source"] = snap.get("source", "census")
            m["samples"] = m.get("samples", 0) + 1
            m["live_bytes"] = live
            hw = max(m.get("high_water_bytes", 0), live,
                     int(peak) if peak else 0)
            m["high_water_bytes"] = hw

    def note_stats(self, stats: dict) -> None:
        self.stats = dict(stats)

    def degrade(self, step: str, cause: str = "") -> None:
        """Record one degradation-ladder step (engine/recovery.py)."""
        with self._lock:
            self.degradations.append({"step": step, "cause": cause})

    def set_decisions(self, decisions) -> None:
        """Adopt the optimizer's decision ledger (``plan._decisions``)."""
        with self._lock:
            self.decisions = [dict(d) for d in decisions]

    def progress_total(self, chunks: int) -> None:
        """Grow the expected-chunk total (footer metadata, per stream —
        a query with several chunked scans accumulates each reader's
        estimate)."""
        with self._lock:
            self.progress["chunks_total"] += int(chunks)

    def progress_step(self, chunks: int = 0, rows: int = 0,
                      nbytes: int = 0) -> None:
        """Publish one chunk boundary: pure host-side dict increments
        (the caller already holds the row/byte counts from buffer
        metadata), so the execution hot path gains zero device syncs."""
        with self._lock:
            p = self.progress
            p["chunks_done"] += int(chunks)
            p["rows"] += int(rows)
            p["bytes"] += int(nbytes)

    def set_outcome(self, status: str, kind: str = "",
                    error: str = "") -> None:
        """Stamp the query's terminal status (``ok`` | ``error``)."""
        with self._lock:
            self.outcome = {"status": status}
            if kind:
                self.outcome["kind"] = kind
            if error:
                self.outcome["error"] = error[:200]

    def finish(self) -> None:
        if self.wall_s is None:
            self.wall_s = time.perf_counter() - self.t0
        with _lock:
            _progress.pop(self.qid, None)

    def summary(self) -> dict:
        """JSON-ready snapshot (safe to call live or after ``finish``)."""
        with _lock:     # `observe` writes them under this one
            hists = {k: _hist_dump(h) for k, h in self.hists.items()}
        with self._lock:
            wall = self.wall_s if self.wall_s is not None \
                else time.perf_counter() - self.t0
            nodes = [{k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in r.items()} for r in self.node_spans.values()]
            out = {"qid": self.qid, "name": self.name,
                   "wall_s": round(wall, 6),
                   "stats": dict(self.stats),
                   "counters": dict(self.counters),
                   "timers": {k: round(v, 6)
                              for k, v in self.timers.items()},
                   "histograms": hists,
                   "nodes": nodes}
            if self.fingerprint:
                out["fingerprint"] = self.fingerprint
            if self.source_fingerprint:
                out["source_fingerprint"] = self.source_fingerprint
            if self.trace_id:
                out["trace_id"] = self.trace_id
            if self.mem:
                out["memory"] = dict(self.mem)
            if self.outcome:
                out["outcome"] = dict(self.outcome)
            if self.degradations:
                out["degradations"] = list(self.degradations)
            if self.decisions:
                out["decisions"] = [dict(d) for d in self.decisions]
            return out


def current() -> QueryMetrics | None:
    """The query context bound to this thread (None outside any query)."""
    return _tls.q


@contextlib.contextmanager
def query(name: str = ""):
    """Open a query context on this thread; records its summary on exit.

    Yields ``None`` (and collects nothing) when ``SRJT_METRICS=0``.
    """
    if not config.metrics:
        yield None
        return
    qm = QueryMetrics(name)
    prev = current()
    _tls.q = qm
    try:
        yield qm
    finally:
        _tls.q = prev
        qm.finish()
        summary = qm.summary()
        with _lock:
            _recent.append(summary)
            # process-wide, so a reader can subtract every query's wall
            # time from what encloses it (Σ `bridge.op.*_s`) however many
            # clients ran and however few summaries `_recent` still holds
            _hist_add(_hists, "engine.query.wall_s", qm.wall_s,
                      _bucket_le(qm.wall_s))
        if config.profile_dir:
            # persist one compact profile per query (utils/profile.py);
            # profile IO must never fail the query it describes
            try:
                from . import profile
                profile.write(summary)
            except Exception as e:  # noqa: BLE001 — best-effort telemetry
                from .config import logger
                logger().debug("profile write failed: %s", e)


@contextlib.contextmanager
def maybe_query(name: str = ""):
    """``query(name)`` unless one is already active on this thread.

    Yields the NEW context or ``None`` — never the enclosing one — so
    callers know whether they own the stats/summary hookup.
    """
    if not config.metrics or current() is not None:
        yield None
        return
    with query(name) as qm:
        yield qm


@contextlib.contextmanager
def bind(qm: QueryMetrics | None):
    """Re-enter a captured query context on a helper thread."""
    prev = current()
    _tls.q = qm
    try:
        yield qm
    finally:
        _tls.q = prev


# -- module-level recording -------------------------------------------------

def count(name: str, n: int = 1) -> int:
    """Flat counter tick (always on) + active-query attribution."""
    v = tracing.count(name, n)
    q = current()
    if q is not None:
        q.count(name, n)
    return v


def observe(name: str, value: float, cpu: float | None = None) -> None:
    """Record ``value`` into histogram ``name`` (global + active query),
    one lock for both; ``cpu``: the thread's CPU seconds beside a timed
    span's wall seconds (``tracing.op_scope``)."""
    if not config.metrics:
        return
    v = float(value)
    le = _bucket_le(v)
    q = _tls.q
    with _lock:
        _hist_add(_hists, name, v, le, cpu)
        if q is not None:
            _hist_add(q.hists, name, v, le, cpu)


def time_add(name: str, dt: float) -> None:
    """Accumulate a duration gauge (global) + per-query timer."""
    if not config.metrics:
        return
    with _lock:
        _gauges[name] = _gauges.get(name, 0.0) + dt
    q = current()
    if q is not None:
        q.add_time(name, dt)


def gauge_set(name: str, value: float) -> None:
    if not config.metrics:
        return
    with _lock:
        _gauges[name] = value


def gauge_max(name: str, value: float) -> None:
    """Keep the high-water mark of ``name`` (e.g. dispatch-ahead depth)."""
    if not config.metrics:
        return
    with _lock:
        if value > _gauges.get(name, float("-inf")):
            _gauges[name] = value


def host_sync(n: int = 1, key=None, label: str = "") -> None:
    """Record a deliberate device->host sync point (attributed if keyed).

    Also drops a timeline instant event at the sync site — timeline-gated
    independently of SRJT_METRICS, so the Perfetto view marks the engine's
    deliberate syncs even with the metrics layer off — and a flight-
    recorder event (utils/blackbox.py), which survives even with BOTH
    observability layers off."""
    from . import blackbox
    blackbox.record("host_sync", label=label, n=n)
    if config.timeline:
        timeline.instant("engine.host_sync",
                         {"label": label} if label else None)
    if not config.metrics:
        return
    tracing.count("engine.host_sync", n)
    q = current()
    if q is not None:
        q.host_sync(n, key=key, label=label)


def mem_checkpoint(platform: str | None = None) -> None:
    """Sample device memory into the active query + process gauges.

    The executor calls this at query boundaries and chunk boundaries of
    the streaming loops; prefers the runtime allocator's stats (cheap C
    call on TPU/GPU) and falls back to the live-array byte census.  Pure
    host-side accounting — no device sync either way."""
    if not config.metrics:
        return
    from . import memory
    snap = memory.telemetry_snapshot(platform)
    live = int(snap.get("live_bytes") or 0)
    gauge_set("memory.device.live_bytes", live)
    peak = snap.get("peak_bytes")
    gauge_max("memory.device.high_water_bytes",
              int(peak) if peak else live)
    if config.timeline:
        timeline.counter("memory.device.live_bytes", live)
    q = current()
    if q is not None:
        q.mem_sample(snap)


# -- snapshots / test isolation ---------------------------------------------

def histograms_snapshot(prefix: str = "") -> dict:
    with _lock:
        return {k: _hist_dump(h) for k, h in _hists.items()
                if k.startswith(prefix)}


def gauges_snapshot(prefix: str = "") -> dict:
    with _lock:
        return {k: v for k, v in _gauges.items() if k.startswith(prefix)}


def recent_summaries(limit: int | None = None) -> list:
    """Completed-query summaries, oldest first (bounded window)."""
    with _lock:
        out = list(_recent)
    return out if limit is None else out[-limit:]


def progress_snapshot() -> list:
    """One entry per in-flight query, qid order: chunk/row/byte progress
    plus a derived ETA (remaining chunks x the query's own
    ``engine.stream.chunk_latency_s`` p50 — the histogram the streaming
    loops already feed, so the estimate costs the READER a percentile
    walk and the running query nothing).  ``chunks_total`` is the footer
    estimate (0 = no chunked stream opened yet).

    Entries carry a per-trace ``key`` (the trace id, or ``qid:<n>`` for
    untraced queries): under multi-tenancy two concurrent sessions can
    run the SAME plan — same name, same fingerprint — and a consumer
    keying by either would merge their (independent) ETAs.  Every field
    here, ETA included, is derived from the entry's own QueryMetrics, so
    same-fingerprint sessions never contaminate each other; ``key``
    makes that identity explicit for clients."""
    with _lock:
        live = list(_progress.values())
    out = []
    for qm in sorted(live, key=lambda q: q.qid):
        with _lock:
            h = qm.hists.get("engine.stream.chunk_latency_s")
            p50 = _hist_percentiles(h, (0.5,))["p50"] if h else None
        with qm._lock:
            p = dict(qm.progress)
            entry = {"qid": qm.qid, "name": qm.name,
                     "key": qm.trace_id or f"qid:{qm.qid}",
                     "fingerprint": qm.fingerprint,
                     "trace_id": qm.trace_id,
                     "wall_s": round(time.perf_counter() - qm.t0, 6),
                     **p}
        remaining = p["chunks_total"] - p["chunks_done"]
        entry["eta_s"] = (round(remaining * p50, 6)
                          if p50 is not None and remaining > 0 else None)
        out.append(entry)
    return out


# -- Prometheus text exposition ----------------------------------------------

def _prom_name(name: str) -> str:
    """Dotted metric name -> exposition-safe name under the srjt_ prefix."""
    safe = "".join(c if (c.isalnum() or c == "_") else "_" for c in name)
    return f"srjt_{safe}"


def _prom_hist(name: str, h: dict, lines: list) -> None:
    """Render one ``_hist_dump``-shaped histogram: cumulative le buckets
    (power-of-two upper bounds) + the mandatory +Inf, _sum, _count."""
    lines.append(f"# TYPE {name} histogram")
    cum = 0
    for le, n in h.get("buckets", ()):
        cum += n
        lines.append(f'{name}_bucket{{le="{float(le):g}"}} {cum}')
    lines.append(f'{name}_bucket{{le="+Inf"}} {h["count"]}')
    lines.append(f"{name}_sum {float(h['sum']):g}")
    lines.append(f"{name}_count {h['count']}")


def prometheus_text(snap: dict | None = None, prefix: str = "") -> str:
    """The whole counters/gauges/histograms registry in Prometheus text
    exposition format (version 0.0.4) — hand-rolled, no client library.

    ``snap`` accepts a ``snapshot()``-shaped dict (e.g. an OP_METRICS
    reply decoded by ``tools/srjt_export.py``) so a scrape can render a
    remote server's registry; default is this process's live registry.
    Adds ``srjt_queries_in_flight`` and per-query progress gauges from
    the progress registry (local scrapes only — a snapshot dict carries
    no live progress), and SLO burn-rate gauges per source fingerprint
    when objectives are declared (``SRJT_SLO_MS``, utils/blackbox.py) —
    either from the snapshot's ``slo`` block (an OP_METRICS reply) or
    evaluated locally from profile-store history."""
    if snap is None:
        snap = {"counters": tracing.counters_snapshot(prefix),
                "histograms": histograms_snapshot(prefix),
                "gauges": gauges_snapshot(prefix),
                "progress": progress_snapshot()}
        from . import blackbox
        if blackbox.slo_enabled():
            snap["slo"] = blackbox.slo_report()
    lines: list[str] = []
    for k in sorted(snap.get("counters") or {}):
        name = _prom_name(k)
        lines.append(f"# TYPE {name} counter")
        lines.append(f"{name} {snap['counters'][k]}")
    for k in sorted(snap.get("gauges") or {}):
        name = _prom_name(k)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {float(snap['gauges'][k]):g}")
    for k in sorted(snap.get("histograms") or {}):
        _prom_hist(_prom_name(k), snap["histograms"][k], lines)
    progress = snap.get("progress")
    if progress is not None:
        lines.append("# TYPE srjt_queries_in_flight gauge")
        lines.append(f"srjt_queries_in_flight {len(progress)}")
        for g in ("chunks_done", "chunks_total", "rows", "bytes"):
            name = f"srjt_query_progress_{g}"
            if progress:
                lines.append(f"# TYPE {name} gauge")
                for e in progress:
                    lines.append(f'{name}{{qid="{e["qid"]}",'
                                 f'name="{e["name"]}"}} {e[g]}')
    slo = snap.get("slo") or {}
    if slo.get("enabled"):
        if slo.get("default_ms") is not None:
            lines.append("# TYPE srjt_slo_default_objective_ms gauge")
            lines.append("srjt_slo_default_objective_ms "
                         f"{float(slo['default_ms']):g}")
        entries = slo.get("entries") or []
        for g in ("objective_ms", "runs", "breaches", "errors",
                  "worst_ms", "burn_rate"):
            if not entries:
                break
            name = f"srjt_slo_{g}"
            lines.append(f"# TYPE {name} gauge")
            for e in entries:
                lines.append(f'{name}{{fingerprint="{e["fingerprint"]}"}} '
                             f"{float(e[g]):g}")
    return "\n".join(lines) + "\n"


def snapshot(prefix: str = "") -> dict:
    """The full export body: counters + histograms + gauges + queries."""
    return {"counters": tracing.counters_snapshot(prefix),
            "histograms": histograms_snapshot(prefix),
            "gauges": gauges_snapshot(prefix),
            "queries": recent_summaries()}


def reset(prefix: str = "") -> None:
    """Zero histograms/gauges under ``prefix`` (tests isolate with this);
    a full reset (empty prefix) also drops the recent-query window."""
    with _lock:
        for k in [k for k in _hists if k.startswith(prefix)]:
            del _hists[k]
        for k in [k for k in _gauges if k.startswith(prefix)]:
            del _gauges[k]
        if not prefix:
            _recent.clear()


def restore(hists: dict | None = None, gauges: dict | None = None,
            prefix: str = "") -> None:
    """Put back a ``histograms_snapshot``/``gauges_snapshot`` pair taken
    before ``reset(prefix)`` (the ``metrics_isolation`` fixture's tail)."""
    with _lock:
        for k in [k for k in _hists if k.startswith(prefix)]:
            del _hists[k]
        for k in [k for k in _gauges if k.startswith(prefix)]:
            del _gauges[k]
        for k, d in (hists or {}).items():
            _hists[k] = _hist_load(d)
        _gauges.update(gauges or {})

"""Runtime flag system (analog of the reference's config pass-through).

The reference threads Maven ``-D`` properties through ant/cmake into compile
definitions and JVM sysprops (reference pom.xml:79-103, 404-408;
CONTRIBUTING.md:64-78 documents the table).  A jax library's equivalent is
environment flags read once at import:

| flag | default | reference analog |
|---|---|---|
| ``SRJT_TRACE``        | ``0``   | ``ai.rapids.cudf.nvtx.enabled`` (pom.xml:84,407) |
| ``SRJT_LOG_LEVEL``    | ``WARNING`` | ``RMM_LOGGING_LEVEL`` (pom.xml:81) |
| ``SRJT_LEAK_DEBUG``   | ``0``   | ``ai.rapids.refcount.debug`` (pom.xml:85,406) |
| ``SRJT_FUSE``         | ``1``   | whole-stage codegen toggle (engine segment fusion) |
| ``SRJT_PREFETCH``     | ``1``   | chunked-scan pipeline depth (0 = serial) |
| ``SRJT_PLAN_CACHE``   | ``128`` | plan-cache capacity (spark.sql plan-cache size) |
| ``SRJT_SEGMENT_CACHE``| ``256`` | compiled-segment cache capacity |
| ``SRJT_BUILD_CACHE``  | ``32``  | prepared-join-build cache capacity (entries) |
| ``SRJT_METRICS``      | ``1``   | query-scoped metrics collection (spans/histograms/gauges, utils/metrics.py) |
| ``SRJT_TIMELINE``     | ``0``   | in-process trace-event timeline (utils/timeline.py, Perfetto-loadable JSON) |
| ``SRJT_TIMELINE_CAP`` | ``16384`` | timeline ring-buffer capacity (events; oldest dropped) |
| ``SRJT_LOG_FORMAT``   | ``text``| ``json`` emits one JSON object per log line (ts/level/logger/msg + active query) |
| ``SRJT_VERIFY``       | ``1``   | static plan verification in optimize()/PLAN_EXECUTE (engine/verify.py) |
| ``SRJT_DIST``         | ``0``   | partitioning-aware distributed planning (Exchange placement rules) |
| ``SRJT_BROADCAST_ROWS`` | ``100000`` | broadcast-join threshold: estimated build rows at or under this replicate instead of shuffling |
| ``SRJT_AQE``          | ``0``   | adaptive query execution (engine/adaptive.py): runtime broadcast flip, hot-key skew split, profile-warmed planning |
| ``SRJT_FUSE_EXCHANGE`` | ``0``  | whole-stage exchange fusion: lower the partial-agg -> hash Exchange -> final-agg sandwich into ONE pjit/shard_map program (engine/segment.py fused stage) |
| ``SRJT_AQE_SKEW``     | ``4.0`` | skew (max/mean device load) above which a hash exchange splits its hot keys round-robin |
| ``SRJT_AQE_BROADCAST_ROWS`` | ``-1`` | measured-rows threshold for the runtime broadcast flip (``-1`` = follow ``SRJT_BROADCAST_ROWS``) |
| ``SRJT_PROFILE_DIR``  | *(unset)* | persist one compact query profile JSON per query into this dir (utils/profile.py; empty = off) |
| ``SRJT_PROFILE_CAP``  | ``512`` | on-disk profile ring capacity (oldest profiles pruned past this) |
| ``SRJT_FAULTS``       | *(unset)* | deterministic fault injection spec ``site:nth[:kind],...`` (utils/faults.py; empty = all seams no-op) |
| ``SRJT_RETRY_MAX``    | ``3``   | max per-site retries of transient failures (engine/recovery.py) |
| ``SRJT_RETRY_BACKOFF_S`` | ``0.01`` | base retry backoff seconds (doubles per attempt, ±25% jitter) |
| ``SRJT_QUERY_TIMEOUT_S`` | ``0`` | cooperative per-query deadline in seconds (0 = none; checked at chunk boundaries) |
| ``SRJT_BRIDGE_TIMEOUT_S`` | ``60`` | per-op socket deadline on bridge client+server (0 = block forever, the pre-hardening behavior) |
| ``SRJT_MEM_DEBUG``    | ``0``   | live-buffer census checkpoints + MemoryScope exit report (io chunked reader, utils/memory.py) |
| ``SRJT_BLACKBOX``     | ``1``   | always-on flight recorder (utils/blackbox.py): bounded ring of coarse events, independent of SRJT_METRICS/SRJT_TIMELINE |
| ``SRJT_BLACKBOX_DIR`` | *(unset)* | post-mortem bundle directory (empty = ring only, no disk writes) |
| ``SRJT_BLACKBOX_CAP`` | ``512`` | flight-recorder ring capacity (events; oldest dropped) |
| ``SRJT_SLO_MS``       | *(unset)* | latency objectives: ``default_ms[,fp12=ms,...]`` per source fingerprint, evaluated from the profile store (utils/blackbox.py slo_report) |
| ``SRJT_TRACE_ID``     | *(unset)* | inherited trace context for helper processes; minted per client/query when empty |
| ``SRJT_MAX_SESSIONS`` | ``8``   | concurrent admitted PLAN_EXECUTE sessions; arrivals past this queue at admission |
| ``SRJT_ADMISSION_QUEUE_S`` | ``5.0`` | max seconds a query waits in the admission queue before it is shed (AdmissionRejectedError) |
| ``SRJT_ADMISSION_BURN`` | ``0.9`` | SLO burn rate (breaches/runs from the profile store) at or above which a saturated server sheds the fingerprint immediately instead of queueing |
| ``SRJT_SESSION_BUDGET_BYTES`` | ``0`` | per-session device-memory budget charged at chunk boundaries (0 = unlimited; bounds the spill ladder and gates the OOM retry-first path) |
| ``SRJT_RESULT_CACHE`` | ``0``   | result-set cache capacity (entries) keyed (plan fingerprint, data version); 0 = off |
| ``SRJT_DEVICE_DECODE`` | ``0``  | device-side parquet page decode (ops/parquet_decode.py): ship compressed pages, decompress + decode in the fused scan segment; ineligible chunks re-plan to the host decoder per chunk |

``refresh()`` re-reads the environment (tests use it); everything else
reads the module-level singleton.
"""

from __future__ import annotations

import json
import logging
import os
import sys
from dataclasses import dataclass, fields


def _bool_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _int_flag(name: str, default: int, minimum: int = 0) -> int:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return max(minimum, int(v.strip()))
    except ValueError:
        return default


def _float_flag(name: str, default: float, minimum: float = 0.0) -> float:
    v = os.environ.get(name)
    if v is None:
        return default
    try:
        return max(minimum, float(v.strip()))
    except ValueError:
        return default


@dataclass
class Config:
    trace: bool = False          # profiler annotations around ops
    log_level: str = "WARNING"
    leak_debug: bool = False     # bridge handle-leak tracking verbosity
    fuse: bool = True            # engine whole-stage segment fusion
    prefetch: int = 1            # chunked-scan pipeline depth (0 = serial)
    plan_cache: int = 128        # PlanCache capacity (entries)
    segment_cache: int = 256     # compiled-segment cache capacity (entries)
    build_cache: int = 32        # prepared-build cache capacity (entries)
    metrics: bool = True         # query-scoped metrics (utils/metrics.py)
    timeline: bool = False       # trace-event timeline (utils/timeline.py)
    timeline_cap: int = 16384    # timeline ring-buffer capacity (events)
    log_format: str = "text"     # "text" | "json" (structured log lines)
    verify: bool = True          # static plan verification (engine/verify.py)
    distribute: bool = False     # Exchange-placement distributed planning
    broadcast_rows: int = 100_000  # broadcast-join build-size threshold (rows)
    aqe: bool = False            # adaptive execution (engine/adaptive.py)
    fuse_exchange: bool = False  # in-program exchange (fused dist stage)
    aqe_skew: float = 4.0        # skew threshold for the hot-key split
    aqe_broadcast_rows: int = -1  # runtime flip threshold (-1 = follow
    #                               broadcast_rows)
    profile_dir: str = ""        # query-profile store dir (empty = off)
    profile_cap: int = 512       # profile-store ring capacity (files)
    faults: str = ""             # fault-injection spec (utils/faults.py)
    retry_max: int = 3           # transient-failure retry bound per site
    retry_backoff_s: float = 0.01  # base retry backoff (doubles/attempt)
    query_timeout_s: float = 0.0   # cooperative query deadline (0 = none)
    bridge_timeout_s: float = 60.0  # bridge per-op socket deadline (0=off)
    mem_debug: bool = False      # live-buffer census + MemoryScope reports
    blackbox: bool = True        # flight recorder ring (utils/blackbox.py)
    blackbox_dir: str = ""       # post-mortem bundle dir (empty = no disk)
    blackbox_cap: int = 512      # flight-recorder ring capacity (events)
    slo_ms: str = ""             # latency objectives spec (default[,fp=ms])
    trace_id: str = ""           # inherited trace context (subprocesses)
    max_sessions: int = 8        # concurrent admitted PLAN_EXECUTE sessions
    admission_queue_s: float = 5.0  # admission-queue wait bound (seconds)
    admission_burn: float = 0.9  # burn rate that sheds when saturated
    session_budget_bytes: int = 0  # per-session memory budget (0=unlimited)
    result_cache: int = 0        # result-set cache capacity (0 = off)
    device_decode: bool = False  # device-side parquet page decode

    @classmethod
    def from_env(cls) -> "Config":
        return cls(
            trace=_bool_flag("SRJT_TRACE", False),
            log_level=os.environ.get("SRJT_LOG_LEVEL", "WARNING").upper(),
            leak_debug=_bool_flag("SRJT_LEAK_DEBUG", False),
            fuse=_bool_flag("SRJT_FUSE", True),
            prefetch=_int_flag("SRJT_PREFETCH", 1),
            plan_cache=_int_flag("SRJT_PLAN_CACHE", 128, minimum=1),
            segment_cache=_int_flag("SRJT_SEGMENT_CACHE", 256, minimum=1),
            build_cache=_int_flag("SRJT_BUILD_CACHE", 32, minimum=1),
            metrics=_bool_flag("SRJT_METRICS", True),
            timeline=_bool_flag("SRJT_TIMELINE", False),
            timeline_cap=_int_flag("SRJT_TIMELINE_CAP", 16384, minimum=16),
            log_format=os.environ.get("SRJT_LOG_FORMAT",
                                      "text").strip().lower(),
            verify=_bool_flag("SRJT_VERIFY", True),
            distribute=_bool_flag("SRJT_DIST", False),
            broadcast_rows=_int_flag("SRJT_BROADCAST_ROWS", 100_000),
            aqe=_bool_flag("SRJT_AQE", False),
            fuse_exchange=_bool_flag("SRJT_FUSE_EXCHANGE", False),
            aqe_skew=_float_flag("SRJT_AQE_SKEW", 4.0, minimum=1.0),
            aqe_broadcast_rows=_int_flag("SRJT_AQE_BROADCAST_ROWS", -1,
                                         minimum=-1),
            profile_dir=os.environ.get("SRJT_PROFILE_DIR", "").strip(),
            profile_cap=_int_flag("SRJT_PROFILE_CAP", 512, minimum=1),
            faults=os.environ.get("SRJT_FAULTS", "").strip(),
            retry_max=_int_flag("SRJT_RETRY_MAX", 3),
            retry_backoff_s=_float_flag("SRJT_RETRY_BACKOFF_S", 0.01),
            query_timeout_s=_float_flag("SRJT_QUERY_TIMEOUT_S", 0.0),
            bridge_timeout_s=_float_flag("SRJT_BRIDGE_TIMEOUT_S", 60.0),
            mem_debug=_bool_flag("SRJT_MEM_DEBUG", False),
            blackbox=_bool_flag("SRJT_BLACKBOX", True),
            blackbox_dir=os.environ.get("SRJT_BLACKBOX_DIR", "").strip(),
            blackbox_cap=_int_flag("SRJT_BLACKBOX_CAP", 512, minimum=16),
            slo_ms=os.environ.get("SRJT_SLO_MS", "").strip(),
            trace_id=os.environ.get("SRJT_TRACE_ID", "").strip(),
            max_sessions=_int_flag("SRJT_MAX_SESSIONS", 8, minimum=1),
            admission_queue_s=_float_flag("SRJT_ADMISSION_QUEUE_S", 5.0),
            admission_burn=_float_flag("SRJT_ADMISSION_BURN", 0.9),
            session_budget_bytes=_int_flag("SRJT_SESSION_BUDGET_BYTES", 0),
            result_cache=_int_flag("SRJT_RESULT_CACHE", 0),
            device_decode=_bool_flag("SRJT_DEVICE_DECODE", False),
        )


config = Config.from_env()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def child_environ() -> dict:
    """Environment for a spawned helper process.

    A copy of ours with the package importable regardless of the child's
    cwd (PYTHONPATH).  The jax platform is NOT defaulted: a child inherits
    ``JAX_PLATFORMS`` (the test suite exports ``cpu``) or, unset, takes
    jax's own choice — the accelerator where there is one.  An accelerator
    belongs to one process at a time, so a parent that has initialised a
    jax backend itself passes ``JAX_PLATFORMS="cpu"`` explicitly for its
    children.  Lives here so ``os.environ`` stays confined to this module
    (the config-env-read lint); callers layer their own overrides on the
    returned dict.
    """
    e = dict(os.environ)
    e["PYTHONPATH"] = _repo_root() + os.pathsep + e.get("PYTHONPATH", "")
    return e


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at a stable directory;
    returns the directory in use.

    Called by the entry point that compiles (``bridge.server.main``),
    never at package import.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax already honours it and nothing
    is set in code.  Otherwise the cache lives at ONE fixed path inside the
    checkout — the path is part of every cache key's lookup, so a temp
    name, pid or timestamp would never hit.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env_dir:
        return env_dir
    import jax
    path = os.path.join(_repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, not only those over jax's 1 s default: a warm start
    # then compiles nothing, and a re-run adds no entry
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def refresh() -> Config:
    """Re-read flags from the environment (returns the live singleton).

    Copies every dataclass field, so a flag added to ``Config`` is
    refresh-visible automatically instead of needing a hand-maintained
    assignment here (where ``SRJT_METRICS`` would have been dropped).
    """
    new = Config.from_env()
    for f in fields(Config):
        setattr(config, f.name, getattr(new, f.name))
    logger()  # re-applies the (possibly changed) level
    return config


class _JsonLogFormatter(logging.Formatter):
    """One JSON object per line: ts/level/logger/msg plus the active query
    name from the metrics layer when one is bound on the emitting thread —
    bridge-server log lines correlate with per-query summaries by name."""

    def format(self, record: logging.LogRecord) -> str:
        doc = {"ts": round(record.created, 6),
               "level": record.levelname,
               "logger": record.name,
               "msg": record.getMessage()}
        try:
            from . import metrics
            q = metrics.current()
            if q is not None:
                doc["query"] = q.name
        except Exception:
            pass
        if record.exc_info:
            doc["exc"] = self.formatException(record.exc_info)
        return json.dumps(doc)


def logger() -> logging.Logger:
    """The package logger (analog of the reference's slf4j-api single dep).

    A ``NullHandler`` keeps library log records from falling through to
    lastResort when the host app never configured logging, and the level
    is applied on EVERY call — a host app that configures root logging
    before importing us must not freeze our level at the import-time
    default.

    ``SRJT_LOG_FORMAT=json`` attaches a stderr handler with
    ``_JsonLogFormatter`` (and stops propagation so lines emit exactly
    once); switching back to ``text`` detaches it and restores the
    host-app-owned path.
    """
    log = logging.getLogger("spark_rapids_jni_tpu")
    if not any(isinstance(h, logging.NullHandler) for h in log.handlers):
        log.addHandler(logging.NullHandler())
    json_handlers = [h for h in log.handlers
                     if getattr(h, "_srjt_json", False)]
    if config.log_format == "json":
        if not json_handlers:
            h = logging.StreamHandler(sys.stderr)
            h.setFormatter(_JsonLogFormatter())
            h._srjt_json = True
            log.addHandler(h)
        log.propagate = False
    else:
        for h in json_handlers:
            log.removeHandler(h)
        log.propagate = True
    log.setLevel(config.log_level)
    return log

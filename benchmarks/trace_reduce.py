"""From a profiler trace (`*.xplane.pb`) to the numbers the benchmark reports.

    reduce_dir(log_dir) -> {"window_s", "busy_s", "devices", "scopes",
                            "programs", "ops", "gaps", "launches"} | None
    breakdown(reduced)  -> {"device_ops": [[name, s]...], "idle_gaps": [...]}

What is read, and why it is read so (one v5e trace was looked at by hand
first; `fixtures/tpu_probe.xplane.pb` is that trace, and `selfcheck.py`
reduces it to known numbers):

* The file is decoded here, with a minimal protobuf reader.  The scope a
  device op was traced under (`tf_op`, e.g. ``jit(fn)/groupby_padded/sort``)
  and its `hlo_category` are stats of the event's METADATA, which
  ``jax.profiler.ProfileData`` does not expose; and a benchmark must not
  need tensorflow to read its own trace.
* Device planes are ``/device:TPU:<n>``.  Line ``XLA Ops`` holds one event
  per executed HLO op, line ``XLA Modules`` one per program execution, with
  a ``run_id``.  Busy time is the union of the op intervals inside the
  traced window; the window is the launcher's ``bench.trace_window`` span.
* A scope that wraps the CALL of a jitted program (the engine's
  ``op_scope("engine.fused_segment")``) never reaches the program's op
  names.  It is found from the host side instead: with ``SRJT_TRACE=1`` the
  scope is a host span; the runtime's ``DoEnqueueProgram`` event carries
  the ``run_id`` of the program execution it enqueued, the device's module
  event carries the same ``run_id``, and the runtime's flow links lead
  from the enqueue back to the call that asked for it (`_launch_time`: the
  enqueue may be deferred to another thread).  So every program execution
  is attributed to the innermost host span open where it was launched.
* An idle gap is named by what the host was doing: the shortest host span
  that covers the gap's midpoint.

Device and host events share the profile's clock to about a millisecond
(the device's offsets are converted by the runtime); gaps and windows here
are far longer.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct

WINDOW_SPAN = "bench.trace_window"
ENQUEUE_EVENT = "DoEnqueueProgram"
MIN_HOST_SPAN_PS = 20_000_000       # 20 us: shorter host events name no gap
MIN_GAP_PS = 100_000_000            # 100 us: shorter gaps are summed unnamed
SHORT_GAPS = "(gaps under 100 us, between ops of one program)"
NO_SPAN = "(no host span: waiting for a request)"
TOP = 10
# a host span that is a scope of the program or of the launcher, not an
# event of the runtime (CamelCase, `::`, spaces): engine.fused_segment,
# groupby_padded, bridge.op.plan_execute
SCOPE_NAME = re.compile(r"[a-z_][a-z0-9_]*(\.[a-z0-9_]+)*$")


# -- a minimal protobuf reader ---------------------------------------------------

def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            value = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span: tuple, stat_names: dict) -> tuple:
    """XStat -> (name, value).  2 double, 3 uint64, 4 int64, 5 str,
    7 ref (a string kept once, as a stat's name)."""
    name, value = "", None
    for f, wire, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 5:
            value = _text(buf, v)
        elif f == 7:
            value = stat_names.get(v, "")
        elif f == 4:
            value = _signed(v)
        elif f == 3:
            value = v
        elif f == 2 and wire == 1:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
    return name, value


def _map_entry(buf, span: tuple) -> tuple:
    key, value = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


class _Plane:
    """One XPlane: its name, lines, and metadata tables."""

    def __init__(self, buf, span: tuple):
        self.buf = buf
        self.name = ""
        self.line_spans, meta_spans, stat_spans = [], [], []
        for f, _, v in _fields(buf, *span):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                self.line_spans.append(v)
            elif f == 4:
                meta_spans.append(v)
            elif f == 5:
                stat_spans.append(v)
        self._meta_spans, self._stat_spans = meta_spans, stat_spans
        self.stat_names: dict = {}
        self.events_meta: dict = {}     # id -> {"name", stats...}

    def load_metadata(self, wanted_stats: tuple) -> None:
        buf = self.buf
        for span in self._stat_spans:
            key, value = _map_entry(buf, span)
            for f, _, v in _fields(buf, *value):
                if f == 2:
                    self.stat_names[key] = _text(buf, v)
        for span in self._meta_spans:
            key, value = _map_entry(buf, span)
            md = {"name": ""}
            for f, _, v in _fields(buf, *value):
                if f == 2:
                    md["name"] = _text(buf, v)
                elif f == 5 and wanted_stats:
                    name, val = _stat(buf, v, self.stat_names)
                    if name in wanted_stats:
                        md[name] = val
            self.events_meta[key] = md

    def lines(self):
        """(line name, timestamp_ps, [event spans])."""
        buf = self.buf
        for span in self.line_spans:
            name, ts_ns, events = "", 0, []
            for f, _, v in _fields(buf, *span):
                if f == 2:
                    name = _text(buf, v)
                elif f == 3:
                    ts_ns = v
                elif f == 4:
                    events.append(v)
            yield name, ts_ns * 1000, events

    def event(self, span: tuple, base_ps: int, want_stats: bool) -> tuple:
        """(metadata id, start_ps, duration_ps, {stat: value})."""
        buf = self.buf
        mid = off = dur = 0
        stats = {}
        for f, _, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
            elif f == 4 and want_stats:
                name, val = _stat(buf, v, self.stat_names)
                stats[name] = val
        return mid, base_ps + off, dur, stats


def read_planes(path: str) -> list:
    with open(path, "rb") as f:
        buf = f.read()
    return [_Plane(buf, v) for f, _, v in _fields(buf, 0, len(buf)) if f == 1]


# -- the reduction ---------------------------------------------------------------

def _union_s(intervals: list, lo: int, hi: int) -> float:
    """Seconds covered by the union of (start_ps, end_ps), clipped."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e12


def _gaps(intervals: list, lo: int, hi: int) -> list:
    """The idle (start_ps, end_ps) stretches of [lo, hi]."""
    out, at = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def _inner_scope(tf_op: str | None, category: str | None) -> str:
    """``jit(fn)/groupby_padded/jit(sort)/sort:`` -> ``groupby_padded``:
    the named scopes of the op's path, without the jit(...) frames and the
    primitive; an op with no path is named by its HLO category."""
    if tf_op:
        parts = [p for p in tf_op.rstrip(":").split("/")[:-1]
                 if p and not (p.startswith("jit(") or p.startswith("pjit("))]
        if parts:
            return "/".join(parts[:2])
    return f"({category or 'op'})"


MAX_HOPS = 8


def _launch_time(line: int, t: int, consumers: dict, producers: dict) -> int:
    """Where on the host the work that runs at ``t`` on ``line`` was asked
    for.  The runtime links an event that hands work on (stats `_pt`,
    `_p`) to the event that takes it up (`_ct`, `_c`), on the same thread
    or — when the runtime defers a launch until its inputs have arrived —
    on another, later.  Follow the links back from the innermost consumer
    event around ``t`` to its producer, as far as they go."""
    for _ in range(MAX_HOPS):
        best = None
        for start, end, key in consumers.get(line, ()):
            if start <= t < end and key in producers \
                    and producers[key][1] <= start \
                    and (best is None or end - start < best[1] - best[0]):
                best = (start, end, key)
        if best is None:
            break
        line, t = producers[best[2]]
    return t


def _host_spans(plane: _Plane) -> tuple:
    """(named spans [(start, end, name)], launches [(launch time,
    run_id)], window (start, end) | None) of the host plane."""
    plane.load_metadata(())
    spans, enqueues, window = [], [], None
    consumers: dict = {}    # line -> [(start, end, (type, id))]
    producers: dict = {}    # (type, id) -> (line, start)
    for line, (_, base, events) in enumerate(plane.lines()):
        for span in events:
            mid, start, dur, stats = plane.event(span, base, True)
            name = plane.events_meta.get(mid, {}).get("name", "")
            if "_p" in stats:
                producers[(stats.get("_pt"), stats["_p"])] = (line, start)
            if "_c" in stats:
                consumers.setdefault(line, []).append(
                    (start, start + dur, (stats.get("_ct"), stats["_c"])))
            if name == ENQUEUE_EVENT and "run_id" in stats:
                enqueues.append((line, start, stats["run_id"]))
            elif name == WINDOW_SPAN:
                window = (start, start + dur)
            elif dur >= MIN_HOST_SPAN_PS:
                spans.append((start, start + dur, name))
    launches = [(_launch_time(line, t, consumers, producers), run_id)
                for line, t, run_id in enqueues]
    return spans, launches, window


def _innermost(spans: list, times: list, only: set | None = None) -> list:
    """For each time of ``times`` (ascending) the name of the shortest of
    ``spans`` (sorted by start) that covers it, or None.  One sweep: the
    spans open at a time are few (nesting depth times threads)."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            if only is None or spans[i][2] in only:
                active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > t]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        out.append(best[2] if best else None)
    return out


def reduce_file(path: str) -> dict | None:
    planes = read_planes(path)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    hosts = [p for p in planes if p.name == "/host:CPU"]
    if not devices:
        return None
    spans, enqueues, window = _host_spans(hosts[0]) if hosts \
        else ([], [], None)
    spans.sort()
    scope_names = {n for _, _, n in spans if SCOPE_NAME.match(n)}
    enqueues.sort()
    # run_id -> the host scope that enqueued that program execution
    launch_scope = dict(zip(
        (run_id for _, run_id in enqueues),
        _innermost(spans, [t for t, _ in enqueues], scope_names)))

    busy, scopes, programs, ops, all_intervals = [], {}, {}, {}, []
    launches: dict = {}     # scope -> program executions
    lo = hi = None
    for plane in devices:
        plane.load_metadata(("tf_op", "hlo_category"))
        op_events, mod_events = [], []
        for name, base, events in plane.lines():
            if name == "XLA Ops":
                op_events = [plane.event(s, base, False) for s in events]
            elif name == "XLA Modules":
                mod_events = [plane.event(s, base, True) for s in events]
        intervals = [(s, s + d) for _, s, d, _ in (op_events or mod_events)]
        if not intervals:
            continue
        if window is None:      # no launcher span: the trace's own extent
            w_lo = min(s for s, _ in intervals)
            w_hi = max(e for _, e in intervals)
        else:
            w_lo, w_hi = window
        lo = w_lo if lo is None else min(lo, w_lo)
        hi = w_hi if hi is None else max(hi, w_hi)
        busy.append(_union_s(intervals, w_lo, w_hi))
        all_intervals.append(intervals)
        # program executions, in time order, each with its launch scope
        mods = sorted((s, s + d, plane.events_meta.get(m, {}).get("name", ""),
                       launch_scope.get(st.get("run_id")))
                      for m, s, d, st in mod_events)
        mod_starts = [m[0] for m in mods]
        for s, e, prog, scope in mods:
            if s < w_lo or e > w_hi:
                continue
            prog = prog.split("(")[0]
            key = scope or f"[{prog}]"
            scopes[key] = scopes.get(key, 0.0) + (e - s) / 1e12
            launches[key] = launches.get(key, 0) + 1
            programs[prog] = programs.get(prog, 0.0) + (e - s) / 1e12
        for m, s, d, _ in op_events:
            if s < w_lo or s + d > w_hi:
                continue
            md = plane.events_meta.get(m, {})
            i = bisect.bisect_right(mod_starts, s) - 1
            outer = None
            if i >= 0 and mods[i][1] >= s:
                outer = mods[i][3] or f"[{mods[i][2].split('(')[0]}]"
            key = f"{outer or '[no program]'}/" \
                  f"{_inner_scope(md.get('tf_op'), md.get('hlo_category'))}"
            ops[key] = ops.get(key, 0.0) + d / 1e12
    if not busy:
        return None
    idle = _gaps(all_intervals[0], lo, hi)
    long_gaps = [g for g in idle if g[1] - g[0] >= MIN_GAP_PS]
    gaps = {SHORT_GAPS: sum(e - s for s, e in idle
                            if e - s < MIN_GAP_PS) / 1e12}
    mids = [(s + e) // 2 for s, e in long_gaps]
    # by the program's own scope where one is open, else by the runtime's
    names = [a or b or NO_SPAN for a, b in zip(
        _innermost(spans, mids, scope_names), _innermost(spans, mids))]
    for (s, e), name in zip(long_gaps, names):
        gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e12
    return {"window_s": (hi - lo) / 1e12,
            "busy_s": sum(busy) / len(busy),
            "devices": len(busy),
            "scopes": scopes, "launches": launches, "programs": programs,
            "ops": ops, "gaps": gaps}


def reduce_dir(log_dir: str) -> dict | None:
    """The one `.xplane.pb` the profiler wrote under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    return reduce_file(paths[-1])


def scope_s_per_query(reduced: dict | None, scope: str, loop) -> float | None:
    """Device seconds per query of the program executions launched under
    host span ``scope``: their share of the traced stretch (device-seconds
    per second) times the window's mean seconds per completed query."""
    queries = sum(dt is not None for _, _, dt in loop.samples)
    if not reduced or not reduced["scopes"].get(scope) or not queries:
        return None
    return reduced["scopes"][scope] / reduced["window_s"] \
        * (loop.t_end - loop.t_start) / queries


def _top(table: dict) -> list:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:TOP]]


def breakdown(reduced: dict) -> dict:
    return {"device_ops": _top(reduced["ops"]),
            "idle_gaps": _top(reduced["gaps"])}

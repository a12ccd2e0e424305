"""Request-level tracing and flight recorder for the serving stack.

One :class:`Tracer` per scheduler records span timelines (ADMIT /
QUEUED / PREFILL_CHUNK[i] / KV_TRANSFER / FINISH, each carrying
model/backend/page-count attributes), live regions at the layer
boundaries of the served path (``repro.*``, see :meth:`Tracer.region`),
process-wide instant events for scheduler decisions (degrade, shed,
COW, OutOfPages requeue, logit-cache hit, prewarm) and backend
compiles (``repro.compile``), and periodic gauge samples — all into
one bounded lock-free ring buffer.  ``tracer.export(path)``
renders the buffer as Chrome trace-event / Perfetto JSON with one
track per backend executor and one per request, so a disaggregated
backend's prefill and decode executors show as separate timelines.

Design constraints, in order:

* **Disabled must be free.**  Hot paths hold a tracer reference and
  guard with ``if tracer.enabled:`` before taking timestamps; the
  :data:`NULL_TRACER` singleton makes every unguarded call a cheap
  no-op.  tests/test_observability.py asserts token-identical
  outputs traced vs untraced — instrumentation only reads clocks and appends to host
  buffers, it never touches RNG state or array shapes.
* **Recording is lock-free.**  Events are plain tuples written into a
  preallocated ring; slot indices come from ``itertools.count()``,
  whose ``next()`` is atomic under the GIL, so executor threads and
  the event loop record concurrently without a lock.  When the ring
  wraps, the oldest events are overwritten (``stats()["dropped"]``
  counts them); ``events()`` reconstructs chronological order.
* **Spans are recorded after the fact.**  ``span(name, track, t0,
  t1)`` takes both endpoints, so there is no per-thread span stack to
  maintain and a span costs one tuple — the caller already holds the
  two timestamps it took for metrics.  ``region(name, track, **args)``
  is the live form for code that holds no timestamps: a context
  manager that takes both on ``tracer.clock`` and also opens a
  ``jax.profiler.TraceAnnotation``, so inside a profiler session the
  span lands in the JAX profiler's trace on the profiler's own clock,
  beside the device operations, with its arguments as stats (outside
  a session the annotation records nothing).  Hot paths pass no
  arguments to ``region`` and compute their counters only under
  ``if tracer.enabled:``, so a disabled region allocates nothing.

The flight recorder is the same buffer viewed backwards:
``flight_recorder_dump(path)`` writes the last N seconds of events,
and ``trip(reason)`` (called by the metrics registry on request
failure / SLO violation) auto-dumps to ``flight_recorder_path`` with
rate limiting, so the trace leading up to a failure survives without
anyone watching.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# Event tuples: (seq, ph, name, track, ts, dur, args)
#   seq   — monotonically increasing record index (ring eviction order)
#   ph    — Chrome trace-event phase: "X" span, "i" instant, "C" counter
#   ts/dur — seconds on the tracer clock (export converts to µs)
#   track — "group/thread" string; export maps groups to pids and
#           threads to tids ("one track per backend executor and one
#           per request")
Event = Tuple[int, str, str, str, float, float, Optional[Dict[str, Any]]]

SPAN = "X"
INSTANT = "i"
COUNTER = "C"

#: default track for process-wide scheduler-decision instants
SCHED_TRACK = "scheduler/decisions"
#: default track group for gauge counter samples
GAUGE_TRACK = "gauges/serving"


def request_track(rid: int) -> str:
    """The per-request track: one thread per request under one
    "requests" process, zero-padded so Perfetto sorts them by rid."""
    return f"requests/req-{rid:05d}"


def backend_track(backend_name: str, executor: str) -> str:
    """The per-backend-executor track: one process per backend, one
    thread per executor (device / prefill / decode / transfer / ...)."""
    return f"backend:{backend_name}/{executor}"


#: the backend-compile event of ``jax.monitoring`` (seconds per XLA
#: compile; a persistent-cache hit does not fire it)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _NullRegion:
    """The region of a disabled tracer: enters, takes arguments and
    exits without reading a clock.  One instance is shared."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_REGION = _NullRegion()


class _Region:
    """One live span: ``tracer.clock`` at enter and exit, recorded in
    the ring at exit, and a ``jax.profiler.TraceAnnotation`` with the
    arguments as stats."""

    __slots__ = ("_tracer", "_name", "_track", "_args", "_t0", "_ann")

    def __init__(self, tracer, name, track, args):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._args = args

    def __enter__(self):
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self._name, **self._args)
        self._ann.__enter__()
        self._t0 = self._tracer.clock()
        return self

    def __exit__(self, *exc):
        t1 = self._tracer.clock()
        self._tracer._record(SPAN, self._name, self._track, self._t0,
                             t1 - self._t0, self._args or None)
        self._ann.__exit__(*exc)
        return False

    def set(self, **args):
        """Add arguments known only inside the region (counters the
        region's own work computes)."""
        self._args.update(args)
        self._ann.set_metadata(**args)


# Every enabled tracer that counts compiles.  jax.monitoring listeners
# are process-wide and cannot be removed, so one listener, registered
# on first use, feeds whichever tracers are alive.
_COMPILE_SINKS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_COMPILE_LOCK = threading.Lock()
_compile_listener_registered = False


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        # snapshot under the lock: another thread may be registering a
        # tracer, and a set that grows while iterated raises (which
        # would fail the jit call that reported the compile)
        with _COMPILE_LOCK:
            sinks = list(_COMPILE_SINKS)
        for tracer in sinks:
            tracer._on_compile(seconds)


def _count_compiles(tracer: "Tracer") -> None:
    global _compile_listener_registered
    with _COMPILE_LOCK:
        _COMPILE_SINKS.add(tracer)
        if not _compile_listener_registered:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _compile_listener_registered = True


class NullTracer:
    """Tracing disabled: every method is a literal no-op and
    ``enabled`` is False so hot paths skip even the timestamp reads.
    Shared as the :data:`NULL_TRACER` singleton."""

    enabled = False
    gauge_interval_s = 0.0
    flight_recorder_path: Optional[str] = None
    host: Optional[str] = None

    def span(self, name, track, t0, t1, args=None):  # pragma: no cover
        pass

    def region(self, name, track, **args):
        return _NULL_REGION

    def instant(self, name, track=SCHED_TRACK, args=None, t=None):
        pass

    def counter(self, name, values, track=GAUGE_TRACK, t=None):
        pass

    def add_consumer(self, fn):
        pass

    def trip(self, reason):
        pass


NULL_TRACER = NullTracer()


class Tracer:
    """Bounded lock-free ring buffer of trace events + exporters."""

    def __init__(self, capacity: int = 1 << 16,
                 clock: Callable[[], float] = time.monotonic,
                 gauge_interval_s: float = 0.05,
                 flight_recorder_path: Optional[str] = None,
                 flight_recorder_window_s: float = 10.0,
                 flight_recorder_min_interval_s: float = 5.0,
                 host: Optional[str] = None):
        if capacity <= 0:
            raise ValueError(f"tracer capacity must be positive: {capacity}")
        self.enabled = True
        self.clock = clock
        # multi-host label: when set, every track group is prefixed
        # "host:group" so traces merged across cluster hosts render as
        # separate Perfetto process tracks instead of colliding on
        # identical group names ("scheduler", "backend:paged", ...)
        self.host = host
        self.capacity = int(capacity)
        self.gauge_interval_s = float(gauge_interval_s)
        self.flight_recorder_path = flight_recorder_path
        self.flight_recorder_window_s = float(flight_recorder_window_s)
        self.flight_recorder_min_interval_s = float(
            flight_recorder_min_interval_s)
        self._buf: List[Optional[Event]] = [None] * self.capacity
        # itertools.count().__next__ is atomic under the GIL: executor
        # threads and the event loop claim distinct slots without a lock
        self._seq = itertools.count()
        self._consumers: List[Callable[[Event], None]] = []
        self.trips = 0                       # trip() calls (rate-limited in)
        self.dumps = 0                       # flight-recorder files written
        self._last_dump_t: Optional[float] = None
        self._compile_lock = threading.Lock()
        self.compiles = 0                    # backend compiles seen
        self.compile_s = 0.0                 # and their seconds
        _count_compiles(self)

    # ---- recording ----------------------------------------------------
    def _record(self, ph: str, name: str, track: str, ts: float,
                dur: float, args: Optional[Dict[str, Any]]) -> None:
        if self.host is not None:
            # prefix the GROUP part: "backend:paged/decode" becomes
            # "hostA:backend:paged/decode" — chrome_trace partitions on
            # the first "/", so each host gets its own pid namespace
            track = f"{self.host}:{track}"
        i = next(self._seq)
        ev: Event = (i, ph, name, track, ts, dur, args)
        self._buf[i % self.capacity] = ev
        for fn in self._consumers:
            fn(ev)

    def span(self, name: str, track: str, t0: float, t1: float,
             args: Optional[Dict[str, Any]] = None) -> None:
        """One complete span [t0, t1) — recorded after the fact, so the
        caller times the operation however it already does."""
        if not self.enabled:
            return
        self._record(SPAN, name, track, t0, t1 - t0, args)

    def region(self, name: str, track: str, **args: Any) -> _Region:
        """A live span around a ``with`` block, on ``self.clock``;
        ``region.set(**args)`` adds arguments computed inside it.  On
        :data:`NULL_TRACER` this is one shared no-op context."""
        if not self.enabled:
            return _NULL_REGION
        return _Region(self, name, track, args)

    def _on_compile(self, seconds: float) -> None:
        if not self.enabled:
            return
        with self._compile_lock:
            self.compiles += 1
            self.compile_s += seconds
        self.instant("repro.compile", args={"seconds": seconds})

    def instant(self, name: str, track: str = SCHED_TRACK,
                args: Optional[Dict[str, Any]] = None,
                t: Optional[float] = None) -> None:
        if not self.enabled:
            return
        self._record(INSTANT, name, track,
                     t if t is not None else self.clock(), 0.0, args)

    def counter(self, name: str, values: Dict[str, Any],
                track: str = GAUGE_TRACK,
                t: Optional[float] = None) -> None:
        """One gauge sample: ``values`` series render as a stacked
        counter track in Perfetto."""
        if not self.enabled:
            return
        self._record(COUNTER, name, track,
                     t if t is not None else self.clock(), 0.0, dict(values))

    def add_consumer(self, fn: Callable[[Event], None]) -> None:
        """Register a synchronous per-event callback (the metrics
        registry consumes instants this way).  Consumers run on the
        recording thread — they must be cheap and must not trace."""
        self._consumers.append(fn)

    # ---- introspection ------------------------------------------------
    def events(self, since: Optional[float] = None) -> List[Event]:
        """Live events in chronological (seq) order; ``since`` keeps
        only events with ``ts >= since`` (flight-recorder windowing).
        Racing writers can at worst tear one in-flight slot — the scan
        copies tuples, never mutates them."""
        evs = [ev for ev in self._buf if ev is not None]
        evs.sort(key=lambda ev: ev[0])
        if since is not None:
            evs = [ev for ev in evs if ev[4] >= since]
        return evs

    def stats(self) -> Dict[str, Any]:
        evs = [ev for ev in self._buf if ev is not None]
        recorded = max(ev[0] for ev in evs) + 1 if evs else 0
        return {
            "capacity": self.capacity,
            "recorded": recorded,
            "dropped": max(0, recorded - self.capacity),
            "consumers": len(self._consumers),
            "trips": self.trips,
            "flight_recorder_dumps": self.dumps,
            "compiles": self.compiles,
            "compile_s": self.compile_s,
        }

    # ---- Chrome trace-event / Perfetto export -------------------------
    def chrome_trace(self, events: Optional[Sequence[Event]] = None,
                     other_data: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
        """Render events as a Chrome trace-event JSON object (the
        format both ``chrome://tracing`` and https://ui.perfetto.dev
        load).  Track strings ``group/thread`` map to one pid per
        group and one tid per thread, with process_name / thread_name
        metadata so the UI shows real names."""
        if events is None:
            events = self.events()
        groups: Dict[str, int] = {}
        threads: Dict[Tuple[str, str], int] = {}
        for ev in events:
            group, _, thread = ev[3].partition("/")
            groups.setdefault(group, 0)
            threads.setdefault((group, thread or "main"), 0)
        for pid, group in enumerate(sorted(groups), start=1):
            groups[group] = pid
        by_group: Dict[str, List[str]] = {}
        for group, thread in threads:
            by_group.setdefault(group, []).append(thread)
        for group, names in by_group.items():
            for tid, thread in enumerate(sorted(names), start=1):
                threads[(group, thread)] = tid
        out: List[Dict[str, Any]] = []
        for group, pid in sorted(groups.items(), key=lambda kv: kv[1]):
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "args": {"name": group}})
            for thread in sorted(by_group[group]):
                out.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": threads[(group, thread)],
                            "args": {"name": thread}})
        for seq, ph, name, track, ts, dur, args in events:
            group, _, thread = track.partition("/")
            rec: Dict[str, Any] = {
                "ph": ph, "name": name, "cat": group,
                "pid": groups[group],
                "tid": threads[(group, thread or "main")],
                "ts": round(ts * 1e6, 3),
            }
            if ph == SPAN:
                rec["dur"] = round(max(dur, 0.0) * 1e6, 3)
            if ph == INSTANT:
                rec["s"] = "t"          # thread-scoped instant marker
            if args is not None:
                rec["args"] = args
            out.append(rec)
        payload: Dict[str, Any] = {"traceEvents": out,
                                   "displayTimeUnit": "ms"}
        if other_data:
            payload["otherData"] = other_data
        return payload

    def export(self, path: str) -> Dict[str, Any]:
        """Write the whole buffer as Chrome trace JSON; returns the
        payload (tests schema-check it without re-reading the file)."""
        payload = self.chrome_trace(
            other_data=({"host": self.host} if self.host is not None
                        else None))
        with open(path, "w") as f:
            json.dump(payload, f)
        return payload

    # ---- flight recorder ----------------------------------------------
    def flight_recorder_dump(self, path: Optional[str] = None,
                             window_s: Optional[float] = None,
                             reason: str = "manual") -> str:
        """Write the last ``window_s`` seconds of events (default: the
        configured window) — the post-mortem view of what the stack
        was doing just before a failure."""
        path = path or self.flight_recorder_path
        if path is None:
            raise ValueError("no path: pass one or set "
                             "Tracer(flight_recorder_path=...)")
        window = (window_s if window_s is not None
                  else self.flight_recorder_window_s)
        now = self.clock()
        other: Dict[str, Any] = {"reason": reason, "window_s": window,
                                 "t_dump": now}
        if self.host is not None:
            other["host"] = self.host
        payload = self.chrome_trace(self.events(since=now - window),
                                    other_data=other)
        with open(path, "w") as f:
            json.dump(payload, f)
        self.dumps += 1
        self._last_dump_t = now
        return path

    def trip(self, reason: str) -> Optional[str]:
        """Auto-dump hook for request failure / SLO violation: writes
        a flight-recorder file when a path is configured, rate-limited
        so a failure storm produces one dump per window, not one per
        request.  No-op (beyond counting) without a configured path."""
        if not self.enabled:
            return None
        self.trips += 1
        if self.flight_recorder_path is None:
            return None
        now = self.clock()
        if (self._last_dump_t is not None and
                now - self._last_dump_t < self.flight_recorder_min_interval_s):
            return None
        return self.flight_recorder_dump(reason=reason)


def validate_chrome_trace(payload: Any) -> List[str]:
    """Schema-check a Chrome trace-event JSON object; returns a list
    of problems (empty = valid).  Checks the envelope, per-event
    required keys, phase-specific fields, and metadata coverage —
    what chrome://tracing / Perfetto actually require to load."""
    problems: List[str] = []
    if not isinstance(payload, dict):
        return [f"payload is {type(payload).__name__}, expected object"]
    evs = payload.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing or non-list traceEvents"]
    named: set = set()
    used: set = set()
    for i, ev in enumerate(evs):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not an object")
            continue
        ph = ev.get("ph")
        for key in ("ph", "name", "pid", "tid"):
            if key not in ev:
                problems.append(f"event {i} ({ph!r}) missing {key!r}")
        if ph == "M":
            if ev.get("name") == "process_name":
                named.add((ev.get("pid"), 0))
            elif ev.get("name") == "thread_name":
                named.add((ev.get("pid"), ev.get("tid")))
            continue
        if "ts" not in ev:
            problems.append(f"event {i} ({ph!r}) missing ts")
        used.add((ev.get("pid"), 0))
        used.add((ev.get("pid"), ev.get("tid")))
        if ph == "X":
            if not isinstance(ev.get("dur"), (int, float)):
                problems.append(f"event {i} (X) missing numeric dur")
            elif ev["dur"] < 0:
                problems.append(f"event {i} (X) negative dur {ev['dur']}")
        elif ph == "C":
            if not isinstance(ev.get("args"), dict):
                problems.append(f"event {i} (C) needs args series dict")
        elif ph != "i":
            problems.append(f"event {i} has unknown phase {ph!r}")
    for pid_tid in sorted(used - named):
        problems.append(f"track pid/tid {pid_tid} has no metadata name")
    return problems

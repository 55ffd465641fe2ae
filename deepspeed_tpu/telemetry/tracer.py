"""Span tracer — nested ``with trace_span("fwd")`` contexts.

Emits Chrome-trace/Perfetto-compatible "X" (complete) events
(``{"name", "ph", "ts", "dur", "pid", "tid", "args"}``, timestamps in
microseconds on ``perf_counter_ns``) into a bounded in-memory list.

A tracer is LIVE when it was enabled explicitly (``telemetry.trace``) or
while a JAX profiler session runs (``jax.profiler.start_trace`` ...
``stop_trace``). While a session runs every span is also a
``jax.profiler.TraceAnnotation``, so it lies in the capture's ``/host:``
plane on the profiler's own clock, beside ``/device:TPU:n``, with its
``args`` as the event's stats; outside one the annotation is a no-op by
itself.

The hot path is the one that is not live: ``trace_span`` then costs one
``TraceAnnotation.is_enabled()`` call and returns one shared no-op context
manager — no allocation, no clock read (tests/perf/telemetry_overhead.py
asserts < 2 µs/span). A live span costs two ``perf_counter_ns`` reads, the
annotation and one locked list append.

Python's cyclic collector stops the host loop whole, and no span of the
loop can say so: a pause inside ``serving_decode_wait`` looks like the
wait. So one process-wide ``gc.callbacks`` hook (:func:`watch_gc`) names
each collection after the host loop that registered last (``serving``,
``train``). While the tracer is live a collection is a span
``<loop>_gc`` (``generation``; ``collected``, set at its end), opened at
the collection's start and closed at its stop on the collecting thread,
so it nests under whatever span was open. Always, it books
``<loop>_gc_collections_total{generation}``,
``<loop>_gc_seconds_total{generation}`` and the gauge
``<loop>_gc_pause_max_seconds`` in the loop's registry: two clock reads,
one liveness check and the counter updates when the tracer is not live.
"""

import gc
import json
import os
import threading
import time
import weakref

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Shared no-op context manager for the disabled tracer."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args):
        """Add to an open span's args what its work has shown since."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


class Tracer:
    """Collects spans into a bounded in-memory buffer; ``export`` writes
    the Chrome-trace JSON (loadable in chrome://tracing / Perfetto)."""

    def __init__(self, enabled=False, max_events=100_000):
        self.enabled = enabled
        self.max_events = max_events
        self.dropped = 0
        self._events = []
        # re-entrant: a collection can start inside ``events()``'s copy
        # and record its span on the same thread before the copy ends
        self._lock = threading.RLock()
        self._pid = os.getpid()
        self._process_label = None
        self._process_sort = None

    def set_process_label(self, name, sort_index=None):
        """Rank-tag this process's trace: ``export`` will prepend
        ``process_name`` / ``process_sort_index`` metadata, so per-rank
        trace files carry their identity and concatenate cleanly into
        one per-rank-lane view (telemetry/fleet.py's ``merge_traces``)."""
        self._process_label = str(name)
        self._process_sort = sort_index

    @property
    def live(self):
        """Enabled explicitly, or a JAX profiler session is running: the
        one predicate ``span``, ``emit`` and ``instant`` ask."""
        return self.enabled or TraceAnnotation.is_enabled()

    def span(self, name, **args):
        if not self.live:
            return _NULL_SPAN
        return _Span(self, name, args)

    def _record(self, name, t0_ns, t1_ns, args):
        ev = {"name": name, "ph": "X", "ts": t0_ns // 1000,
              "dur": max(0, (t1_ns - t0_ns) // 1000),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def emit(self, event):
        """Append a pre-built Chrome-trace event dict verbatim. The
        serving observatory synthesizes per-slot lane events with its
        own pid/tid (and "M" metadata naming the lanes) — those cannot
        go through span()/instant(), which stamp the CURRENT thread."""
        if not self.live:
            return
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(event)

    def instant(self, name, **args):
        """Zero-duration marker event (ph="i")."""
        if not self.live:
            return
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": time.perf_counter_ns() // 1000,
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def events(self):
        with self._lock:
            return list(self._events)

    def event_count(self):
        return len(self._events)   # len() is atomic; no copy needed

    def clear(self):
        with self._lock:
            self._events.clear()
        self.dropped = 0

    def export(self, path):
        """Write the Chrome-trace JSON object format; returns the path."""
        events = self.events()
        if self._process_label is not None:
            meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                     "args": {"name": self._process_label}}]
            if self._process_sort is not None:
                meta.append({"name": "process_sort_index", "ph": "M",
                             "pid": self._pid,
                             "args": {"sort_index":
                                      int(self._process_sort)}})
            events = meta + events
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if self.dropped:
            doc["metadata"] = {"dropped_events": self.dropped}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)   # readers never see a half-written trace
        return path


# ---------------------------------------------------------------- lane tids
#
# Synthetic trace lanes (serving slots, fleet ranks, profiler device
# lanes) need tids that cannot collide with real thread idents or with
# each other — two subsystems both hard-coding "base + index" produced
# duplicate (pid, tid) pairs with conflicting thread_name metadata in
# merged traces. One process-scoped registry hands out a stable tid per
# lane key instead: the same key always maps to the same tid, distinct
# keys never share one.

_LANE_TID_BASE = 1_000_000
_LANE_LOCK = threading.Lock()
_LANE_TIDS = {}
_LANE_NEXT = [_LANE_TID_BASE]


def allocate_lane_tid(key):
    """Return the process-unique synthetic tid for lane *key* (any
    hashable; idempotent — repeated calls with the same key return the
    same tid)."""
    with _LANE_LOCK:
        tid = _LANE_TIDS.get(key)
        if tid is None:
            tid = _LANE_NEXT[0]
            _LANE_NEXT[0] += 1
            _LANE_TIDS[key] = tid
        return tid


def _reset_lane_tids():
    """Test hook: forget all lane-tid assignments."""
    with _LANE_LOCK:
        _LANE_TIDS.clear()
        _LANE_NEXT[0] = _LANE_TID_BASE


# Module-level default tracer: not enabled until a TelemetryManager (or a
# test) installs an enabled one, and live all the same while a profiler
# session runs. Library code (engine, server, checkpoint_io) calls
# ``trace_span`` unconditionally; the cost with neither is one global
# lookup, one ``is_enabled()`` + a shared no-op context manager.
_GLOBAL = Tracer(enabled=False)


def get_tracer():
    return _GLOBAL


def set_tracer(tracer):
    """Install *tracer* as the process-global default; returns the old."""
    global _GLOBAL
    old, _GLOBAL = _GLOBAL, tracer
    return old


def trace_span(name, **args):
    return _GLOBAL.span(name, **args)


# ---------------------------------------------------------------- GC pauses
class GCLoop:
    """One host loop's registration with the collection hook (see
    :func:`watch_gc`); ``close`` ends it, and is idempotent."""

    __slots__ = ("span_name", "_collections", "_seconds", "_pause_max")

    def __init__(self, loop, registry):
        self.span_name = f"{loop}_gc"
        gens = [{"generation": str(g)} for g in range(3)]
        self._collections = [registry.counter(
            f"{loop}_gc_collections_total",
            "Python garbage collections while this loop owned the "
            "process", labels=g) for g in gens]
        self._seconds = [registry.counter(
            f"{loop}_gc_seconds_total",
            "seconds the host loop stood in garbage collection",
            labels=g) for g in gens]
        self._pause_max = registry.gauge(
            f"{loop}_gc_pause_max_seconds",
            "the longest garbage collection so far")

    def book(self, generation, seconds):
        self._collections[generation].inc()
        self._seconds[generation].inc(seconds)
        if seconds > self._pause_max.value:
            self._pause_max.set(seconds)

    def close(self):
        _GC_WATCH.unregister(self)


class _GCWatch:
    """The ``gc.callbacks`` hook. Collections do not nest (the collector
    runs one at a time), so one open collection is all the state."""

    def __init__(self):
        self._loops = []        # registrations; the newest owns the process
        self._owner = None      # the loop of the collection under way
        self._span = None
        self._t0 = 0

    def register(self, loop, registry, owner):
        if self not in gc.callbacks:
            # first, so that the pause it times holds the other hooks'
            # work at both ends (jax's own runs at start and stop)
            gc.callbacks.insert(0, self)
        handle = GCLoop(loop, registry)
        self._loops.append(handle)
        # an owner dropped without close() takes its registration with it
        weakref.finalize(owner, self.unregister, handle)
        return handle

    def unregister(self, handle):
        if handle in self._loops:
            self._loops.remove(handle)

    def __call__(self, phase, info):
        if phase == "start":
            if self._owner is not None or not self._loops:
                return
            self._owner = self._loops[-1]
            if _GLOBAL.live:
                self._span = _Span(_GLOBAL, self._owner.span_name,
                                   {"generation": info["generation"]})
                self._span.__enter__()
            self._t0 = time.perf_counter_ns()
            return
        if self._owner is None:
            return
        seconds = (time.perf_counter_ns() - self._t0) * 1e-9
        owner, self._owner = self._owner, None
        owner.book(info["generation"], seconds)
        span, self._span = self._span, None
        if span is not None:
            span.set(collected=info["collected"])
            span.__exit__(None, None, None)


_GC_WATCH = _GCWatch()


def watch_gc(loop, registry, owner):
    """Register the host loop ``loop`` (``serving``, ``train``) of
    ``owner`` with the process's one collection hook, installing the hook
    on first use: until the returned handle is closed, or ``owner`` is
    collected, each collection is booked in ``registry`` under
    ``<loop>_gc_*`` and, while the tracer is live, recorded as a span
    ``<loop>_gc``. Of several loops registered, the newest owns the
    collections."""
    return _GC_WATCH.register(loop, registry, owner)

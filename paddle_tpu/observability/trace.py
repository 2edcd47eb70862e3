"""Typed event tracing for the program's host code (OBSERVABILITY.md).

One ``Tracer`` records a flat stream of timestamped events on named
*tracks* — the engine's per-step phases on the ``engine`` track, each
request's lifecycle on its own ``rid`` track, the KV pool on ``pool``,
``jit.TrainStep``'s phases on ``train`` — and renders it as Chrome
trace-event JSON (``dump_chrome_trace``), loadable in Perfetto /
``chrome://tracing`` with one row per track.

Event vocabulary (mirrors the Chrome ``ph`` phases):
- ``span(name, ...)``     — a scoped duration (``ph="X"``, carries dur):
                            the per-step engine phases;
- ``begin/end(name, ...)`` — an open duration (``ph="B"/"E"``): request
                            lifecycle phases that open and close in
                            different engine calls (queued, decode);
- ``instant(name, ...)``  — a point event (``ph="i"``): admit, preempt,
                            finish, compile, eviction;
- ``bump(name)``          — a named counter (``ph="C"``): compiles,
                            preempts — Perfetto draws these as a graph.

Every event carries ``parent`` (the name of the span that encloses it
on its thread, a stack the tracer keeps) and ``step`` (the ``step``
argument of the nearest enclosing span that was given one: the engine's
or TrainStep's step index); both are ``None`` outside any span.

While a JAX profiler session is on, ``span`` also enters a
``jax.profiler.TraceAnnotation`` named ``serve.<name>`` (``train.<name>``
on the ``train`` track), so the program's spans lie in the profiler's
own trace (``/host:CPU``, the Python thread's line), on the time axis of
the device's ``XLA Ops``. ``profiler_annotation`` is the one helper that
enters it; ``paddle_tpu.profiler.RecordEvent`` goes through it too.

``PROFILE_TRACER`` is the process-wide tracer that ``ServingEngine``
(built without a tracer) and ``jit.TrainStep`` hold: it is enabled
exactly while a profiler session is on (``jax.profiler.start_trace``,
``paddle_tpu.profiler.Profiler``, a capture from a live server) and
costs one ``TraceAnnotation.is_enabled()`` call per hook otherwise. Its
clock is ``time.perf_counter``; its buffer is a ring (the oldest events
fall off) and its counters run on from session to session, so a reader
takes a window's events by their ``ts`` and a counter's growth over it
from the counter events' increments ``n``. A ``Tracer`` built by hand
is on from construction, keeps every event, and takes an injected
clock (share it with ``ServingMetrics`` so spans and latency
percentiles are in the same timebase); timestamps are stored in clock
seconds and scaled to the microseconds Chrome expects at dump time.

Tracing must cost nothing when off: every recording method checks
``self.enabled`` first and returns immediately (``span`` returns a
shared null context manager — no allocation), and the module-level
``NULL_TRACER`` singleton is what a scheduler or pool built without an
engine holds. Sinks (``add_sink``) observe every recorded event — the
``FlightRecorder`` ring buffer subscribes this way.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

__all__ = ["Tracer", "NULL_TRACER", "PROFILE_TRACER", "profiler_annotation"]

# is a profiler session recording host events right now (a static call
# into the profiler's C++ side: tens of nanoseconds)
_session_on = _TraceAnnotation.is_enabled

# events the process-wide tracer keeps (the oldest fall off): a traced
# minute of serving is some tens of thousands
PROFILE_RING_EVENTS = 1 << 18


class _NullCtx:
    """Shared no-op context manager returned by a disabled tracer's
    ``span`` — entering/exiting records nothing and allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


def profiler_annotation(name: str, **stats):
    """The program's one way into the profiler's trace: a
    ``jax.profiler.TraceAnnotation`` (``stats`` become the event's
    stats) while a session is on, the shared null context otherwise."""
    if _session_on():
        return _TraceAnnotation(name, **stats)
    return _NULL_CTX


def _annotation_name(name: str, track: str) -> str:
    return ("train." if track == "train" else "serve.") + name


class _Span:
    """Scoped-duration recorder: one complete ``ph="X"`` event on exit.
    ``args`` may be added to until then."""

    __slots__ = ("_tracer", "name", "track", "args", "parent", "step",
                 "_t0", "_ann")

    def __init__(self, tracer, name, track, args):
        self._tracer = tracer
        self.name = name
        self.track = track
        self.args = args

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        top = stack[-1] if stack else None
        self.parent = top.name if top is not None else None
        self.step = self.args.get(
            "step", top.step if top is not None else None)
        stack.append(self)
        self._ann = profiler_annotation(
            _annotation_name(self.name, self.track), **self.args)
        self._ann.__enter__()
        self._t0 = tr.now()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        t1 = tr.now()
        self._ann.__exit__(*exc)
        tr._stack().pop()
        tr._emit({"name": self.name, "ph": "X", "ts": self._t0,
                  "dur": t1 - self._t0, "track": self.track,
                  "args": self.args, "parent": self.parent,
                  "step": self.step})
        return False


class Tracer:
    def __init__(self, clock=None, enabled: bool = True,
                 follow_profiler: bool = False, capacity: int | None = None):
        self._enabled = enabled
        # follow_profiler: enabled exactly while a profiler session is on
        # (PROFILE_TRACER); capacity: keep only the last so many events
        self._follow = follow_profiler
        self._clock = clock if clock is not None else time.monotonic
        self.events = ([] if capacity is None
                       else collections.deque(maxlen=capacity))
        self.counters: dict[str, int] = {}
        self._sinks: list = []
        self._local = threading.local()

    @property
    def enabled(self) -> bool:
        return _session_on() if self._follow else self._enabled

    def _stack(self) -> list:
        """The open spans of the calling thread, outermost first."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _point(self, name, ph, track, args) -> dict:
        stack = self._stack()
        top = stack[-1] if stack else None
        return {"name": name, "ph": ph, "ts": self.now(), "track": track,
                "args": args,
                "parent": top.name if top is not None else None,
                "step": top.step if top is not None else None}

    def now(self) -> float:
        return self._clock()

    def add_sink(self, fn) -> None:
        """Subscribe ``fn(event_dict)`` to every recorded event (the
        FlightRecorder ring buffer attaches here). Idempotent — the
        engine re-attaches its recorder without double-recording."""
        if fn not in self._sinks:
            self._sinks.append(fn)

    # ---- recording ----

    def _emit(self, ev: dict) -> None:
        self.events.append(ev)
        for fn in self._sinks:
            fn(ev)

    def span(self, name: str, track: str = "engine", **args):
        """Scoped duration: ``with tracer.span("decode_dispatch"): ...``
        records one complete event with its measured dur; spans opened
        inside it (same thread) name it as their ``parent`` and inherit
        its ``step`` argument."""
        if not self.enabled:
            return _NULL_CTX
        return _Span(self, name, track, args)

    def begin(self, name: str, track: str = "engine", **args) -> None:
        """Open a duration that closes in a later call (``end``)."""
        if not self.enabled:
            return
        self._emit(self._point(name, "B", track, args))

    def end(self, name: str, track: str = "engine", **args) -> None:
        if not self.enabled:
            return
        self._emit(self._point(name, "E", track, args))

    def instant(self, name: str, track: str = "engine", **args) -> None:
        if not self.enabled:
            return
        self._emit(self._point(name, "i", track, args))

    def bump(self, name: str, n: int = 1, track: str = "engine") -> None:
        """Increment a named counter and record its new value as a
        Chrome counter event (Perfetto draws a step graph)."""
        if not self.enabled:
            return
        value = self.counters.get(name, 0) + n
        self.counters[name] = value
        ev = self._point(name, "C", track, {name: value})
        ev["n"] = n
        self._emit(ev)

    # ---- export ----

    def chrome_trace(self) -> dict:
        """The event stream as a Chrome trace-event JSON object: every
        track becomes a thread (tid) of one process, requests therefore
        render as parallel rows; ``thread_name`` metadata labels them."""
        out = [{"name": "process_name", "ph": "M", "ts": 0, "pid": 0,
                "tid": 0, "args": {"name": "paddle_tpu.serving"}}]
        events = list(self.events)
        # track name -> tid, in order of first appearance; "engine" is
        # row 0
        tracks: dict[str, int] = {"engine": 0}
        for ev in events:
            tracks.setdefault(ev["track"], len(tracks))
        for track, tid in tracks.items():
            out.append({"name": "thread_name", "ph": "M", "ts": 0,
                        "pid": 0, "tid": tid,
                        "args": {"name": track}})
            out.append({"name": "thread_sort_index", "ph": "M", "ts": 0,
                        "pid": 0, "tid": tid, "args": {"sort_index": tid}})
        for ev in events:
            ce = {"name": ev["name"], "ph": ev["ph"],
                  "ts": ev["ts"] * 1e6, "pid": 0,
                  "tid": tracks[ev["track"]],
                  "args": ev.get("args") or {}}
            if ev["ph"] == "X":
                ce["dur"] = ev["dur"] * 1e6
            if ev["ph"] == "i":
                ce["s"] = "t"  # thread-scoped instant
            out.append(ce)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def dump_chrome_trace(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` (atomic) and return
        the path — load it at https://ui.perfetto.dev."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path


# what a scheduler or pool built without an engine holds: every method
# returns before touching state
NULL_TRACER = Tracer(enabled=False)

# what ServingEngine (no tracer passed) and jit.TrainStep hold: on while a
# profiler session is on, and only then
PROFILE_TRACER = Tracer(clock=time.perf_counter, follow_profiler=True,
                        capacity=PROFILE_RING_EVENTS)

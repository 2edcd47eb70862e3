"""What the program records of a request's first token and of the
host's garbage collections, as the per-layer metrics read them (built
on ``program_spans.window_events``; the arithmetic is here).

- ``first_token``: a point (``ph`` ``i``) on the request's own track,
  once per request, at the step that emits its first token. Its
  arguments: ``steps`` (engine steps from its first admission through
  this one), ``chunks`` (steps that ran a chunk of its prompt),
  ``waits`` (steps in which it was prefilling and the plan left it
  out), ``preemptions`` and ``cached`` (prompt tokens the prefix cache
  served).
- ``gc``: a span on the ``host`` track for each Python collection.

A program without them (the parent of the PR that added them) gives no
such events, and every reader returns ``None``.
"""

from __future__ import annotations

import gc

from benchmarks import program_spans


def first_tokens(ctx: dict) -> list[dict]:
    """The ``first_token`` points inside the window."""
    return [e for e in program_spans.window_events(ctx)
            if e["ph"] == "i" and e["name"] == "first_token"]


def engine_ttfts(ctx: dict) -> list[float]:
    """Seconds from a request's first ``queued`` begin to its
    ``first_token`` point, both on the program's clock (which is the
    harness's: ``time.perf_counter``), over the requests whose first
    token lies inside the window. A request queued before the window
    opened (and with it the profiler session) is left out. A ``finish``
    closes a request's track, so that a track name used again starts
    afresh."""
    queued_at: dict = {}
    out = []
    for e in program_spans.window_events(ctx):
        name, track = e["name"], e["track"]
        if name == "queued" and e["ph"] == "B":
            queued_at.setdefault(track, e["ts"])
        elif name == "first_token":
            t0 = queued_at.pop(track, None)
            if t0 is not None:
                out.append(e["ts"] - t0)
        elif name == "finish":
            queued_at.pop(track, None)
    return out


def times_collections() -> bool:
    """Does the program time the process's garbage collections (its
    tracer holds a ``gc.callbacks`` entry)?"""
    try:
        from paddle_tpu.observability import PROFILE_TRACER
    except ImportError:
        return False
    return any(getattr(cb, "__self__", None) is PROFILE_TRACER
               for cb in gc.callbacks)


def gc_seconds_per_step(ctx: dict) -> float | None:
    """Seconds of ``gc`` spans inside the window over its engine
    ``step`` spans (a collection inside a step or between two counts
    alike)."""
    events = program_spans.window_events(ctx)
    steps = program_spans.spans(events, "step", "engine")
    if not steps or not times_collections():
        return None
    collections = program_spans.spans(events, "gc", "host")
    return sum(e["dur"] for e in collections) / len(steps)

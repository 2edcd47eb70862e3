"""The program's own spans and counters, as the per-layer metrics read
them (``paddle_tpu.observability.PROFILE_TRACER``: on while the traced
run's profiler session is on). Only raw events and counter increments
are taken from the program; the arithmetic is here.

An event is a dict: ``name``, ``ph`` (``X`` a span with ``dur``, ``B`` /
``E`` an open duration's two ends, ``i`` a point, ``C`` a counter with
its increment ``n``), ``ts`` in seconds on ``time.perf_counter`` (the
harness's own clock), ``track`` (``engine``, ``train``, ``pool`` or a
request's id), ``parent`` (the enclosing span's name) and ``step`` (the
engine's or TrainStep's step index). A program without the tracer (the
parent of the PR that added it) gives no events, and every reader
returns ``None``.
"""

from __future__ import annotations

# the engine's step phases, grouped as the three phase metrics group them
SCHEDULE = ("deadline_sweep", "brownout", "admission", "draft",
            "ensure_pages", "plan")
DISPATCH = ("build_inputs", "decode_dispatch", "mixed_dispatch")
EMIT = ("sample_emit", "bookkeeping")
SYNC = "device_sync"


def window_events(ctx: dict) -> list[dict]:
    """What the program recorded between the opening of the window and
    the end of its last step."""
    try:
        from paddle_tpu.observability import PROFILE_TRACER
    except ImportError:
        return []
    rec = ctx["record"]
    t0, t1 = rec["t_open"], rec["t_last"]
    return [e for e in PROFILE_TRACER.events if t0 <= e["ts"] <= t1]


def spans(events, name: str, track: str) -> list[dict]:
    return [e for e in events if e["ph"] == "X" and e["name"] == name
            and e["track"] == track]


def children(events, parent: dict) -> list[dict]:
    """The spans that ``parent`` directly encloses: they name it, carry
    its step index and lie inside it."""
    t0, t1 = parent["ts"], parent["ts"] + parent["dur"]
    return [e for e in events if e["ph"] == "X" and e is not parent
            and e["parent"] == parent["name"] and e["step"] == parent["step"]
            and t0 <= e["ts"] and e["ts"] + e["dur"] <= t1]


def covered(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_time(parent: dict, kids) -> float:
    """A span's duration minus what its child spans cover of it."""
    return parent["dur"] - covered(
        (k["ts"], k["ts"] + k["dur"]) for k in kids)


def engine_step_phases(ctx: dict) -> dict | None:
    """Mean seconds per engine step of the traced window: ``step`` (the
    parent span), each child by name, and ``self`` (the parent's self
    time). ``None`` where the program recorded no step."""
    events = window_events(ctx)
    steps = spans(events, "step", "engine")
    if not steps:
        return None
    by_step: dict = {}
    for e in events:
        if e["ph"] == "X" and e["parent"] == "step":
            by_step.setdefault(e["step"], []).append(e)
    totals = {"step": 0.0, "self": 0.0}
    for st in steps:
        kids = children(by_step.get(st["step"], ()), st)
        totals["step"] += st["dur"]
        totals["self"] += self_time(st, kids)
        for k in kids:
            totals[k["name"]] = totals.get(k["name"], 0.0) + k["dur"]
    return {name: t / len(steps) for name, t in totals.items()}


def phase_group_ms(ctx: dict, names) -> float | None:
    """Mean milliseconds per engine step spent in the named phases."""
    phases = engine_step_phases(ctx)
    if phases is None:
        return None
    return 1e3 * sum(phases.get(n, 0.0) for n in names)


def counter_growth(ctx: dict, name: str) -> int:
    """By how much a counter of the program grew inside the window."""
    return sum(e["n"] for e in window_events(ctx)
               if e["ph"] == "C" and e["name"] == name)


def queued_waits(ctx: dict) -> list[float]:
    """Seconds from ``queued`` begin to ``queued`` end on each request's
    track, of the requests admitted inside the window."""
    open_at: dict = {}
    waits = []
    for e in window_events(ctx):
        if e["name"] != "queued":
            continue
        if e["ph"] == "B":
            open_at[e["track"]] = e["ts"]
        elif e["ph"] == "E" and e["track"] in open_at:
            waits.append(e["ts"] - open_at.pop(e["track"]))
    return waits

"""Flight recorder: a bounded ring buffer over the trace-event stream,
dumped to rank-annotated JSON at the moment something dies.

PR 4 proved the value of structured state-at-death — but
``SchedulerStalledError.snapshot``, the watchdog post-mortem and the
chaos histograms each invented their own format. The recorder unifies
them: it subscribes to a :class:`~..trace.Tracer` (``add_sink``), keeps
the last ``capacity`` events, and ``dump()`` writes ONE schema
(``paddle_tpu.flight_recorder/v1``) wherever the engine hits a terminal
condition — scheduler stall, nonfinite quarantine, drain, comm-watchdog
timeout. The stall→drain playbook then points at a file, not a stack
trace.

Dump destination: explicit ``path`` > ``dump_dir`` (constructor) >
``$PADDLE_FLIGHT_DIR`` > cwd; the filename carries the rank and the
dump reason (``flight_recorder.rank0.scheduler_stalled.json``). Writes
are atomic (tmp + rename), same discipline as the checkpoint layer.
"""

from __future__ import annotations

import collections
import json
import os
import time

__all__ = ["FlightRecorder"]

SCHEMA = "paddle_tpu.flight_recorder/v1"


def _rank() -> str:
    return (os.environ.get("PADDLE_TRAINER_ID")
            or os.environ.get("PROCESS_ID", "0"))


class FlightRecorder:
    def __init__(self, capacity: int = 2048, tracer=None,
                 dump_dir: str | None = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.dump_dir = dump_dir
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self.last_dump_path: str | None = None
        self.dumps = 0
        if tracer is not None:
            tracer.add_sink(self.record)

    def record(self, event: dict) -> None:
        """Sink for the tracer's event stream (oldest events fall off)."""
        self._ring.append(event)

    def __len__(self) -> int:
        return len(self._ring)

    def events(self) -> list[dict]:
        return list(self._ring)

    def histogram(self) -> dict[str, int]:
        """Event-name counts over the ring — the one-line summary at the
        head of a post-mortem (OBSERVABILITY.md "Flight recorder")."""
        h: collections.Counter = collections.Counter(
            ev["name"] for ev in self._ring)
        return dict(sorted(h.items(), key=lambda kv: (-kv[1], kv[0])))

    def dump(self, reason: str, snapshot: dict | None = None,
             path: str | None = None) -> str:
        """Write the ring (plus the caller's state ``snapshot``) as
        rank-annotated JSON and return the file path."""
        rank = _rank()
        if path is None:
            d = (self.dump_dir or os.environ.get("PADDLE_FLIGHT_DIR")
                 or ".")
            os.makedirs(d, exist_ok=True)
            safe = "".join(c if c.isalnum() or c in "-_" else "_"
                           for c in reason)
            path = os.path.join(
                d, f"flight_recorder.rank{rank}.{safe}.json")
        payload = {
            "schema": SCHEMA,
            "rank": int(rank) if rank.isdigit() else rank,
            "reason": reason,
            "dumped_at": time.time(),
            "capacity": self.capacity,
            "n_events": len(self._ring),
            "histogram": self.histogram(),
            "snapshot": dict(snapshot or {}),
            "events": list(self._ring),
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self.last_dump_path = path
        self.dumps += 1
        return path

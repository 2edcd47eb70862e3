"""Profiler (parity: python/paddle/profiler/ — Profiler profiler.py:346
with scheduler windows, RecordEvent, summary statistics, timer throughput
meter).

TPU-native: jax.profiler produces XPlane traces viewable in TensorBoard /
Perfetto (replacing the CUPTI → chrome-trace pipeline, SURVEY §5.1);
RecordEvent maps to jax.profiler.TraceAnnotation + named_scope so
annotations appear inside the device trace. The scheduler-window state
machine (CLOSED → READY → RECORD → repeat) and the host-side event
statistics table are framework-level, implemented here.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import defaultdict
from enum import Enum
from typing import Iterable

import jax

from ..observability.trace import profiler_annotation

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "benchmark", "Timer",
           "load_profiler_result"]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    XPU = "xpu"
    TPU = "tpu"
    CUSTOM_DEVICE = "custom"


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


# host-side event aggregation (the profiler_statistic.py analogue)
_EVENT_STATS: dict[str, list[float]] = defaultdict(list)
_STATS_LOCK = threading.Lock()
_COLLECTING = [False]


class RecordEvent:
    """Annotation context (parity: paddle.profiler.RecordEvent →
    platform/profiler/event_tracing.h:43). Inside a device trace the name
    shows up via TraceAnnotation/named_scope; host-side wall time feeds the
    Profiler.summary() statistics table."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ns = jax.named_scope(name)
        self._t0 = None

    def __enter__(self):
        # the same way into the profiler's trace as observability.Tracer
        self._ta = profiler_annotation(self.name)
        self._ta.__enter__()
        self._ns.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is not None and _COLLECTING[0]:
            with _STATS_LOCK:
                _EVENT_STATS[self.name].append(time.perf_counter() - self._t0)
        self._ns.__exit__(*exc)
        self._ta.__exit__(*exc)
        return False

    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)


def make_scheduler(*, closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0):
    """Window scheduler (parity: profiler.py make_scheduler): per step
    returns CLOSED/READY/RECORD/RECORD_AND_RETURN, cycling
    [closed, ready, record] ``repeat`` times (0 = forever) after
    ``skip_first`` steps."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = s // period
        if repeat and cycle >= repeat:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name=None):
    """on_trace_ready handler (parity name): the XPlane trace is already in
    dir_name; the handler records where it went."""

    def handler(prof):
        prof.trace_dirs.append(dir_name)

    return handler


def load_profiler_result(path: str):
    """The XPlane/TensorBoard trace directory listing (the reference loads
    its own protobuf; the TPU trace is consumed by TensorBoard)."""
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


class Profiler:
    """Parity: paddle.profiler.Profiler — scheduler-windowed tracing plus
    step timing and an event statistics summary."""

    def __init__(self, targets: Iterable[str] | None = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False,
                 profile_memory=False, timer_only=False,
                 log_dir: str = "./profiler_log"):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self.on_trace_ready = on_trace_ready
        if isinstance(scheduler, tuple):
            start, stop = scheduler
            scheduler = make_scheduler(closed=start, ready=0,
                                       record=stop - start, repeat=1)
        self.scheduler = scheduler
        self.trace_dirs: list[str] = []
        self._tracing = False
        self._window_closing = False
        self._step_num = 0
        self._step_times: list[float] = []
        self._t0 = None

    # ---- trace control ----

    def _set_tracing(self, on: bool):
        if self.timer_only:
            return
        if on and not self._tracing:
            jax.profiler.start_trace(self.log_dir)
            self._tracing = True
        elif not on and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def start(self):
        _COLLECTING[0] = True
        with _STATS_LOCK:
            _EVENT_STATS.clear()
        if self.scheduler is None:
            self._set_tracing(True)
        else:
            self._apply_state(self.scheduler(self._step_num))
        self._t0 = time.perf_counter()
        return self

    def _apply_state(self, state: ProfilerState):
        if state == ProfilerState.RECORD_AND_RETURN:
            # last recording step of the window: keep tracing ON for the
            # step itself; the handler fires on the NEXT transition (below)
            self._set_tracing(True)
            self._window_closing = True
            return
        was_closing = getattr(self, "_window_closing", False)
        self._set_tracing(state in (ProfilerState.RECORD,))
        if was_closing:
            # trace flushed by the stop above — now the handler can read it
            self._window_closing = False
            if self.on_trace_ready:
                self.on_trace_ready(self)

    def stop(self):
        was_active = self._tracing or getattr(self, "_window_closing", False)
        self._set_tracing(False)
        _COLLECTING[0] = False
        self._window_closing = False
        if self.on_trace_ready and was_active:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t0 is not None:
            self._step_times.append(now - self._t0)
        self._t0 = now
        self._step_num += 1
        if self.scheduler is not None:
            self._apply_state(self.scheduler(self._step_num))

    def step_info(self, unit="samples"):
        if not self._step_times:
            return ""
        avg = sum(self._step_times[-10:]) / len(self._step_times[-10:])
        return f"avg step {avg * 1000:.2f} ms"

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ---- statistics (profiler_statistic.py analogue) ----

    def event_stats(self) -> dict[str, dict]:
        with _STATS_LOCK:
            return {
                name: {"calls": len(ts), "total_ms": sum(ts) * 1e3,
                       "avg_ms": sum(ts) / len(ts) * 1e3,
                       "max_ms": max(ts) * 1e3, "min_ms": min(ts) * 1e3}
                for name, ts in _EVENT_STATS.items() if ts
            }

    def summary(self, sorted_by="total_ms", op_detail=True, thread_sep=False,
                time_unit="ms"):
        stats = self.event_stats()
        lines = []
        if self._step_times:
            lines.append(self.step_info())
        if stats:
            width = max(len(n) for n in stats) + 2
            lines.append(f"{'Event':<{width}}{'Calls':>7}{'Total(ms)':>12}"
                         f"{'Avg(ms)':>10}{'Max(ms)':>10}")
            for name, s in sorted(stats.items(),
                                  key=lambda kv: -kv[1][sorted_by]):
                lines.append(f"{name:<{width}}{s['calls']:>7}"
                             f"{s['total_ms']:>12.3f}{s['avg_ms']:>10.3f}"
                             f"{s['max_ms']:>10.3f}")
        out = "\n".join(lines)
        print(out)
        return out


class Timer:
    """Throughput meter (parity: paddle.profiler.timer ips benchmark)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.count = 0
        self.elapsed = 0.0
        self._t = None

    def begin(self):
        self._t = time.perf_counter()

    def end(self, samples: int = 1):
        if self._t is not None:
            self.elapsed += time.perf_counter() - self._t
        self.count += samples

    def ips(self):
        return self.count / self.elapsed if self.elapsed else 0.0


def benchmark():
    return Timer()

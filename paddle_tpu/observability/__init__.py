"""paddle_tpu.observability — tracing, flight recording, SLO export.

The observability spine of the serving engine and of ``jit.TrainStep``
(OBSERVABILITY.md):

- :class:`Tracer` (trace.py): typed spans/events on per-request,
  per-engine-step and train-step tracks, each with its parent span and
  step index, Chrome trace-event JSON export (Perfetto-loadable),
  compile/retrace counters. ``PROFILE_TRACER`` is what the engine and
  TrainStep hold by default: on exactly while a JAX profiler session
  is on, its spans then also written into the profiler's own trace
  (``serve.<name>`` / ``train.<name>``); otherwise a strict no-op on
  the hot path.
- :class:`FlightRecorder` (recorder.py): bounded ring buffer over the
  event stream, auto-dumped to rank-annotated JSON by the engine on
  scheduler stall, nonfinite quarantine, drain and watchdog timeout.
- :func:`render_prometheus` / :class:`MetricsServer` /
  :func:`goodput_at_slo` (export.py): Prometheus text exposition of
  metrics + pool + trace counters, an optional ``/metrics`` +
  ``/healthz`` endpoint, and goodput-under-SLO — the metric that ranks
  schedulers and cache tiers (ROADMAP item 5).

    from paddle_tpu.observability import Tracer
    tr = Tracer()
    eng = ServingEngine(model, ..., tracer=tr)
    ...
    tr.dump_chrome_trace("serve.trace.json")   # open in Perfetto
"""

from .export import (MetricsServer, goodput_at_slo, parse_prometheus,
                     render_fleet_prometheus, render_prometheus)
from .recorder import FlightRecorder
from .trace import NULL_TRACER, PROFILE_TRACER, Tracer, profiler_annotation

__all__ = ["Tracer", "NULL_TRACER", "PROFILE_TRACER", "profiler_annotation",
           "FlightRecorder",
           "render_prometheus", "render_fleet_prometheus",
           "parse_prometheus", "MetricsServer", "goodput_at_slo"]

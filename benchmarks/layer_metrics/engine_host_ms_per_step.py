"""Mean milliseconds of host work per engine step of the traced window:
the program's ``step`` span minus its ``device_sync`` child (the wait
for the device). The three phase metrics (``engine_schedule_``,
``engine_dispatch_``, ``engine_emit_ms_per_step``) and the remainder
(the ``step`` span's self time, ``watchdog_arm`` and
``snapshot_capture``) add up to it."""

from benchmarks import program_spans


def read(ctx):
    phases = program_spans.engine_step_phases(ctx)
    if phases is None:
        return None
    return 1e3 * (phases["step"] - phases.get(program_spans.SYNC, 0.0))

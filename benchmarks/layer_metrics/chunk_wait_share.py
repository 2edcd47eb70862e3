"""Prefilling slots that sat out a mixed dispatch for the prefill token
budget over those that sat out or ran a chunk, inside the traced window,
in percent: the program's counters ``chunk_waits`` (bumped on every mixed
dispatch by the prefilling slots its plan left out, 0 included) and
``chunks`` (one a chunk that ran). A program without ``chunk_waits`` reads
nothing."""

from benchmarks import program_spans


def read(ctx):
    bumps = [e["n"] for e in program_spans.window_events(ctx)
             if e["ph"] == "C" and e["name"] == "chunk_waits"]
    if not bumps:
        return None
    waits = sum(bumps)
    if not waits:
        return 0.0
    return 100.0 * waits / (program_spans.counter_growth(ctx, "chunks")
                            + waits)

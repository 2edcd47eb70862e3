"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics
read. Nothing here knows a model or a cell.

What a TPU trace holds (looked at by hand, PR 26): the plane
``/device:TPU:<n>`` has a line ``XLA Ops`` with one event per executed HLO
instruction, named by the instruction's whole text (``%fusion.12 = ...``;
a Pallas kernel is ``%<kernel name>.<k> = ... custom_call_target=
"tpu_custom_call"``, with ``jvp_``/``transpose_`` in front when autodiff
made the call), and a line ``XLA Modules`` with one event per program run.
The plane ``/host:CPU`` has one line per thread; ``TraceAnnotation`` spans
are on the Python thread's line. Device and host events share one time
axis to about a millisecond.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SHORT_GAP_NS = 20_000
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+\d*\[[\d,]*\])")
_SUFFIX = re.compile(r"\.\d+$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def parse_op(text: str) -> dict:
    """``%name.3 = <type> opcode(...)`` -> its parts."""
    instr, _, rest = text.partition(" = ")
    instr = instr.lstrip("%")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "?"
    shape = _SHAPE.search(rest)
    kind = re.search(r"kind=(k\w+)", rest)
    return {"instr": instr, "base": _SUFFIX.sub("", instr).rstrip("_"),
            "opcode": opcode, "shape": shape.group(1) if shape else "",
            "fusion_kind": kind.group(1) if kind else "",
            "pallas": 'custom_call_target="tpu_custom_call"' in rest}


def union_seconds(intervals) -> tuple[float, list]:
    """Total length of the union of [start, end) intervals (ns), and the
    gaps between its pieces as (start, end)."""
    ivs = sorted(intervals)
    if not ivs:
        return 0.0, []
    busy, gaps = 0, []
    cs, ce = ivs[0]
    for s, e in ivs[1:]:
        if s > ce:
            busy += ce - cs
            gaps.append((ce, s))
            cs, ce = s, e
        elif e > ce:
            ce = e
    busy += ce - cs
    return busy / 1e9, gaps


def kernel_matches(base: str, kernel: str) -> bool:
    """Is the instruction ``base`` (suffix stripped) a call of the Pallas
    kernel ``kernel``, bare or wrapped by autodiff (``jvp_<kernel>``)?"""
    return base == kernel or base.endswith("_" + kernel)


def reduce_trace(path: str, span_prefixes=("bench.", "setup.")) -> dict:
    """The reduced trace: per chip busy seconds, per operation totals,
    Pallas kernels by name, idle gaps labelled by the harness's span."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    chips, host_spans, host_calls = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split("(")[0]) for e in line.events)
            chips.append({"plane": plane.name, "ops": ops,
                          "modules": modules})
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefixes):
                        host_spans.append((e.start_ns,
                                           e.start_ns + e.duration_ns, e.name))
                    elif e.name.startswith("PjitFunction("):
                        host_calls.append((e.start_ns,
                                           e.start_ns + e.duration_ns, e.name))
    host_spans.sort()
    host_calls.sort()
    out = {"chips": [], "ops": {}, "kernels": {}, "idle": {}}
    parsed: dict[str, dict] = {}
    for chip in chips:
        ops = chip["ops"]
        busy, gaps = union_seconds((s, e) for s, e, _ in ops)
        out["chips"].append({"plane": chip["plane"], "busy_s": busy,
                             "n_ops": len(ops)})
        mod_starts = [m[0] for m in chip["modules"]]
        for s, e, text in ops:
            p = parsed.get(text)
            if p is None:
                p = parsed[text] = parse_op(text)
            i = bisect.bisect_right(mod_starts, s) - 1
            mod = (chip["modules"][i][2]
                   if i >= 0 and s < chip["modules"][i][1] else "?")
            # one entry for the same operation of every layer: program,
            # opcode, kind and result shape (a kernel keeps its name)
            what = p["base"] if p["pallas"] else p["opcode"]
            label = " ".join(x for x in (
                f"{mod}/{what}", p["fusion_kind"], p["shape"]) if x)
            rec = out["ops"].setdefault(label, [0, 0.0, p["instr"]])
            rec[0] += 1
            rec[1] += (e - s) / 1e9
            if p["pallas"]:
                k = out["kernels"].setdefault(p["base"], [0, 0.0])
                k[0] += 1
                k[1] += (e - s) / 1e9
        for gs, ge in gaps:
            if ge - gs < SHORT_GAP_NS:
                # between two operations of one program: the device's
                # own turnaround, not the host's doing
                label = f"between_ops_under_{SHORT_GAP_NS // 1000}us"
            else:
                mid = (gs + ge) // 2
                label = (f"{_covering(host_spans, mid) or 'no_bench_span'}/"
                         f"{_covering(host_calls, mid) or 'host_code'}")
            rec = out["idle"].setdefault(label, [0, 0.0])
            rec[0] += 1
            rec[1] += (ge - gs) / 1e9
    out["host_spans"] = [(n, (e - s) / 1e9) for s, e, n in host_spans]
    return out


def _covering(spans, t):
    """Name of the innermost (latest-starting) span that covers ``t``."""
    i = bisect.bisect_right(spans, (t, float("inf"), ""))
    for s, e, n in reversed(spans[max(0, i - 64):i]):
        if s <= t < e:
            return n
    return None


def kernel_totals(reduced: dict, kernel: str) -> tuple[int, float]:
    """(events, device seconds) of every call of a Pallas kernel."""
    n, sec = 0, 0.0
    for base, (c, s) in reduced["kernels"].items():
        if kernel_matches(base, kernel):
            n += c
            sec += s
    return n, sec


def idle_share(ctx: dict):
    """The reader of every ``device_idle_share.*`` metric: 1 minus the
    union of device-operation intervals over the traced window, in
    percent (the mean over the chips used)."""
    rec = ctx["record"]
    if rec.get("busy_s") is None or not rec["window_s"]:
        return None
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    idle = sorted(reduced["idle"].items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[f"{k} x{v[0]} (e.g. {v[2]})"[:160], v[1]]
                           for k, v in ops],
            "idle_gaps": [[f"{k} x{v[0]}"[:160], v[1]] for k, v in idle]}


class Tracer:
    """``start()`` before the window, ``stop()`` after it; ``reduced()``
    reads the trace and removes it."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def start(self):
        if not self.on:
            return
        import tempfile

        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        if self.on:
            import jax
            jax.profiler.stop_trace()

    def reduced(self) -> dict | None:
        if not self.on:
            return None
        import shutil
        try:
            return reduce_trace(find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)

"""The benchmark's one command.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``benchmarks/configs/<name>.json``) and its traffic mix
(``benchmarks/traffic/<name>.json``); the traffic's ``kind`` names the
driver (``benchmarks/drivers/<kind>.py``); a per-layer metric is
``benchmarks/layer_metrics/<name>.py``.
The last line of standard output is the result; without a TPU of a kind
that ``benchmarks/peaks.py`` knows there is no result and the exit code
is 2.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # before anything heavy is imported

import argparse          # noqa: E402
import importlib         # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(RuntimeError):
    """No result can be reported (exit code 2)."""


def load_cell(workload: str, benchmark_json: str | None = None):
    path = benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in {path}: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    from benchmarks import traffic
    return bench, cell, cfg, traffic.load(cell["traffic"])


def metrics_of(bench: dict, cell: dict, group: str) -> list[dict]:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def find_device(chips: int, require_tpu: bool = True):
    import jax

    from benchmarks.peaks import UnknownDevice, peaks_for

    devices = jax.devices()
    dev = devices[0]
    if not require_tpu:
        return devices, {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    if dev.platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {dev.platform!r} "
                      f"({dev.device_kind}); nothing was run")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    try:
        return devices, peaks_for(dev.device_kind)
    except UnknownDevice as e:
        raise Refused(str(e)) from None


def find_driver(kind: str):
    """``benchmarks/drivers/<kind>.py``: ``make_traffic``, ``run``,
    ``end_to_end``, ``check`` and ``attempted`` of a traffic kind."""
    if not os.path.exists(os.path.join(HERE, "drivers", f"{kind}.py")):
        raise Refused(f"traffic kind {kind!r} has no driver yet "
                      f"(benchmarks/drivers/{kind}.py)")
    return importlib.import_module(f"benchmarks.drivers.{kind}")


def read_layer_metric(name: str, ctx: dict):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def run_cell(bench, cell, cfg, spec, seed, seconds, trace_on,
             require_tpu=True, control=False) -> dict:
    devices, peaks = find_device(cell["chips"], require_tpu)
    import jax

    from benchmarks.spans import Spans
    from benchmarks import trace as trace_mod

    if require_tpu:
        from paddle_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        # small programs too: a run after the first compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    family = importlib.import_module(f"benchmarks.families.{cfg['family']}")
    driver = find_driver(spec["kind"])
    tracer, spans = trace_mod.Tracer(trace_on), Spans()
    rec = driver.run(cfg, spec, seed, seconds, family, tracer, spans)
    setup_s = rec["t_open"] - T_PROCESS
    rec["window_s"] = rec["t_last"] - rec["t_open"]

    metrics, notes = {}, {}
    if trace_on:
        reduced = tracer.reduced()
        chips = reduced["chips"][:cell["chips"]]
        rec["busy_s"] = (sum(c["busy_s"] for c in chips) / len(chips)
                         if chips else None)
        ctx = {"trace": reduced, "record": rec, "cfg": cfg, "spec": spec,
               "peaks": peaks, "chips": cell["chips"]}
        for m in metrics_of(bench, cell, "per_layer"):
            value = read_layer_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values, notes = driver.end_to_end(rec)
        values["setup_s"] = (setup_s, "s")
        for m in metrics_of(bench, cell, "end_to_end"):
            if m["name"] in values:
                v, unit = values[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}

    # the reference runs last: the window is closed, the peak is read,
    # the program's state is freed
    t_check = time.perf_counter()
    ok, compared, extra = driver.check(rec, cfg, spec, seed, control)
    attempted = driver.attempted(rec)
    check_s = time.perf_counter() - t_check
    failed = int(rec["failed"])
    correct = bool(ok and failed == 0 and not rec["compiled_in_window"])
    compared["compiled_in_window"] = {
        "value": int(bool(rec["compiled_in_window"])), "limit": 0}
    compared["failed"] = {"value": failed, "limit": 0}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace_on:
        device["busy_s"] = rec["busy_s"]
        device["window_s"] = rec["window_s"]
        result["breakdown"] = trace_mod.breakdown(reduced)
    result["notes"] = {
        **notes, "setup_s": setup_s, "check_s": check_s,
        "setup_spans": spans.totals("setup."), **extra}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also read the lower-precision control and the "
                         "planted faults (benchmarks/README.md); never set "
                         "by the driver")
    args = ap.parse_args(argv)
    try:
        bench, cell, cfg, spec = load_cell(args.workload)
        result = run_cell(bench, cell, cfg, spec, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control))
    except Refused as e:
        print(f"benchmarks.run: {e}", file=sys.stderr)
        return 2
    compared = result["compared"]
    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.exit(main())

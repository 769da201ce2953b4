"""One run of one benchmark cell: set-up, a closed-loop window of calls,
then the check against the plain references and one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file, its traffic file ``bench/traffic/<traffic>.json``,
the module ``bench/entries/<entry>.py`` of the entry the traffic names
and, for a ``--trace 1`` run, one reader module
``bench/metrics/<metric>.py`` per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the first call compiles or loads every program; the second runs them
# once more, so the window starts on programs that have run before
WARMUP_CALLS = 2


class RunError(RuntimeError):
    """The run cannot give a result: no chip, too few chips, or a cell
    that ``BENCHMARK.json`` does not describe."""


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader may read about one run."""
    calls: int
    bursts_per_call: int
    setup_compile_s: float
    peak_bytes: list          # peak_bytes_in_use of each of the cell's chips
    trace: object = None      # bench.trace.TraceSummary of a --trace 1 run


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise RunError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def cell_files(bench: dict, wl: dict, root: str = ROOT) -> tuple[str, str]:
    """The configuration file and the traffic file of a cell."""
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return (os.path.join(root, cfg["file"]),
            os.path.join(root, "bench", "traffic", wl["traffic"] + ".json"))


def _reports(metric: dict, wl: dict, bench: dict) -> bool:
    if "workloads" in metric:
        return wl["name"] in metric["workloads"]
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    return "workloads" not in moved or wl["name"] in moved["workloads"]


def per_layer_metrics(bench: dict, wl: dict) -> list[dict]:
    """The per-layer metrics a ``--trace 1`` run of this cell reports."""
    return [m for m in bench["per_layer"] if _reports(m, wl, bench)]


def read_metric(name: str, run: RunData, root: str = ROOT):
    """The value ``bench/metrics/<name>.py`` reads from ``run``, or None
    where it finds nothing to read."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _peak_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def _say(stream, line: str) -> None:
    print(line, file=stream, flush=True)


def run(args, t_start: float, *, require_tpu: bool = True, root: str = ROOT,
        out=None, err=None) -> int:
    """Run the cell ``args.workload`` once.  Returns the exit code; the
    result line is the last line of ``out`` and only printed when the
    run reached its check."""
    out, err = out or sys.stdout, err or sys.stderr
    bench = load_benchmark(root)
    wl = find_workload(bench, args.workload)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise RunError(f"the first device is {devices[0].platform!r}, not "
                       "'tpu'; a benchmark run never falls back to the CPU")
    if len(devices) < wl["chips"]:
        raise RunError(f"cell {wl['name']} needs {wl['chips']} chips, JAX "
                       f"sees {len(devices)}")
    devices = devices[:wl["chips"]]
    from repro.utils.env import use_compile_cache

    from bench import generator
    from bench.compile_log import CompileLog

    # sub-second programs are cached too, so no run but a cell's first
    # in a checkout compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache_dir = use_compile_cache()
    log = CompileLog()
    log.register()
    cfg_path, traffic_path = cell_files(bench, wl, root)
    with open(cfg_path) as f:
        config = json.load(f)
    with open(traffic_path) as f:
        traffic = json.load(f)
    cell = generator.build(config, traffic, args.seed, devices, root)
    seconds = args.seconds
    if args.trace:
        # a cell whose calls fill the device trace fast traces less
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
    try:
        return _measure(args, seconds, t_start, bench, wl, cell, devices,
                        log, cache_dir, root, out, err)
    finally:
        cell.close()


def _measure(args, seconds, t_start, bench, wl, cell, devices, log,
             cache_dir, root, out, err) -> int:
    import jax

    from bench import trace as bench_trace

    with jax.default_device(devices[0]):
        for _ in range(WARMUP_CALLS):      # every shape the window runs
            cell.call()
    setup_compile_s, setup_compiles = log.seconds, log.compiles
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    outs, ends = [], []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with jax.default_device(devices[0]), \
            jax.profiler.TraceAnnotation(bench_trace.WINDOW_SPAN):
        while not ends or ends[-1] - t0 < seconds:
            with jax.profiler.TraceAnnotation(bench_trace.CALL_SPAN):
                outs.append(cell.call())
            ends.append(time.perf_counter())
    window_s = ends[-1] - t0
    window_compiles = log.compiles - setup_compiles
    peaks = _peak_bytes(devices)
    summary = None
    if trace_dir:
        jax.profiler.stop_trace()
        summary = bench_trace.summarize(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run_data = RunData(
        calls=len(outs), bursts_per_call=cell.bursts_per_call,
        setup_compile_s=setup_compile_s, peak_bytes=peaks, trace=summary)
    per_call = [b - a for a, b in zip([t0] + ends[:-1], ends)]
    _say(err, json.dumps({
        "workload": wl["name"], "seed": args.seed, "trace": args.trace,
        "compile_cache": cache_dir, "setup_s": setup_s,
        "setup_compile_s": setup_compile_s, "setup_compiles": setup_compiles,
        "setup_cache_hits": log.cache_hits, "window_compiles": window_compiles,
        "calls": len(outs), "per_call_s": per_call,
        "bursts_per_call": cell.bursts_per_call}))
    for line in cell.notes(outs[-1]):
        _say(err, line)
    t_check = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        check = cell.check(outs)
    _say(err, json.dumps({"check_s": time.perf_counter() - t_check}))
    del outs
    metrics = {}
    if args.trace:
        for m in per_layer_metrics(bench, wl):
            value = read_metric(m["name"], run_data, root)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        rate = cell.bursts_per_call * run_data.calls / window_s
        metrics["sim_bursts_per_s"] = {"value": rate, "unit": "bursts/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": max(peaks)}
    result = {"correct": check.correct, "attempted": run_data.calls,
              "failed": check.failed_calls, "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in check.numbers.items()}
    result["checks"] = checks
    for name, c in checks.items():
        _say(err, f"check {name}: {c['value']} (limit {c['limit']})")
    _say(out, json.dumps(result))
    return 0

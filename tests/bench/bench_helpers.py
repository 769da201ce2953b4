"""Shared helpers of the benchmark's CPU tests: a copy of the benchmark
at sizes a test run can hold, and one run of a cell of it in-process."""
from __future__ import annotations

import argparse
import io
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# small stand-ins for each traffic file and configuration, keyed by name
SMALL_TRAFFIC = {
    "frame-grid": {"max_ops": 4},
    "fig6-grid": {"mixes": [[0, "l1"], [1, "llc"], [2, "llc"], [2, "dram"]],
                  "batch_points": 4},
}
SMALL_CONFIG = {"nvdla-soc-yolov3": {
    "window_bursts": 512,
    "llc": {"size_bytes": 16384, "ways": 4, "block_bytes": 64}}}
# the Fig. 6 grid over four DRAM organisations as one batch sharded over
# a points mesh: its cell waits for four-chip time (PERF.md, Open
# questions); here it rehearses the mesh path on one device
MESH_TRAFFIC = {**SMALL_TRAFFIC["fig6-grid"], "entry": "campaign",
                "name": "fig6-dram4", "drams": [[8, 1024], [32, 2048]],
                "batch_points": 8, "mesh": True}
MESH_CELL = {"name": "fig6-campaign-mesh", "config": "nvdla-soc-yolov3",
             "traffic": "fig6-grid-mesh", "chips": 1,
             "why": "the campaign over a points mesh"}


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs")


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def dump(obj, path: Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def small_config(name: str) -> dict:
    return {**load(ROOT / "bench" / "configs" / f"{name}.json"),
            **SMALL_CONFIG[name]}


def small_traffic(name: str) -> dict:
    return {**load(ROOT / "bench" / "traffic" / f"{name}.json"),
            **SMALL_TRAFFIC[name]}


def mini_root(tmp: Path) -> Path:
    """The benchmark under ``tmp`` with every configuration and traffic
    file cut to a test size, the mesh cell added, and every cell on one
    device."""
    bench = load(ROOT / "BENCHMARK.json")
    for wl in bench["workloads"]:
        wl["chips"] = 1
        dump(small_traffic(wl["traffic"]),
             tmp / "bench" / "traffic" / (wl["traffic"] + ".json"))
    bench["workloads"].append(dict(MESH_CELL))
    dump(MESH_TRAFFIC, tmp / "bench" / "traffic" / "fig6-grid-mesh.json")
    dump(bench, tmp / "BENCHMARK.json")
    for cfg in bench["configs"]:
        dump(small_config(cfg["name"]), tmp / cfg["file"])
    for sub in ("metrics", "entries"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


def run_cell(root: Path, workload: str, *, seed: int = 3, trace: int = 0,
             seconds: float = 0.3) -> tuple[int, dict | None, str]:
    """One run of ``workload`` from ``root`` on the CPU: (exit code,
    the result line as a dict, standard error)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bench import harness

    out, err = io.StringIO(), io.StringIO()
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    # a run turns on the persistent compile cache for its process; the
    # other tests of this worker must not compile through it
    saved = {k: getattr(jax.config, k) for k in CACHE_OPTIONS}
    try:
        rc = harness.run(args, time.perf_counter(), require_tpu=False,
                         root=str(root), out=out, err=err)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

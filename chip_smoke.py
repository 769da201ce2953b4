#!/usr/bin/env python3
"""Run the simulator's main path once on one TPU chip and check every
simulated statistic bit for bit against the CPU.

    python3 chip_smoke.py             # one chip: the four phases below
    python3 chip_smoke.py --chips 4   # the mesh-sharded campaign only

Phases, all at the sizes the paper and the benchmarks use, through the
entry points a user calls:

1. ``fig5``: the whole YOLOv3 frame's compressed DBB trace through
   ``segment_lane_hit_counts`` over the 12-point Fig. 5 grid (sizes
   {0.5, 64, 1024, 4096} KiB x blocks {32, 64, 128} B);
2. ``campaign``: the 64-point, 16384-burst acceptance campaign through
   ``run_campaign(batch_points=64)``, every point a lane of one batched
   lane program; no batch may fall back to the sequential path;
3. ``farm``: ``simulate_farm`` at ``benchmarks/fig6_tail.py``'s full size
   with 4 co-runner nodes, with and without the victim way mask;
4. ``conv``: darknet conv layers 0-1 on a 416x416x3 int8 input through
   the compiled Pallas ``conv2d_int8`` and ``postprocess`` kernels.

The oracle is the same computation on this process's CPU device.
``--chips 4`` runs phase 2 sharded over a 4-chip ``("points",)`` mesh
and on one chip of the same process, and compares the two manifests.

Every phase prints one JSON line with its checks, the wall seconds of
its first call in this process (compilation, or loading from the
persistent compile cache, included), and the compile seconds and cache
hits JAX reports.  The script refuses to run when the first device is
not a TPU.  It exits non-zero on any failed check or error;
only when everything passed is the last line of standard output
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# full sizes (a rehearsal at a tiny size lowers these)
CAMPAIGN_POINTS = 64
CAMPAIGN_WINDOW = 16384
FARM_NODES = 4
FARM_MAX_BURSTS = 2048
CONV_HW = 416
SEED = 0
# the kernels' tolerance in tests/test_kernels.py
EPILOGUE_TOL = 1e-5
PAPER_FIG5_4096KIB_128B = 1.56


class CheckFailed(RuntimeError):
    """A phase's result disagreed with its oracle."""


def same(a, b) -> bool:
    """Bit identity: equal bytes, or arrays of one dtype and shape with
    equal elements."""
    import numpy as np

    if isinstance(a, bytes):
        return a == b
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and bool(
        np.array_equal(a, b))


@functools.cache
def _compile_log():
    """The process's listener of JAX's compile events
    (``bench.compile_log.CompileLog``), registered on first use."""
    from bench.compile_log import CompileLog

    log = CompileLog()
    log.register()
    return log


# the timing fields every stats dict and phase line carries
TIMING = ("seconds", "compile_seconds", "persistent_cache_hits")


def _timed(device, fn, *args) -> tuple:
    """``fn`` with ``device`` as JAX's default device, so every array the
    simulator builds and every program it runs lands there: its result,
    and its wall seconds, compile seconds and persistent-cache hits as a
    dict keyed by ``TIMING``."""
    import jax

    log = _compile_log()
    c0, h0 = log.seconds, log.cache_hits
    t0 = time.perf_counter()
    with jax.default_device(device):
        out = fn(*args)
    return out, dict(zip(TIMING, (time.perf_counter() - t0,
                                  log.seconds - c0,
                                  log.cache_hits - h0)))


def _timing(stats: dict, ref: dict | None = None) -> dict:
    out = {k: stats[k] for k in TIMING}
    if ref is not None:
        out["oracle_seconds"] = ref["seconds"]
    return out


# --------------------------------------------------------------------------
# phase 1: Fig. 5 over the whole frame
# --------------------------------------------------------------------------
def _fig5_counts(grid):
    from repro.core.sweep import segment_lane_hit_counts

    _, per_op, _, cfgs = grid
    flat = [seg for segs in per_op for seg in segs]
    return segment_lane_hit_counts(flat, cfgs)


def fig5_stats(device) -> dict:
    from benchmarks.fig5_llc import frame_grid

    grid = frame_grid()
    counts, timing = _timed(device, _fig5_counts, grid)
    return {**timing, "grid": grid, "counts": counts}


def phase_fig5(device, cpu) -> dict:
    from benchmarks.fig5_llc import sim_driven_speedups

    got, ref = fig5_stats(device), fig5_stats(cpu)
    checks = {"hit_counts_bit_identical": same(got["counts"], ref["counts"])}
    stream, per_op, points, cfgs = got["grid"]
    speedups = sim_driven_speedups(stream, per_op, points, cfgs,
                                   got["counts"])
    return {**_timing(got, ref), "checks": checks,
            "lanes": len(points), "segments": int(got["counts"].shape[1]),
            "hit_count_sums": [int(x) for x in got["counts"].sum(axis=1)],
            "sim_speedup_4096KiB_128B": speedups.get((4096, 128)),
            "paper_speedup_4096KiB_128B": PAPER_FIG5_4096KIB_128B}


# --------------------------------------------------------------------------
# phase 2: the Fig. 6 acceptance campaign as one batched lane program
# --------------------------------------------------------------------------
def _campaign(mesh=None) -> tuple[bytes, list[str]]:
    from benchmarks.campaign_bench import _acceptance_spec
    from repro.campaign import run_campaign

    spec = _acceptance_spec(CAMPAIGN_POINTS, CAMPAIGN_WINDOW)
    notes: list[str] = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        res = run_campaign(spec, work, batch_points=CAMPAIGN_POINTS,
                           mesh=mesh, progress=notes.append)
        with open(res.manifest_path, "rb") as f:
            manifest = f.read()
    if res.completed != CAMPAIGN_POINTS or res.failed:
        raise CheckFailed(f"campaign completed {res.completed}/"
                          f"{CAMPAIGN_POINTS} points, failed {res.failed}")
    return manifest, notes


def campaign_stats(device, mesh=None) -> dict:
    (manifest, notes), timing = _timed(device, _campaign, mesh)
    return {**timing, "manifest": manifest,
            "fallbacks": sum("fell back to sequential" in n for n in notes)}


def _campaign_line(got, ref) -> dict:
    return {"checks": {
                "no_sequential_fallback":
                    got["fallbacks"] == ref["fallbacks"] == 0,
                "manifest_byte_identical":
                    same(got["manifest"], ref["manifest"])},
            "points": CAMPAIGN_POINTS, "window_bursts": CAMPAIGN_WINDOW,
            "manifest_sha256": hashlib.sha256(got["manifest"]).hexdigest()}


def phase_campaign(device, cpu) -> dict:
    got, ref = campaign_stats(device), campaign_stats(cpu)
    return {**_timing(got, ref), **_campaign_line(got, ref)}


def phase_campaign_mesh(devices) -> dict:
    """The campaign sharded over a ``("points",)`` mesh of ``devices``
    against the same campaign on the first of them alone."""
    from repro.launch.mesh import make_sweep_mesh

    sharded = campaign_stats(devices[0], make_sweep_mesh(devices))
    # read before the one-chip run adds its own peak on the first device
    sharded_peak = _peak_bytes(devices)
    single = campaign_stats(devices[0])
    return {**_timing(sharded), **_campaign_line(sharded, single),
            "oracle": "one chip",
            "single_chip_seconds": single["seconds"],
            "single_chip_compile_seconds": single["compile_seconds"],
            "mesh_devices": len(devices),
            "peak_bytes_in_use_after_sharded": sharded_peak,
            "peak_bytes_in_use_after_single": _peak_bytes(devices)}


# --------------------------------------------------------------------------
# phase 3: the multi-node farm
# --------------------------------------------------------------------------
def _farms() -> dict:
    from benchmarks.fig6_tail import LLC, WAY_MASK
    from repro.core.dram import DRAMConfig
    from repro.core.farm import FarmConfig, simulate_farm

    out = {}
    for tag, way_mask in (("unpartitioned", None), ("way_mask", WAY_MASK)):
        out[tag] = simulate_farm(llc=LLC, dram=DRAMConfig(),
                                 farm=FarmConfig(nodes=FARM_NODES,
                                                 way_mask=way_mask),
                                 max_bursts=FARM_MAX_BURSTS)
    return out


def farm_stats(device) -> dict:
    farms, timing = _timed(device, _farms)
    return {**timing, "farms": farms}


def phase_farm(device, cpu) -> dict:
    from repro.utils.stats import latency_summary

    got, ref = farm_stats(device), farm_stats(cpu)
    checks, p99 = {}, {}
    for tag, res in got["farms"].items():
        want = ref["farms"][tag]
        for name in ("noc_latency", "mem_latency", "total_latency"):
            checks[f"{tag}/{name}_bit_identical"] = same(
                getattr(res, name), getattr(want, name))
        checks[f"{tag}/metrics_identical"] = (
            res.metrics.to_record() == want.metrics.to_record())
        p99[tag] = latency_summary(res.steady())["p99"]
    return {**_timing(got, ref), "checks": checks,
            "nodes": FARM_NODES, "max_bursts": FARM_MAX_BURSTS,
            "steady_p99_cycles": p99}


# --------------------------------------------------------------------------
# phase 4: the NVDLA int8 conv stage at full width
# --------------------------------------------------------------------------
# darknet-53 layers 0-1: (cout, kernel, stride)
CONV_LAYERS = ((32, 3, 1), (64, 3, 2))
# layer outputs are requantized on the host by an exact integer shift
REQUANT_SHIFT = 7


def conv_inputs():
    """The stage's int8 input, weights and per-channel epilogues, made
    on the host from ``SEED`` (device-independent by construction)."""
    import numpy as np

    rng = np.random.RandomState(SEED)
    x = rng.randint(-127, 128, (1, CONV_HW, CONV_HW, 3)).astype(np.int8)
    layers = []
    cin = 3
    for cout, k, stride in CONV_LAYERS:
        w = rng.randint(-127, 128, (k, k, cin, cout)).astype(np.int8)
        scale = rng.uniform(1e-4, 1e-2, cout).astype(np.float32)
        bias = rng.normal(size=cout).astype(np.float32)
        layers.append((w, scale, bias, stride))
        cin = cout
    return x, layers


def _requant(acc):
    """ReLU + arithmetic shift back to int8: exact integer math, so the
    next layer's input does not depend on float rounding."""
    import numpy as np

    return np.clip(np.maximum(np.asarray(acc, np.int64), 0)
                   >> REQUANT_SHIFT, 0, 127).astype(np.int8)


def _conv_stage(use_kernels: bool) -> dict:
    """Both layers then the 2x2 max-pool: per layer the exact int32
    accumulator (unit scale, zero bias, float32 out: every value here is
    below 2**24) and the fused epilogue output, then the pooled map.
    ``use_kernels`` picks the Pallas kernels or the jnp references."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.convcore import conv2d_int8
    from repro.kernels.convcore.ref import conv2d_int8_ref
    from repro.kernels.postproc import postprocess
    from repro.kernels.postproc.ref import postprocess_ref

    def conv(x, w, scale, bias, stride, relu):
        kw = dict(stride=stride, padding=1, relu=relu,
                  out_dtype=jnp.float32)
        if use_kernels:
            return conv2d_int8(x, w, scale, bias, **kw)
        return conv2d_int8_ref(x, w, scale, bias, **kw)

    x, layers = conv_inputs()
    out = {}
    for i, (w, scale, bias, stride) in enumerate(layers):
        ones = np.ones_like(scale)
        acc = np.asarray(conv(x, w, ones, np.zeros_like(bias), stride,
                              False))
        out[f"layer{i}/acc"] = acc.astype(np.int32)
        out[f"layer{i}/epilogue"] = np.asarray(
            conv(x, w, scale, bias, stride, True))
        x = _requant(acc)
    feat = out[f"layer{len(layers) - 1}/epilogue"]
    c = feat.shape[-1]
    pool_args = (feat, np.ones(c, np.float32), np.zeros(c, np.float32))
    pool_kw = dict(act="none", pool=2, out_dtype=jnp.float32)
    out["pooled"] = np.asarray(
        postprocess(*pool_args, **pool_kw)
        if use_kernels else postprocess_ref(*pool_args, **pool_kw))
    return out


def conv_stats(device, *, use_kernels=True) -> dict:
    out, timing = _timed(device, _conv_stage, use_kernels)
    return {**timing, "arrays": out}


def phase_conv(device, cpu) -> dict:
    import numpy as np

    got = conv_stats(device)
    ref = conv_stats(cpu, use_kernels=False)
    checks, errs = {}, {}
    for k, v in got["arrays"].items():
        r = ref["arrays"][k]
        if k.endswith("/acc"):
            checks[f"{k}_exact"] = same(v, r)
            continue
        checks[f"{k}_within_tol"] = bool(np.allclose(
            v, r, rtol=EPILOGUE_TOL, atol=EPILOGUE_TOL))
        errs[k] = float(np.max(np.abs(v - r)))
    shapes = {k: list(v.shape) for k, v in got["arrays"].items()}
    return {**_timing(got, ref), "checks": checks,
            "max_abs_err": errs, "shapes": shapes}


# --------------------------------------------------------------------------
# runner, entry point
# --------------------------------------------------------------------------
PHASES = (("fig5", phase_fig5), ("campaign", phase_campaign),
          ("farm", phase_farm), ("conv", phase_conv))


def _peak_bytes(devices) -> dict:
    out = {}
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[str(d.id)] = int(stats["peak_bytes_in_use"])
    return out


def _say(line: str) -> None:
    print(line, flush=True)


def run(phases, device, cpu, emit=_say) -> bool:
    """Run ``phases`` in order on ``device`` against the ``cpu`` oracle,
    one JSON line each; stop at the first error or failed check.  True
    only if every check passed."""
    for name, fn in phases:
        try:
            line = fn(device, cpu)
        except Exception as e:
            traceback.print_exc()
            emit(json.dumps({"phase": name, "ok": False,
                             "error": f"{type(e).__name__}: {e}"}))
            return False
        ok = all(line["checks"].values())
        line.setdefault("oracle", "cpu device")
        # the first call of each program in this process: compiles (or
        # persistent-cache loads) are inside it, and said so
        line["seconds_first_call"] = line.pop("seconds")
        emit(json.dumps({"phase": name, "ok": ok, **line}, sort_keys=True))
        if not ok:
            return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the mesh-sharded campaign only")
    args = ap.parse_args(argv)
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no simulator sources at {src}", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: the first device is {devices[0].platform!r}, "
              "not 'tpu'; refusing to run (a chip run never falls back "
              "to the CPU)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    sys.path[:0] = [src, HERE]

    from repro.utils.env import use_compile_cache

    cache_dir = use_compile_cache()
    dev = devices[0]
    header = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"device": header, "compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)
    if args.chips > 1:
        mesh_devices = devices[:args.chips]
        ok = run([("campaign_mesh",
                   lambda d, c: phase_campaign_mesh(mesh_devices))],
                 dev, None)
    else:
        ok = run(PHASES, dev, jax.devices("cpu")[0])
    print(json.dumps({"memory": {"peak_bytes_in_use":
                                 _peak_bytes(devices[:args.chips])}}))
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": header}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Sweep-engine benchmark: seed vs batched/compressed simulation.

Before/after comparisons, all on the same inputs with parity asserted
(the fast paths are exact, not approximations):

* **accesses/sec** — exact per-access LLC scan vs one segment lane
  (``segment_lane_hit_counts``) on a real interleaved layer window;
* **sweep-points/sec** — a 16-point LLC geometry sweep, per-config
  scans (each geometry a fresh XLA specialization, as the seed ran it)
  vs the segment lanes (``segment_lane_hit_rates``), and per-access
  bits vs one vmapped padded-geometry program;
* **segment lanes** — the same 16-point sweep over the *full-frame*
  trace (no window cap): vmapped segment lanes vs the expanded-trace
  per-access batched path, bit-identical hit counts per lane;
* **segment-native socsim** — LLC+DRAM latency totals from segment
  arithmetic vs the per-access FAME-1 pipeline;
* **FAME-1 replay** — the seed's fixed ``4*T*(n+1)`` host-cycle
  schedule vs the chunked early-exit scheduler, warm-program timings.

Emits ``BENCH_sweep.json`` (override the path with ``BENCH_SWEEP_JSON``)
so CI can archive the perf trajectory.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import traces
from repro.core.cache import LLCConfig, simulate_trace
from repro.core.socsim import simulate_dbb_segments, simulate_dbb_stream
from repro.core.sweep import (
    _batched_hits,
    grid_configs,
    segment_lane_hit_counts,
    segment_lane_hit_rates,
)
from repro.utils.env import jax_enable_x64


def _wall(fn, iters: int = 3) -> float:
    fn()                                     # warm: compile + caches
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def _bench_compressed(rows: list, smoke: bool = False) -> None:
    cfg = LLCConfig(size_bytes=256 * 1024, ways=8, block_bytes=64)
    max_ops, clip = (4, 60_000) if smoke else (12, 400_000)
    # stream granularity: whole weight/ifmap/ofmap streams in issue
    # order (what the Fig. 5 hit-rate replay consumes) ...
    streams = traces.window(traces.network_trace(max_ops=max_ops), clip)
    # ... and arbiter granularity: 256-burst round-robin interleave
    fine = traces.window(traces.interleave(
        traces.network_trace(max_ops=max_ops), 256), clip)

    for label, segs in (("stream", streams), ("interleaved", fine)):
        n = traces.total_bursts(segs)
        addrs = traces.expand(segs)
        blocks = jnp.asarray((addrs // cfg.block_bytes).astype(np.int32))

        def exact():
            return jax.block_until_ready(
                simulate_trace(blocks, sets=cfg.sets, ways=cfg.ways))

        def compressed():
            return segment_lane_hit_counts(segs, [cfg])

        t_exact = _wall(exact, iters=1)
        t_comp = _wall(compressed, iters=3)
        assert (int(compressed().sum())
                == int(np.asarray(exact()).sum())), "parity violation"
        rows.append((f"socsim/exact_scan_{label}_acc_per_s",
                     round(n / t_exact), ""))
        rows.append((f"socsim/compressed_{label}_acc_per_s",
                     round(n / t_comp),
                     f"{n} bursts, {len(segs)} segments"))
        rows.append((f"socsim/compressed_{label}_speedup_x",
                     round(t_exact / t_comp, 1),
                     "target >= 10x" if label == "stream" else
                     "arbiter-interleaved segments"))


def _bench_sweep(rows: list, smoke: bool = False) -> None:
    if smoke:
        cfgs = grid_configs((8, 1024), (32, 128))                # 4 points
    else:
        cfgs = grid_configs((0.5, 8, 64, 1024), (32, 64, 128, 256))  # 16
    configs = list(cfgs.values())
    pts = len(configs)

    # the sweep: all geometries over the full-frame DBB stream.  The
    # seed's exact per-access scan is linear in trace length, so it is
    # measured on a sub-window and extrapolated (a full-frame seed sweep
    # would run for minutes); the engine replays the whole frame.
    frame = traces.network_trace(max_ops=8 if smoke else None)
    n_frame = traces.total_bursts(frame)
    win = traces.window(frame, 50_000 if smoke else 400_000)
    n_win = traces.total_bursts(win)
    addrs = traces.expand(win)

    def seed_window():
        # the seed path: expand + one exact scan per geometry, each
        # (sets, ways) its own XLA specialization
        out = []
        for c in configs:
            blocks = jnp.asarray((addrs // c.block_bytes).astype(np.int32))
            out.append(float(jnp.mean(simulate_trace(
                blocks, sets=c.sets, ways=c.ways).astype(jnp.float32))))
        return out

    ref = seed_window()                      # also warms per-point compiles
    assert np.allclose(ref, segment_lane_hit_rates(win, configs),
                       atol=1e-6), "sweep parity violation"
    t_seed_win = _wall(seed_window, iters=1)
    scale = n_frame / n_win
    t_seed_frame = t_seed_win * scale

    def engine_frame():
        return segment_lane_hit_rates(frame, configs)

    t0 = time.perf_counter()
    engine_frame()
    t_engine_cold = time.perf_counter() - t0
    t_engine = _wall(engine_frame, iters=2)
    rows.append(("socsim/sweep_seed_pts_per_s",
                 round(pts / t_seed_frame, 3),
                 f"{pts}-point grid, {n_frame}-burst frame "
                 f"(measured on {n_win}, x{scale:.1f} linear)"))
    rows.append(("socsim/sweep_engine_pts_per_s", round(pts / t_engine, 2),
                 "segment lanes, full frame, warm"))
    rows.append(("socsim/sweep_speedup_x",
                 round(t_seed_frame / t_engine, 1), "target >= 10x"))
    rows.append(("socsim/sweep_speedup_cold_x",
                 round(t_seed_frame / t_engine_cold, 1),
                 "first sweep incl. engine compiles"))

    # -- vmapped per-access path (fine-interleaved windows, fig5/fig6) --
    win = traces.expand(traces.default_dbb_window(max_bursts=2048))

    def seed_window():
        out = []
        for c in configs:
            blocks = jnp.asarray((win // c.block_bytes).astype(np.int32))
            out.append(simulate_trace(blocks, sets=c.sets, ways=c.ways))
        return jax.block_until_ready(out)

    def batched():
        # the private parity oracle — the public wrapper is deprecated
        return jax.block_until_ready(_batched_hits(win, configs))

    ref_w = seed_window()
    got_w = batched()
    for i in range(pts):
        assert np.array_equal(np.asarray(ref_w[i]), np.asarray(got_w[i])), i
    t_seed_w = _wall(seed_window)
    t_batched_w = _wall(batched)
    rows.append(("socsim/sweep_vmapped_warm_speedup_x",
                 round(t_seed_w / t_batched_w, 1),
                 "per-access bits, one vmapped program"))


def _bench_segment_lanes(rows: list, smoke: bool = False) -> None:
    """The tentpole comparison: a full-trace (no window cap) LLC
    geometry sweep through the vmapped segment-lane engine vs the
    expanded-trace per-access ``_batched_hits`` parity oracle — bit-identical hit
    counts per lane, wall-clock measured on the same grid."""
    if smoke:
        cfgs = grid_configs((8, 1024), (32, 128))
        frame = traces.network_trace(max_ops=8)
        probe_bursts = 20_000
    else:
        cfgs = grid_configs((0.5, 8, 64, 1024), (32, 64, 128, 256))
        frame = traces.network_trace()
        probe_bursts = 100_000
    configs = list(cfgs.values())
    pts = len(configs)
    n_frame = traces.total_bursts(frame)

    # parity: lane counts == per-access batched bits, per lane, on a
    # window where expansion is affordable
    probe = traces.window(frame, probe_bursts)
    addrs = traces.expand(probe)
    lane_counts = segment_lane_hit_counts(probe, configs).sum(axis=1)
    bit_counts = np.asarray(_batched_hits(addrs, configs)).sum(axis=1)
    assert np.array_equal(lane_counts, bit_counts), "lane parity violation"

    def expanded_probe():
        return jax.block_until_ready(_batched_hits(addrs, configs))

    t_probe = _wall(expanded_probe, iters=1)
    t_expanded = t_probe * (n_frame / len(addrs))    # linear in trace len

    def lanes_full():
        return segment_lane_hit_counts(frame, configs)

    t0 = time.perf_counter()
    lanes_full()
    t_lanes_cold = time.perf_counter() - t0
    t_lanes = _wall(lanes_full, iters=1)
    rows.append(("socsim/lanes_expanded_pts_per_s",
                 round(pts / t_expanded, 3),
                 f"{pts}-point grid, {n_frame}-burst frame (measured on "
                 f"{len(addrs)}, linear extrapolation)"))
    rows.append(("socsim/lanes_pts_per_s", round(pts / t_lanes, 2),
                 "segment lanes, full frame, warm"))
    rows.append(("socsim/lanes_speedup_x", round(t_expanded / t_lanes, 1),
                 "target >= 5x, bit-identical per-lane hit counts"))
    rows.append(("socsim/lanes_speedup_cold_x",
                 round(t_expanded / t_lanes_cold, 1),
                 "first sweep incl. lane-engine compiles"))
    rows.append(("socsim/lanes_acc_per_s", round(n_frame * pts / t_lanes),
                 "trace-accesses simulated per second across lanes"))


def _bench_segment_socsim(rows: list, smoke: bool = False) -> None:
    """Segment-native LLC+DRAM latency totals vs the per-access FAME-1
    pipeline (bit-identical totals)."""
    llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
    n = 2_000 if smoke else 8_000
    segs = traces.default_dbb_window(max_bursts=n, chunk_bursts=64)
    addrs = traces.expand(segs)

    def pipeline():
        return jax.block_until_ready(
            simulate_dbb_stream(addrs, llc=llc).latencies)

    def seg_native():
        return simulate_dbb_segments(segs, llc=llc)

    ref = simulate_dbb_stream(addrs, llc=llc)
    got = seg_native()
    assert int(ref.total_cycles) == got.total_cycles, "socsim parity"
    t_pipe = _wall(pipeline, iters=1)
    t_seg = _wall(seg_native, iters=3)
    rows.append(("socsim/pipeline_acc_per_s", round(n / t_pipe),
                 "per-access FAME-1 LLC+DRAM replay"))
    rows.append(("socsim/segment_totals_acc_per_s", round(n / t_seg),
                 "segment LLC engine + closed-form DRAM rows"))
    rows.append(("socsim/segment_totals_speedup_x",
                 round(t_pipe / t_seg, 1), "bit-identical totals"))


def _bench_fame1(rows: list, smoke: bool = False) -> None:
    llc = LLCConfig(size_bytes=4096, ways=4, block_bytes=64)
    addrs = traces.expand(traces.default_dbb_window(
        max_bursts=256 if smoke else 1024))

    def seed():
        return jax.block_until_ready(
            simulate_dbb_stream(addrs, llc=llc, early_exit=False).latencies)

    def fast():
        return jax.block_until_ready(
            simulate_dbb_stream(addrs, llc=llc, early_exit=True).latencies)

    assert np.array_equal(np.asarray(seed()), np.asarray(fast()))
    t_seed = _wall(seed)
    t_fast = _wall(fast)
    t = len(addrs)
    r_seed = simulate_dbb_stream(addrs, llc=llc, early_exit=False)
    r_fast = simulate_dbb_stream(addrs, llc=llc, early_exit=True)
    rows.append(("socsim/fame1_seed_acc_per_s", round(t / t_seed),
                 f"{r_seed.host_cycles} host cycles"))
    rows.append(("socsim/fame1_early_exit_acc_per_s", round(t / t_fast),
                 f"{r_fast.host_cycles} host cycles"))
    rows.append(("socsim/fame1_speedup_x", round(t_seed / t_fast, 1),
                 "target >= 3x"))


def _write_json(rows: list, smoke: bool) -> str:
    """BENCH_sweep.json: every row plus a headline block with the
    before/after accesses-per-sec and sweep-points/sec trajectory."""
    metrics = {name: {"value": value, "note": note}
               for name, value, note in rows}

    def val(name):
        m = metrics.get(name)
        return m["value"] if m else None

    doc = {
        "generated_by": "benchmarks/socsim_bench.py",
        "smoke": smoke,
        "headline": {
            "exact_scan_acc_per_s": val("socsim/exact_scan_stream_acc_per_s"),
            "compressed_acc_per_s": val("socsim/compressed_stream_acc_per_s"),
            "sweep_expanded_pts_per_s": val("socsim/lanes_expanded_pts_per_s"),
            "sweep_lanes_pts_per_s": val("socsim/lanes_pts_per_s"),
            "sweep_lanes_speedup_x": val("socsim/lanes_speedup_x"),
            "segment_totals_speedup_x": val("socsim/segment_totals_speedup_x"),
        },
        "metrics": metrics,
    }
    path = os.environ.get("BENCH_SWEEP_JSON", "BENCH_sweep.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    return path


def run(smoke: bool = False) -> list[tuple]:
    jax_enable_x64(False)   # defer to JAX_ENABLE_X64; addresses are checked
    rows: list[tuple] = []
    _bench_compressed(rows, smoke)
    _bench_sweep(rows, smoke)
    _bench_segment_lanes(rows, smoke)
    _bench_segment_socsim(rows, smoke)
    _bench_fame1(rows, smoke)
    path = _write_json(rows, smoke)
    rows.append(("socsim/bench_json", path, "machine-readable metrics"))
    return rows


if __name__ == "__main__":
    import sys

    from repro.utils.env import use_compile_cache

    use_compile_cache()
    print("name,value,note")
    for row in run(smoke="--smoke" in sys.argv):
        print(",".join(str(x) for x in row))

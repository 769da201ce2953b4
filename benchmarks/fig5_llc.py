"""Fig. 5 — NVDLA speedup from sharing the LLC (size x block-size grid).

Driven by ``repro.core.sweep.sweep_llc``: the closed-form timing grid
(anchored against the paper's bars) plus exact simulated hit rates for
every geometry from the vmapped segment-lane engine over a real DBB
window.  On top of that, the sim-driven path (the ROADMAP item): for
the paper-anchored geometries, ``accel_time_s(mode="simulated")`` feeds
every layer's hit rates from the exact simulator on its own DBB trace,
and ``recalibrate_stream_conflict`` re-fits the closed form's conflict
constant against a full-frame simulated grid.
"""
from __future__ import annotations

from repro.core.sweep import sweep_llc

PAPER_ANCHORS = {
    (0.5, 64): 1.17, (64, 64): 1.28,
    (1024, 32): 1.01, (1024, 64): 1.25, (1024, 128): 1.51,
    (4096, 128): 1.56,
}


def run(smoke: bool = False) -> list[tuple]:
    if smoke:
        sw = sweep_llc(sizes_kib=(0.5, 1024), blocks=(32, 64),
                       window_bursts=512)
    else:
        sw = sweep_llc(sizes_kib=(0.5, 2, 8, 64, 512, 1024, 4096),
                       blocks=(32, 64, 128))
    rows = [("fig5/no_llc_ms", round(sw.no_llc_s * 1e3, 2), "baseline")]
    for (size, block), sp in sorted(sw.speedups.items()):
        paper = PAPER_ANCHORS.get((size, block))
        note = f"paper: {paper}" if paper else ""
        rows.append((f"fig5/llc_{size}KiB_{block}B", round(sp, 3), note))
    for (size, block), hr in sorted(sw.sim_hit_rates.items()):
        rows.append((f"fig5/simhit_{size}KiB_{block}B", round(hr, 3),
                     f"exact sim, {sw.window_bursts}-burst window"))
    if smoke:
        return rows
    rows.extend(_sim_driven_rows())
    return rows


def frame_grid() -> tuple:
    """The whole-frame sim-driven grid: the YOLOv3 op stream, its
    per-op compressed DBB traces, the paper-anchor (size, block) points
    (every size x every block: 12) and their LLC geometries."""
    from repro.core import traces
    from repro.core.runtime import compile_network
    from repro.core.soc import SoCConfig, llc_config_for

    stream = compile_network(conv_buf_bytes=SoCConfig().accel.conv_buf_bytes)
    sizes = sorted({s for s, _ in PAPER_ANCHORS})
    blocks = sorted({b for _, b in PAPER_ANCHORS})
    points = [(s, b) for s in sizes for b in blocks]
    cfgs = [llc_config_for(s, b) for s, b in points]
    return stream, traces.network_op_segments(stream), points, cfgs


def sim_driven_speedups(stream, per_op, points, cfgs, counts) -> dict:
    """(size, block) -> NVDLA speedup over no LLC, with op_cycles fed
    by the lane engine's per-segment hit ``counts`` (one row per
    point, as ``segment_lane_hit_counts`` returns them)."""
    import dataclasses

    from repro.core.accelerator import _fold_op_stream_rates, accel_time_s
    from repro.core.soc import SoCConfig

    soc = SoCConfig()
    base = accel_time_s(
        stream, acc=soc.accel,
        mem=dataclasses.replace(soc.mem, llc=None))["seconds"]
    out = {}
    for idx, point in enumerate(points):
        mem = dataclasses.replace(soc.mem, llc=cfgs[idx])
        hr = _fold_op_stream_rates(per_op, counts[idx])
        out[point] = base / accel_time_s(stream, acc=soc.accel, mem=mem,
                                         hit_rates=hr)["seconds"]
    return out


def _sim_driven_rows() -> list[tuple]:
    """Speedups with op_cycles driven by the exact simulator, plus the
    closed-form re-calibration against the same simulated grid — one
    full-frame lane-engine replay feeds both (the per-op fold gives the
    timing model's hit rates, the per-lane sums give the overall rates
    the re-calibration fits)."""
    from repro.core import traces
    from repro.core.accelerator import recalibrate_stream_conflict
    from repro.core.sweep import segment_lane_hit_counts

    stream, per_op, points, cfgs = frame_grid()
    flat = [seg for segs in per_op for seg in segs]
    counts = segment_lane_hit_counts(flat, cfgs)   # the one grid replay
    total = traces.total_bursts(flat)
    speedups = sim_driven_speedups(stream, per_op, points, cfgs, counts)
    rows = []
    for size, block in sorted(points):
        paper = PAPER_ANCHORS.get((size, block))
        note = ("sim-driven op_cycles, full frame" +
                (f", paper: {paper}" if paper else ""))
        rows.append((f"fig5/simdrv_{size}KiB_{block}B",
                     round(speedups[(size, block)], 3), note))
    sim_rates = {points[i]: float(counts[i].sum()) / total
                 for i in range(len(points))}
    cal = recalibrate_stream_conflict(sim_rates)
    rows.append(("fig5/recal_conflict_blocks",
                 round(cal["stream_conflict_blocks"], 3),
                 f"shipped: {cal['shipped']}"))
    rows.append(("fig5/recal_rms_shipped", round(cal["rms_shipped"], 4),
                 f"closed form vs simulated grid, {cal['points']} points"))
    rows.append(("fig5/recal_rms_fit", round(cal["rms_fit"], 4),
                 "best single-constant fit"))
    return rows

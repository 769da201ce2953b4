"""Entry ``frame_grid``: the Fig. 5 whole-frame LLC grid through
``repro.core.sweep.segment_lane_hit_counts``.  Per call, every geometry
of the traffic's grid replays every burst of the frame on a cold LLC.

Traffic parameters: ``sizes_kib`` and ``blocks`` (the grid, with the
Fig. 5 rule for ways), ``region_shift_rows`` (how far a seed may move
each region, in DRAM rows) and optionally ``max_ops`` (a leading part
of the frame).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.generator import digest, verdict
from bench.reference import dbb as ref_dbb
from bench.reference import lane as ref_lane


def seeded_regions(config: dict, seed: int, shift_rows: int) -> tuple:
    """Weight heap and the two feature-map regions, each moved by a
    seeded whole number of DRAM rows below ``shift_rows``, with their
    bank offsets kept pairwise distinct.  Seed 0 is the default map."""
    dbb = config["dbb"]
    bases = [dbb["weight_region"], dbb["fmap_region_a"], dbb["fmap_region_b"]]
    if seed == 0:
        return tuple(bases)
    rng = np.random.default_rng(seed)
    row, banks = dbb["row_bytes"], dbb["banks"]
    while True:
        moved = [b + int(r) * row
                 for b, r in zip(bases, rng.integers(0, shift_rows, 3))]
        if len({(b // row) % banks for b in moved}) == 3:
            return tuple(moved)


def frame_segments(stream, weight_base: int, fmap_a: int, fmap_b: int):
    """Per-op DBB segments of the whole frame over the given address
    map: weights packed in layer order, feature maps ping-ponging
    between the two regions (the map ``traces.network_op_segments``
    builds at its fixed bases)."""
    from repro.core import traces

    per_op, cursor, regions = [], weight_base, (fmap_a, fmap_b)
    for i, op in enumerate(stream.accel_ops):
        per_op.append(traces.op_segments(op, cursor, regions[i % 2],
                                         regions[(i + 1) % 2]))
        cursor += op.weight_traffic // max(1, op.weight_passes)
    return per_op


def sim_driven_speedups(stream, per_op, points, cfgs, counts) -> dict:
    """(size, block) -> NVDLA speedup over no LLC, with op_cycles fed by
    the lane engine's per-segment hit ``counts`` (from
    ``benchmarks/fig5_llc.py``)."""
    from repro.core.accelerator import _fold_op_stream_rates, accel_time_s
    from repro.core.soc import SoCConfig

    soc = SoCConfig()
    base = accel_time_s(stream, acc=soc.accel,
                        mem=dataclasses.replace(soc.mem, llc=None))["seconds"]
    out = {}
    for idx, point in enumerate(points):
        mem = dataclasses.replace(soc.mem, llc=cfgs[idx])
        hr = _fold_op_stream_rates(per_op, counts[idx])
        out[point] = base / accel_time_s(stream, acc=soc.accel, mem=mem,
                                         hit_rates=hr)["seconds"]
    return out


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.core.runtime import compile_network
        from repro.core.soc import llc_config_for

        self.config = config
        self.stream = compile_network(
            conv_buf_bytes=config["accelerator"]["conv_buf_bytes"])
        self.bases = seeded_regions(config, seed, traffic["region_shift_rows"])
        self.per_op = frame_segments(self.stream, *self.bases)
        self.ops = config["dbb_ops"]
        if traffic.get("max_ops"):            # a leading part of the frame
            n = traffic["max_ops"]
            self.per_op, self.ops = self.per_op[:n], self.ops[:n]
            self.stream = dataclasses.replace(
                self.stream, accel_ops=self.stream.accel_ops[:n])
        self.flat = [s for segs in self.per_op for s in segs]
        self.points = [(s, b) for s in traffic["sizes_kib"]
                       for b in traffic["blocks"]]
        self.cfgs = [llc_config_for(s, b) for s, b in self.points]
        frame = sum(s.count for s in self.flat)
        self.bursts_per_call = frame * len(self.cfgs)

    def call(self):
        from repro.core.sweep import segment_lane_hit_counts

        return segment_lane_hit_counts(self.flat, self.cfgs)

    def check(self, outs, control: bool = False):
        """Per-segment hit counts of every geometry against the per-access
        LRU scan of the frame the reference builds from the op table."""
        segments, _ = ref_dbb.frame(self.ops, *self.bases,
                                    burst=self.config["dbb"]["burst_bytes"])
        geoms = [(c.size_bytes, c.ways, c.block_bytes) for c in self.cfgs]
        ref = ref_lane.segment_hits(segments, geoms,
                                    policy="fifo" if control else "lru")
        last = np.asarray(outs[-1], np.int64)
        bad = (int((last != ref).sum()) if last.shape == ref.shape
               else int(ref.size))
        return verdict({"hit_count_mismatches": (bad, 0)},
                       [digest(np.asarray(o).tolist()) for o in outs])

    def notes(self, out) -> list[str]:
        paper = {(s, b): v for s, b, v in self.config["paper_fig5_speedups"]}
        speedups = sim_driven_speedups(self.stream, self.per_op, self.points,
                                       self.cfgs, out)
        lines = []
        for (s, b), v in sorted(speedups.items()):
            if (s, b) in paper:
                err = 100 * (v - paper[(s, b)]) / paper[(s, b)]
                lines.append(f"fig5 sim-driven speedup {s} KiB/{b} B: "
                             f"{v:.3f} (paper {paper[(s, b)]}, "
                             f"error {err:+.1f}%)")
        return lines

    def close(self) -> None:
        pass

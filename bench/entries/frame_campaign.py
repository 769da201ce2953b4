"""Entry ``frame_campaign``: the Fig. 6 campaign over the whole YOLOv3
frame through ``repro.campaign.run_campaign``, journaling into a fresh
campaign each call.  Every point replays every burst of the frame beside
its co-runners on the configuration's own LLC and DRAM; the lanes run
compacted (``repro.core.sweep.compact_lane``).

Traffic parameters: ``name`` (the campaign's), ``mixes`` (``[co-runners,
working-set class]`` pairs), ``batch_points``, ``mesh``,
``region_shift_rows`` (how far a seed may move the weight heap and each
feature-map region, in DRAM rows, as ``frame_grid`` does).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import tempfile

from bench.entries import campaign
from bench.entries.frame_grid import seeded_regions
from bench.reference import frame as ref_frame
from bench.reference import lane as ref_lane


class Cell(campaign.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.campaign import (CampaignSpec, DRAMSpec, GeometrySpec,
                                    MixSpec, ModelSpec)
        # a whole frame takes the compacted lane engine: a simulator
        # without it would replay every chunk for minutes, so it stops
        # here instead
        from repro.core.sweep import compact_lane  # noqa: F401

        self.config = config
        self.bases = seeded_regions(config, seed,
                                    traffic["region_shift_rows"])
        llc, d = config["llc"], config["dram"]
        self.spec = CampaignSpec(
            name=traffic["name"],
            models=(ModelSpec(window_bursts=None,
                              chunk_bursts=config["dbb"]["chunk_bursts"],
                              regions=self.bases),),
            geometries=(GeometrySpec(size_kib=llc["size_bytes"] / 1024,
                                     block=llc["block_bytes"],
                                     ways=llc["ways"]),),
            mixes=tuple(MixSpec(n, wss) for n, wss in traffic["mixes"]),
            drams=(DRAMSpec(banks=d["banks"], row_bytes=d["row_bytes"],
                            t_cas_cycles=d["t_cas"], t_rcd_cycles=d["t_rcd"],
                            t_rp_cycles=d["t_rp"]),))
        self.points = self.spec.expand()
        self.batch_points = traffic["batch_points"]
        self.mesh = None
        if traffic.get("mesh"):
            from repro.launch.mesh import make_sweep_mesh

            self.mesh = make_sweep_mesh(devices)
        self.work = tempfile.mkdtemp(prefix="bench_frame_campaign_")
        self.out_dir = os.path.join(self.work, "campaign")
        frame = int(ref_frame.victim(config, self.bases)[2].sum())
        self.bursts_per_call = frame * sum(
            1 + (0 if p.mix.wss == "l1" else p.mix.corunners)
            for p in self.points)

    def reference(self, control: bool = False) -> list[dict]:
        """Every point's ``LaneMetrics`` fields, the frame's lane expanded
        access by access (``bench.reference.frame``).  The control keeps
        DRAM rows per master, breaking the shared banks."""
        c = self.config
        out = []
        for p in self.points:
            g, x, d = p.geometry, p.mix, p.dram
            mem = ref_lane.Memory(
                size_bytes=round(g.size_kib * 1024), ways=g.ways,
                block_bytes=g.block, banks=d.banks, row_bytes=d.row_bytes,
                t_cas=d.t_cas_cycles, t_rcd=d.t_rcd_cycles, t_rp=d.t_rp_cycles,
                t_llc_hit=c["t_llc_hit"])
            out.append(ref_frame.frame_lane(
                c, self.bases, mem, x.corunners, x.wss,
                rows="per_master" if control else "shared"))
        return out

    def notes(self, out) -> list[str]:
        with open(os.path.join(self.out_dir, "manifest.json"), "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
        return [f"campaign {self.spec.name}: whole frame, regions "
                f"{list(self.bases)}, {out['counts']['completed']}/"
                f"{len(self.points)} points, manifest sha256 {sha}"
                ] + frame_times(self.config,
                                [g["result"] for g in out["points"]],
                                [p.mix for p in self.points])


def _soc(config: dict):
    from repro.core.cache import LLCConfig
    from repro.core.dram import DRAMConfig
    from repro.core.soc import SoCConfig

    soc = SoCConfig()
    d = config["dram"]
    return dataclasses.replace(soc, mem=dataclasses.replace(
        soc.mem, llc=LLCConfig(**config["llc"]),
        dram=DRAMConfig(banks=d["banks"], row_bytes=d["row_bytes"],
                        t_cas_cycles=d["t_cas"], t_rcd_cycles=d["t_rcd"],
                        t_rp_cycles=d["t_rp"])))


def frame_times(config: dict, results: list[dict], mixes) -> list[str]:
    """Each lane's simulated frame time, its slowdown over the solo lane
    and the paper's frame budget, beside the paper-anchored closed-form
    slowdowns (``repro.core.soc.interference_sweep``).  A lane's NVDLA
    time is the calibrated NVDLA model (``repro.core.accelerator``)
    priced with the lane's simulated LLC hit rate on every stream and
    its simulated DRAM row-miss share as extra DRAM latency; the CPU
    layers add their model time.  Simulated results, not metrics."""
    from repro.core.accelerator import accel_time_s
    from repro.core.runtime import compile_network
    from repro.core.soc import cpu_time_s, interference_sweep

    soc = _soc(config)
    stream = compile_network(
        conv_buf_bytes=config["accelerator"]["conv_buf_bytes"])
    cpu_s = cpu_time_s(stream, soc.cpu)
    dram = soc.mem.dram
    row_miss_cycles = ((dram.t_rp_cycles + dram.t_rcd_cycles)
                       * soc.accel.freq_hz / dram.clock_hz)
    budget_ms = 1e3 / config["paper_fps"]
    closed = interference_sweep(soc, corunners=sorted(
        {m.corunners for m in mixes}))
    accel = []
    for r in results:
        mem = dataclasses.replace(soc.mem, extra_dram_latency=(
            1.0 - r["nvdla_miss_row_hit_rate"]) * row_miss_cycles)
        h = r["nvdla_hit_rate"]
        accel.append(accel_time_s(stream, acc=soc.accel, mem=mem,
                                  hit_rates=[(h, h, h)] * len(
                                      stream.accel_ops))["seconds"])
    solo = next((a for a, m in zip(accel, mixes) if m.corunners == 0),
                accel[0])
    lines = []
    for a, m in zip(accel, mixes):
        frame_ms = 1e3 * (a + cpu_s)
        slow = a / solo
        cf = closed[m.wss][m.corunners]
        lines.append(
            f"frame {m.wss} x{m.corunners}: NVDLA {1e3 * a:.2f} ms + CPU "
            f"{1e3 * cpu_s:.2f} ms = {frame_ms:.2f} ms, "
            f"{'meets' if frame_ms <= budget_ms else 'misses'} the "
            f"{config['paper_fps']} fps budget ({budget_ms:.1f} ms); "
            f"slowdown {slow:.4f} over x0, closed form {cf:.4f} "
            f"(error {100 * (slow - cf) / cf:+.1f}%)")
    lines.append("frame times price each lane's simulated NVDLA LLC hit "
                 "rate and DRAM row-hit rate with the calibrated NVDLA "
                 "model: simulated results, not metrics")
    return lines

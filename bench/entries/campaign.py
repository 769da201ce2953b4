"""Entry ``campaign``: a Fig. 6 campaign through
``repro.campaign.run_campaign``, journaling into a fresh campaign each
call; optionally with its lane batches sharded over a ``("points",)``
mesh of the cell's chips.

Traffic parameters: ``name`` (the campaign's), ``mixes`` (``[co-runners,
working-set class]`` pairs), ``batch_points``, ``mesh``, and optionally
``geometries`` (``[size_kib, block, ways]``) and ``drams`` (``[banks,
row_bytes]``), which default to the configuration's LLC and DRAM.  Each
point replays the ``window_bursts`` window of one layer, which the seed
picks.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import shutil
import tempfile

from bench.generator import digest, verdict
from bench.reference import dbb as ref_dbb
from bench.reference import lane as ref_lane

LLC_FIELDS = ("llc_hits", "hit_rate", "nvdla_hits", "nvdla_hit_rate")
DRAM_FIELDS = ("dram_row_hits", "nvdla_misses", "nvdla_miss_row_hits",
               "nvdla_miss_row_hit_rate")
LATENCY_FIELDS = ("total_cycles",)
SHAPE_FIELDS = ("segments", "accesses", "nvdla_accesses", "t_llc_hit")
DEFAULT_LAYER = 40          # the simulator's default window (ModelSpec)


def victim_window(config: dict, layer: int):
    """The reference's own build of ``layer``'s window at the default
    address map."""
    d = config["dbb"]
    return ref_dbb.window(config["dbb_ops"][layer], d["weight_region"],
                          d["fmap_region_a"], d["fmap_region_b"],
                          burst=d["burst_bytes"], chunk=d["chunk_bursts"],
                          max_bursts=config["window_bursts"])


@functools.lru_cache(maxsize=8)
def _distinct_layers(key: str) -> list[int]:
    config = json.loads(key)
    seen: dict = {}
    for i in range(len(config["dbb_ops"])):
        base, _, count = victim_window(config, i)
        if (count.sum() < config["window_bursts"]
                or (count != config["dbb"]["chunk_bursts"]).any()):
            continue
        window = (tuple(base.tolist()), tuple(count.tolist()))
        if window not in seen or i == DEFAULT_LAYER:
            seen[window] = i
    return sorted(seen.values())


def distinct_layers(config: dict) -> list[int]:
    """Layers whose window is cut into whole chunks only, one layer for
    each distinct window (the default layer for its own).  Their lanes
    all have the same segment structure, so every seed runs the same
    compiled programs, and no two of them replay the same bursts."""
    return _distinct_layers(json.dumps(
        {k: config[k] for k in ("dbb", "dbb_ops", "window_bursts")},
        sort_keys=True))


def seeded_layer(config: dict, seed: int) -> int:
    """The default layer at seed 0, the other layers in turn for later
    seeds."""
    layers = distinct_layers(config)
    return layers[(layers.index(DEFAULT_LAYER) + seed) % len(layers)]


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.campaign import (CampaignSpec, DRAMSpec, GeometrySpec,
                                    MixSpec, ModelSpec)

        self.config = config
        self.layer = seeded_layer(config, seed)
        llc, d = config["llc"], config["dram"]
        geoms = traffic.get("geometries") or [
            [llc["size_bytes"] / 1024, llc["block_bytes"], llc["ways"]]]
        drams = traffic.get("drams") or [[d["banks"], d["row_bytes"]]]
        self.spec = CampaignSpec(
            name=traffic["name"],
            models=(ModelSpec(window_bursts=config["window_bursts"],
                              chunk_bursts=config["dbb"]["chunk_bursts"],
                              layer_index=self.layer),),
            geometries=tuple(GeometrySpec(size_kib=s, block=b, ways=w)
                             for s, b, w in geoms),
            mixes=tuple(MixSpec(n, wss) for n, wss in traffic["mixes"]),
            drams=tuple(DRAMSpec(banks=banks, row_bytes=row,
                                 t_cas_cycles=d["t_cas"],
                                 t_rcd_cycles=d["t_rcd"], t_rp_cycles=d["t_rp"])
                        for banks, row in drams))
        self.points = self.spec.expand()
        self.batch_points = traffic["batch_points"]
        self.mesh = None
        if traffic.get("mesh"):
            from repro.launch.mesh import make_sweep_mesh

            self.mesh = make_sweep_mesh(devices)
        self.work = tempfile.mkdtemp(prefix="bench_campaign_")
        self.out_dir = os.path.join(self.work, "campaign")
        self.bursts_per_call = config["window_bursts"] * sum(
            1 + (0 if p.mix.wss == "l1" else p.mix.corunners)
            for p in self.points)

    def call(self):
        from repro.campaign import run_campaign

        res = run_campaign(self.spec, self.out_dir, overwrite=True,
                           batch_points=self.batch_points, mesh=self.mesh)
        return res.manifest

    def reference(self, control: bool = False) -> list[dict]:
        """Every point's ``LaneMetrics`` fields, the lane expanded access
        by access from the reference's own victim window.  The control
        keeps DRAM rows per master, breaking the shared banks."""
        c = self.config
        victim = victim_window(c, self.layer)
        out = []
        for p in self.points:
            g, x, d = p.geometry, p.mix, p.dram
            mem = ref_lane.Memory(
                size_bytes=round(g.size_kib * 1024), ways=g.ways,
                block_bytes=g.block, banks=d.banks, row_bytes=d.row_bytes,
                t_cas=d.t_cas_cycles, t_rcd=d.t_rcd_cycles, t_rp=d.t_rp_cycles,
                t_llc_hit=c["t_llc_hit"])
            layout = ref_lane.corunner_layout(c["corunners"], mem,
                                              x.corunners, x.wss)
            out.append(ref_lane.lane(
                victim, mem, layout, chunk_bursts=c["dbb"]["chunk_bursts"],
                line_bytes=c["corunners"]["line_bytes"],
                rows="per_master" if control else "shared"))
        return out

    def check(self, outs, control: bool = False):
        ref = self.reference(control)
        got = outs[-1]["points"]
        counts = dict.fromkeys(("llc", "dram", "latency", "shape"), 0)
        groups = (("llc", LLC_FIELDS), ("dram", DRAM_FIELDS),
                  ("latency", LATENCY_FIELDS), ("shape", SHAPE_FIELDS))
        by_id = {g["point_id"]: g for g in got}
        for p, want in zip(self.points, ref):
            have = by_id.get(p.point_id)
            if have is None or have["params"] != p.params():
                counts["shape"] += len(want)
                continue
            for key, fields in groups:
                counts[key] += sum(have["result"].get(f) != want[f]
                                   for f in fields)
        return verdict({"llc_field_mismatches": (counts["llc"], 0),
                        "dram_field_mismatches": (counts["dram"], 0),
                        "latency_field_mismatches": (counts["latency"], 0),
                        "lane_shape_mismatches": (counts["shape"], 0),
                        "points_not_completed":
                            (len(self.points) - len(by_id), 0)},
                       [digest(o) for o in outs])

    def notes(self, out) -> list[str]:
        with open(os.path.join(self.out_dir, "manifest.json"), "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
        lines = [f"campaign {self.spec.name}: layer {self.layer}, "
                 f"{out['counts']['completed']}/{len(self.points)} points, "
                 f"manifest sha256 {sha}"]
        for g in out["points"]:
            mix, r = g["params"]["mix"], g["result"]
            lines.append(f"campaign {mix['wss']} x{mix['corunners']}: NVDLA "
                         f"LLC hit rate {r['nvdla_hit_rate']:.4f}, miss row "
                         f"hit rate {r['nvdla_miss_row_hit_rate']:.4f}")
        lines.append("NPU backend: unvalidated (no campaign cell runs it)")
        return lines

    def close(self) -> None:
        shutil.rmtree(self.work, ignore_errors=True)

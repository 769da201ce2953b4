"""Entry ``frame_partition``: the whole-frame Fig. 6 campaign of
``frame_campaign`` on a way-partitioned LLC.  Every point fences the
NVDLA into the configuration's ``llc_partition`` capacity bitmask (Intel
CAT semantics) and the co-runners into theirs; the campaign runs its
lanes as compacted records (``repro.core.sweep.lane_records``).

Traffic parameters: those of ``frame_campaign``.
"""
from __future__ import annotations

import dataclasses

from bench.entries import frame_campaign
from bench.reference import frame as ref_frame
from bench.reference import lane as ref_lane
from bench.reference import partition as ref_partition

CONTROLS = ("masks_ignored", "rows_per_master")
# the unfenced cell's simulated frame times, as fig6-frame-dram's notes
# give them (PERF.md, section 6)
UNFENCED_MS = {0: "131.9 ms solo", "co": "137.6-140.1 ms with 1-4 co-runners"}


class Cell(frame_campaign.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        super().__init__(config, traffic, seed, devices)
        self.partition = config["llc_partition"]
        self.spec = dataclasses.replace(
            self.spec, way_masks=(self.partition["nvdla"],))
        self.points = self.spec.expand()

    def reference(self, control=False) -> list[dict]:
        """Every point's ``LaneMetrics`` fields, the frame's lane expanded
        access by access on the partitioned LLC
        (``bench.reference.partition``).  The controls each break one
        guarantee: ``masks_ignored`` (``True``) scans the LLC
        unpartitioned, ``rows_per_master`` keeps DRAM rows per master."""
        if control is True:
            control = CONTROLS[0]
        if control not in (False,) + CONTROLS:
            raise ValueError(f"control must be one of {CONTROLS}, "
                             f"got {control!r}")
        if control == "masks_ignored":
            return super().reference()
        c = self.config
        victim = ref_frame.victim(c, self.bases)
        out = []
        for p in self.points:
            g, x, d = p.geometry, p.mix, p.dram
            mem = ref_lane.Memory(
                size_bytes=round(g.size_kib * 1024), ways=g.ways,
                block_bytes=g.block, banks=d.banks, row_bytes=d.row_bytes,
                t_cas=d.t_cas_cycles, t_rcd=d.t_rcd_cycles, t_rp=d.t_rp_cycles,
                t_llc_hit=c["t_llc_hit"])
            out.append(ref_partition.lane(
                victim, mem, ref_lane.corunner_layout(
                    c["corunners"], mem, x.corunners, x.wss),
                chunk_bursts=c["dbb"]["chunk_bursts"],
                line_bytes=c["corunners"]["line_bytes"],
                victim_mask=self.partition["nvdla"],
                corunner_mask=self.partition["corunners"],
                rows="per_master" if control else "shared"))
        return out

    def notes(self, out) -> list[str]:
        """``frame_campaign``'s notes with each fenced lane's simulated
        frame time beside the unfenced cell's."""
        lines = super().notes(out)
        mixes = [p.mix for p in self.points]
        at = next(i for i, line in enumerate(lines)
                  if line.startswith("frame "))
        for i, m in zip(range(at, at + len(mixes)), mixes):
            lines[i] = (f"fenced {self.partition['nvdla']:#04x} / "
                        f"{self.partition['corunners']:#04x}: {lines[i]}; "
                        f"unfenced fig6-frame-dram "
                        f"{UNFENCED_MS[0 if m.corunners == 0 else 'co']}")
        return lines

"""The whole frame as one interference lane, expanded access by access.

The victim is every DBB segment of the frame (``dbb.frame``), in op
order, over a given address map; ``lane.lane`` cuts it into arbiter
chunks, interleaves the co-runners' lines and scans every access
through the LLC and the DRAM banks.  Nothing is compacted or planned.
"""
from __future__ import annotations

from bench.reference import dbb as ref_dbb
from bench.reference import lane as ref_lane


def victim(config: dict, bases: tuple):
    """The frame's (base, stride, count) segments, its ops' weight heap
    and two feature-map regions at ``bases``."""
    segments, _ = ref_dbb.frame(config["dbb_ops"], *bases,
                                burst=config["dbb"]["burst_bytes"])
    return segments


def frame_lane(config: dict, bases: tuple, mem: ref_lane.Memory,
               corunners: int, wss: str, *, rows: str = "shared") -> dict:
    """The metric record of the frame beside ``corunners`` BwWrite
    co-runners of working-set class ``wss``."""
    layout = ref_lane.corunner_layout(config["corunners"], mem, corunners,
                                      wss)
    return ref_lane.lane(victim(config, bases), mem, layout,
                         chunk_bursts=config["dbb"]["chunk_bursts"],
                         line_bytes=config["corunners"]["line_bytes"],
                         rows=rows)

"""The plain per-access reference of one interference lane, for tests
outside ``tests/bench``: ``bench/reference/lane.py`` expands the lane
access by access and replays it through a one-step-per-access LRU and
per-bank open rows.  A way-partitioned lane (``way_mask``) replays the
same accesses through a per-access LRU that allocates each master only
into its ways (Intel CAT semantics, ``sweep.partition_way_sels``):
hits are served from any way, a miss evicts the least recently used way
of the master's mask, ways never filled first."""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from repro.core import traces
from repro.core.sweep import LaneMetrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import dram as ref_dram  # noqa: E402
from bench.reference import lane as ref_lane  # noqa: E402

with open(os.path.join(ROOT, "bench/configs/nvdla-soc-yolov3.json")) as f:
    CORUNNERS = json.load(f)["corunners"]


def lane_metrics(segs, *, llc, dram, mix, chunk_bursts: int = 16,
                 t_llc_hit: int = 20, way_mask: int | None = None
                 ) -> LaneMetrics:
    """The lane's ``LaneMetrics``, computed access by access."""
    mem = ref_lane.Memory(llc.size_bytes, llc.ways, llc.block_bytes,
                          dram.banks, dram.row_bytes, dram.t_cas_cycles,
                          dram.t_rcd_cycles, dram.t_rp_cycles, t_llc_hit)
    layout = ref_lane.corunner_layout(CORUNNERS, mem, mix.corunners,
                                      mix.wss)
    victim = tuple(np.asarray(a, np.int64)
                   for a in zip(*map(traces.segment_tuple, segs)))
    if way_mask is None:
        return LaneMetrics.from_record(ref_lane.lane(
            victim, mem, layout, chunk_bursts=chunk_bursts, line_bytes=64))
    addr, who, _, n_segments = ref_lane.expand_lane(
        victim, layout, chunk_bursts=chunk_bursts, line_bytes=64)
    full = (1 << mem.ways) - 1
    vm = way_mask & full
    co = (full & ~vm) or full
    hit = _partitioned_hits(addr, np.where(who == 0, vm, co), mem)
    miss = ~hit
    row_hit = np.zeros(addr.shape, bool)
    row_hit[miss] = ref_dram.row_hits(
        addr[miss] // mem.block_bytes * mem.block_bytes,
        banks=mem.banks, row_bytes=mem.row_bytes)
    is_victim = who == 0
    accesses, hits = int(addr.shape[0]), int(hit.sum())
    misses, row_hits = accesses - hits, int(row_hit.sum())
    nv_acc = int(is_victim.sum())
    nv_hits = int((hit & is_victim).sum())
    nv_miss = int((miss & is_victim).sum())
    nv_rh = int((row_hit & is_victim).sum())
    return LaneMetrics(
        segments=n_segments, accesses=accesses, llc_hits=hits,
        dram_row_hits=row_hits, t_llc_hit=t_llc_hit,
        total_cycles=(accesses * t_llc_hit + misses * mem.t_cas
                      + (misses - row_hits) * (mem.t_rp + mem.t_rcd)),
        hit_rate=hits / max(1, accesses), nvdla_accesses=nv_acc,
        nvdla_hits=nv_hits, nvdla_hit_rate=nv_hits / max(1, nv_acc),
        nvdla_misses=nv_miss, nvdla_miss_row_hits=nv_rh,
        nvdla_miss_row_hit_rate=nv_rh / nv_miss if nv_miss else 1.0)


def _partitioned_hits(addr, alloc, mem) -> np.ndarray:
    """Per-access hit bits on a cold cache; access ``k`` allocates only
    into the ways of ``alloc[k]``."""
    sets, ways = mem.sets, mem.ways
    blocks = np.asarray(addr, np.int64) // mem.block_bytes
    tags = np.full((sets, ways), -1, np.int64)
    last = np.zeros((sets, ways), np.int64)
    hit = np.zeros(blocks.shape[0], bool)
    for k, (block, mask) in enumerate(zip(blocks, alloc)):
        s, t = block % sets, block // sets
        match = np.flatnonzero(tags[s] == t)
        if match.size:
            hit[k], way = True, match[0]
        else:
            allowed = np.flatnonzero((int(mask) >> np.arange(ways)) & 1)
            way = allowed[np.argmin(last[s, allowed])]
        tags[s, way], last[s, way] = t, k + 1
    return hit

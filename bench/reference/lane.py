"""One interference lane, expanded access by access.

A lane is the victim's DBB window cut into ``chunk_bursts``-burst
chunks.  After each victim chunk, every co-runner writes as many
``line_bytes`` lines as the chunk had bursts, walking its working set
from where it stopped and wrapping at its end.  Every access goes
through the LLC; every miss reads its block from DRAM.  The latency
of a group of accesses is

    accesses * t_llc_hit + misses * tCAS + row_misses * (tRP + tRCD).

The control ``rows="per_master"`` keeps one open row per bank for each
master apart, as if co-runner misses never closed the victim's rows: it
breaks the shared-bank guarantee the configuration states.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import dram as ref_dram
from bench.reference import llc as ref_llc

ROWS = ("shared", "per_master")


@dataclasses.dataclass(frozen=True)
class Memory:
    """LLC geometry, DRAM timing and the co-runner layout of one lane."""
    size_bytes: int
    ways: int
    block_bytes: int
    banks: int
    row_bytes: int
    t_cas: int
    t_rcd: int
    t_rp: int
    t_llc_hit: int

    @property
    def sets(self) -> int:
        return max(1, self.size_bytes // (self.ways * self.block_bytes))


def corunner_layout(corunners: dict, mem: Memory, n: int, wss: str
                    ) -> list[tuple[int, int]]:
    """Each co-runner's (span in lines, region base) for ``n`` co-runners
    of working-set class ``wss``, from the configuration's
    ``corunners`` block.  An ``l1`` working set never leaves the core."""
    if wss == "l1":
        return []
    cls = corunners[wss]
    line = corunners["line_bytes"]
    out = []
    for w in range(n):
        if "llc_fraction" in cls:
            span = max(line, int(mem.size_bytes * cls["llc_fraction"]))
        else:
            span = mem.size_bytes * cls["llc_multiple"]
        region = (cls["region"] + w * cls["region_step"]
                  + (corunners["stagger_first"] + corunners["stagger_step"] * w)
                  * corunners["stagger_bytes"])
        out.append((span // line, region))
    return out


def _chunks(victim, chunk_bursts: int):
    base, stride, count = (np.asarray(a, np.int64) for a in victim)
    keep = count > 0
    base, stride, count = base[keep], stride[keep], count[keep]
    n_ch = -(-count // chunk_bursts)
    seg = np.repeat(np.arange(count.shape[0]), n_ch)
    j = np.arange(seg.shape[0]) - np.repeat(np.cumsum(n_ch) - n_ch, n_ch)
    ch_base = base[seg] + j * chunk_bursts * stride[seg]
    ch_count = np.minimum(chunk_bursts, count[seg] - j * chunk_bursts)
    return ch_base, stride[seg], ch_count


def expand_lane(victim, layout, *, chunk_bursts: int, line_bytes: int):
    """The lane's accesses in order: (byte addresses, master per access
    (0 the victim, w + 1 co-runner w), chunk index per access, segment
    count of the compressed lane)."""
    ch_base, ch_stride, ch_count = _chunks(victim, chunk_bursts)
    n_ch, k = ch_base.shape[0], 1 + len(layout)
    slot_count = np.repeat(ch_count, k)
    slot_start = np.cumsum(slot_count) - slot_count
    total = int(slot_count.sum())
    slot_of = np.repeat(np.arange(n_ch * k), slot_count)
    j = np.arange(total) - slot_start[slot_of]
    chunk = slot_of // k
    who = slot_of % k                       # 0: victim, w + 1: co-runner w
    addr = ch_base[chunk] + j * ch_stride[chunk]
    cursor = np.cumsum(ch_count) - ch_count  # co-runner lines before chunk
    pieces = 0
    for w, (span, region) in enumerate(layout):
        mine = who == w + 1
        addr[mine] = region + ((cursor[chunk[mine]] + j[mine]) % span) * line_bytes
        start = cursor % span
        pieces += int(((start + ch_count - 1) // span + 1).sum())
    return addr, who, chunk, n_ch + pieces


def lane(victim, mem: Memory, layout, *, chunk_bursts: int, line_bytes: int,
         policy: str = "lru", rows: str = "shared"):
    """The lane's metric record: the fields of the simulator's
    ``LaneMetrics``."""
    if rows not in ROWS:
        raise ValueError(f"rows must be one of {ROWS}, got {rows!r}")
    addr, who, _, n_segments = expand_lane(
        victim, layout, chunk_bursts=chunk_bursts, line_bytes=line_bytes)
    is_victim = who == 0
    hit = ref_llc.hits(addr, sets=mem.sets, ways=mem.ways,
                       block_bytes=mem.block_bytes, policy=policy)
    miss = ~hit
    row_hit = np.zeros(addr.shape, bool)
    for m in (np.unique(who) if rows == "per_master" else [None]):
        sel = miss if m is None else miss & (who == m)
        row_hit[sel] = ref_dram.row_hits(
            addr[sel] // mem.block_bytes * mem.block_bytes,
            banks=mem.banks, row_bytes=mem.row_bytes)
    accesses, hits = int(addr.shape[0]), int(hit.sum())
    misses, row_hits = accesses - hits, int(row_hit.sum())
    nv_acc = int(is_victim.sum())
    nv_hits = int((hit & is_victim).sum())
    nv_miss = int((miss & is_victim).sum())
    nv_rh = int((row_hit & is_victim).sum())
    record = {
        "segments": n_segments,
        "accesses": accesses,
        "llc_hits": hits,
        "dram_row_hits": row_hits,
        "t_llc_hit": mem.t_llc_hit,
        "total_cycles": _latency(accesses, misses, row_hits, mem),
        "hit_rate": hits / max(1, accesses),
        "nvdla_accesses": nv_acc,
        "nvdla_hits": nv_hits,
        "nvdla_hit_rate": nv_hits / max(1, nv_acc),
        "nvdla_misses": nv_miss,
        "nvdla_miss_row_hits": nv_rh,
        "nvdla_miss_row_hit_rate": nv_rh / nv_miss if nv_miss else 1.0,
    }
    return record


def _latency(accesses, misses, row_hits, mem: Memory):
    return (accesses * mem.t_llc_hit + misses * mem.t_cas
            + (misses - row_hits) * (mem.t_rp + mem.t_rcd))


def segment_hits(segments, mem_geometries, *, policy: str = "lru"
                 ) -> np.ndarray:
    """(n_geometries, n_segments) hit counts of one compressed trace,
    each geometry ``(size_bytes, ways, block_bytes)`` on a cold cache."""
    base, stride, count = (np.asarray(a, np.int64) for a in segments)
    seg = np.repeat(np.arange(count.shape[0]), count)
    j = np.arange(seg.shape[0]) - np.repeat(np.cumsum(count) - count, count)
    addr = base[seg] + j * stride[seg]
    out = np.zeros((len(mem_geometries), count.shape[0]), np.int64)
    for g, (size, ways, block) in enumerate(mem_geometries):
        sets = max(1, size // (ways * block))
        hit = ref_llc.hits(addr, sets=sets, ways=ways, block_bytes=block,
                           policy=policy)
        out[g] = np.bincount(seg[hit], minlength=count.shape[0])
    return out

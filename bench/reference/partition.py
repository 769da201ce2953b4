"""One interference lane on a way-partitioned LLC, expanded access by
access.

The lane's accesses are those of ``lane.expand_lane``.  The LLC is
partitioned as Intel's Cache Allocation Technology partitions it: each
master holds a capacity bitmask of ways.  A hit is a tag match in any
way of the set, and refreshes that way's recency.  A miss allocates
into the least recently used way of the master's bitmask; ways never
filled are taken first, lowest index first.  Every access is one step:
nothing is compressed, planned or closed-form.  DRAM rows are kept as
``lane.lane`` keeps them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dram as ref_dram
from bench.reference import lane as ref_lane


@functools.partial(jax.jit, static_argnames=("sets", "ways"))
def _scan(set_idx, tag, alloc, *, sets: int, ways: int):
    big = jnp.iinfo(jnp.int32).max

    def step(carry, x):
        tags, last = carry                      # (sets, ways) each
        s, t, a, k = x
        row_tags, row_last = tags[s], last[s]
        match = row_tags == t
        hit = jnp.any(match)
        mine = ((a >> jnp.arange(ways)) & 1) == 1
        victim = jnp.argmin(jnp.where(mine, row_last, big))
        way = jnp.where(hit, jnp.argmax(match), victim)
        return (tags.at[s, way].set(t), last.at[s, way].set(k)), hit

    init = (jnp.full((sets, ways), -1, jnp.int32),
            jnp.zeros((sets, ways), jnp.int32))
    stamps = jnp.arange(1, set_idx.shape[0] + 1, dtype=jnp.int32)
    _, hits = jax.lax.scan(step, init, (set_idx, tag, alloc, stamps))
    return hits


def hits(byte_addrs, alloc, *, sets: int, ways: int, block_bytes: int
         ) -> np.ndarray:
    """Per-access hit bits of ``byte_addrs`` on a cold cache, access
    ``k`` allocating only into the ways of bitmask ``alloc[k]``."""
    blocks = np.asarray(byte_addrs, np.int64) // block_bytes
    alloc = np.asarray(alloc, np.int64)
    if blocks.size == 0:
        return np.zeros(0, bool)
    if blocks.min() < 0 or blocks.max() // sets > np.iinfo(np.int32).max:
        raise ValueError("block addresses out of range for int32 tags")
    if blocks.size >= np.iinfo(np.int32).max:
        raise ValueError("trace too long for int32 recency stamps")
    if ((alloc & ((1 << ways) - 1)) == 0).any() or (alloc >> ways).any():
        raise ValueError(f"every bitmask must hold some of the {ways} ways "
                         "and no other")
    out = _scan(jnp.asarray(blocks % sets, jnp.int32),
                jnp.asarray(blocks // sets, jnp.int32),
                jnp.asarray(alloc, jnp.int32), sets=int(sets), ways=int(ways))
    return np.asarray(out, bool)


def lane(victim, mem: ref_lane.Memory, layout, *, chunk_bursts: int,
         line_bytes: int, victim_mask: int, corunner_mask: int,
         rows: str = "shared") -> dict:
    """The lane's metric record, the fields of the simulator's
    ``LaneMetrics``, with the victim allocating into ``victim_mask``'s
    ways and every co-runner into ``corunner_mask``'s."""
    if rows not in ref_lane.ROWS:
        raise ValueError(f"rows must be one of {ref_lane.ROWS}, got {rows!r}")
    addr, who, _, n_segments = ref_lane.expand_lane(
        victim, layout, chunk_bursts=chunk_bursts, line_bytes=line_bytes)
    is_victim = who == 0
    hit = hits(addr, np.where(is_victim, victim_mask, corunner_mask),
               sets=mem.sets, ways=mem.ways, block_bytes=mem.block_bytes)
    miss = ~hit
    row_hit = np.zeros(addr.shape, bool)
    for m in (np.unique(who) if rows == "per_master" else [None]):
        sel = miss if m is None else miss & (who == m)
        row_hit[sel] = ref_dram.row_hits(
            addr[sel] // mem.block_bytes * mem.block_bytes,
            banks=mem.banks, row_bytes=mem.row_bytes)
    accesses, n_hits = int(addr.shape[0]), int(hit.sum())
    misses, row_hits = accesses - n_hits, int(row_hit.sum())
    nv_acc = int(is_victim.sum())
    nv_hits = int((hit & is_victim).sum())
    nv_miss = int((miss & is_victim).sum())
    nv_rh = int((row_hit & is_victim).sum())
    return {
        "segments": n_segments,
        "accesses": accesses,
        "llc_hits": n_hits,
        "dram_row_hits": row_hits,
        "t_llc_hit": mem.t_llc_hit,
        "total_cycles": (accesses * mem.t_llc_hit + misses * mem.t_cas
                         + (misses - row_hits) * (mem.t_rp + mem.t_rcd)),
        "hit_rate": n_hits / max(1, accesses),
        "nvdla_accesses": nv_acc,
        "nvdla_hits": nv_hits,
        "nvdla_hit_rate": nv_hits / max(1, nv_acc),
        "nvdla_misses": nv_miss,
        "nvdla_miss_row_hits": nv_rh,
        "nvdla_miss_row_hit_rate": nv_rh / nv_miss if nv_miss else 1.0,
    }

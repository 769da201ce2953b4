"""LLC way-masking (CAT-style partitioning): the two pinned invariants.

1. **Isolation**: a way outside a traffic class's allocation mask never
   *holds* that class's lines — hits may touch any way (recency refresh,
   Intel CAT semantics), but allocation is confined to the mask, so the
   final tag state proves the fence.
2. **Sentinel/identity**: the full mask (and the batch path's zero
   sentinel) is bit-exactly the unpartitioned scan — same hits, same
   miss runs, same ``LaneMetrics``.
"""
from __future__ import annotations

import numpy as np
import pytest

import lane_reference
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import (MixConfig, interference_lane_metrics,
                              interference_lane_metrics_batch,
                              lane_request_latencies, _masked_lane_run,
                              partition_way_sels)

VICTIM_REGION = 0x1000_0000
CORUN_REGION = 0x2000_0000
LLC = LLCConfig(size_bytes=16 * 1024, ways=4, block_bytes=64)  # 64 sets


def _two_class_lane(rng, n_segs: int = 24):
    """Interleaved victim/co-runner segments, both streaming well past
    the 4-way capacity of every set."""
    b, s, c, is_victim = [], [], [], []
    for i in range(n_segs):
        victim = i % 2 == 0
        region = VICTIM_REGION if victim else CORUN_REGION
        b.append(region + int(rng.integers(0, 64)) * 64 * 64)
        s.append(int(rng.choice((32, 64))))
        c.append(int(rng.integers(32, 256)))
        is_victim.append(victim)
    return (np.asarray(b, np.int64), np.asarray(s, np.int64),
            np.asarray(c, np.int64), np.asarray(is_victim, bool))


def _resident_blocks(tags, sets: int):
    """(way, block_byte_addr) pairs of every valid line in the final
    tag state (tags are block // sets per (way, set))."""
    ways = tags.shape[0]
    w, s = np.nonzero(tags != -1)
    blocks = tags[w, s].astype(np.int64) * sets + s
    return w, blocks * 64


class TestIsolation:
    def test_masked_ways_never_hold_foreign_lines(self):
        rng = np.random.default_rng(7)
        for trial in range(5):
            b, s, c, nv = _two_class_lane(rng)
            vm = int(rng.choice((0b0001, 0b0011, 0b0110)))
            sels = partition_way_sels(nv, LLC, vm)
            _, _, (tags, _) = _masked_lane_run(b, s, c, LLC, sels,
                                               return_state=True)
            way, addr = _resident_blocks(np.asarray(tags), LLC.sets)
            is_victim_line = addr < CORUN_REGION
            co = ((1 << LLC.ways) - 1) & ~vm
            for w, victim_line in zip(way, is_victim_line):
                mask = vm if victim_line else co
                assert (mask >> w) & 1, (
                    f"trial {trial}: way {w} holds a "
                    f"{'victim' if victim_line else 'co-runner'} line "
                    f"outside its allocation mask {mask:#x}")

    def test_partition_protects_victim_reuse(self):
        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        from repro.core import traces

        segs = traces.default_dbb_window(max_bursts=512) * 2
        mix = MixConfig(corunners=2, wss="llc")
        dram = DRAMConfig()
        base = interference_lane_metrics(segs, llc=llc, dram=dram, mix=mix)
        part = interference_lane_metrics(segs, llc=llc, dram=dram, mix=mix,
                                         way_mask=0x0F)
        assert part.nvdla_hit_rate > base.nvdla_hit_rate
        assert part.total_cycles < base.total_cycles


class TestSentinelIdentity:
    def test_full_mask_is_bit_exact_unpartitioned(self):
        from repro.core import traces

        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        dram = DRAMConfig()
        segs = traces.default_dbb_window(max_bursts=512)
        full = (1 << llc.ways) - 1
        for n in (0, 2):
            mix = MixConfig(corunners=n, wss="llc" if n else "l1")
            a = interference_lane_metrics(segs, llc=llc, dram=dram,
                                          mix=mix)
            b = interference_lane_metrics(segs, llc=llc, dram=dram,
                                          mix=mix, way_mask=full)
            assert a == b

    def test_batch_mixes_masked_and_unmasked_lanes(self):
        from repro.core import traces

        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        dram = DRAMConfig()
        segs = traces.default_dbb_window(max_bursts=256)
        mix = MixConfig(corunners=2, wss="llc")
        mixes = [MixConfig(0, "l1"), mix, mix, mix]
        masks = [None, None, (1 << llc.ways) - 1, 0x0F]
        batch = interference_lane_metrics_batch(
            segs, llcs=[llc] * 4, drams=[dram] * 4, mixes=mixes,
            way_masks=masks)
        for got, mix_i, mask_i in zip(batch, mixes, masks):
            ref = lane_reference.lane_metrics(segs, llc=llc, dram=dram,
                                              mix=mix_i, way_mask=mask_i)
            assert got == ref

    def test_request_latencies_sum_to_lane_total(self):
        from repro.core import traces

        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        dram = DRAMConfig()
        segs = traces.default_dbb_window(max_bursts=512)
        mix = MixConfig(corunners=2, wss="llc")
        for mask in (None, 0x0F):
            lat, metrics = lane_request_latencies(
                segs, llc=llc, dram=dram, mix=mix, way_mask=mask)
            assert metrics == lane_reference.lane_metrics(
                segs, llc=llc, dram=dram, mix=mix, way_mask=mask)
            # victim chunks carry the victim's share; the co-runner
            # share is the rest — both sides of the identity are exact
            assert lat.shape[0] == 512 // 16
            assert 0 < int(lat.sum()) <= metrics.total_cycles


class TestPartitionWaySels:
    def test_empty_victim_mask_raises(self):
        with pytest.raises(ValueError, match="at least one way"):
            partition_way_sels(np.array([True]), LLC, 0x10)  # beyond ways

    def test_full_mask_means_unpartitioned_for_both_classes(self):
        full = (1 << LLC.ways) - 1
        sels = partition_way_sels(np.array([True, False]), LLC, full)
        assert sels.tolist() == [full, full]

    def test_complement_assignment(self):
        sels = partition_way_sels(np.array([True, False]), LLC, 0b0011)
        assert sels.tolist() == [0b0011, 0b1100]

"""Way-partitioned interference lanes run as compacted records
(``cache.record_lane_scan(masks=)``): each class of an Intel-CAT-style
partition (``sweep.partition_way_sels``) is one LRU stack over its own
ways, and the records give every ``LaneMetrics`` field bit for bit as
the uncompacted masked segment program and the plain per-access
partitioned reference (``tests/lane_reference.py``) give them.

The frame is cut to its first two ops, each segment to 100 bursts
(op 1's weights start mid-block, so its chunks share their boundary
blocks), and read twice: on a 64-set LLC of 8 ways its second pass
re-reads what a partition of 7 ways still holds."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import lane_reference
from repro.core import sweep, traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import MixConfig, interference_lane_metrics_batch
from repro.utils import tracing

FRAME = traces.network_trace(max_ops=2)
CUT = [traces.Segment(s.base, s.stride, min(s.count, 100)) for s in FRAME]
TWICE = CUT + CUT
DRAM = DRAMConfig()
WIDE = LLCConfig(size_bytes=32768, ways=8, block_bytes=64)
MIXES = [MixConfig(k, wss) for wss in ("l1", "llc", "dram")
         for k in range(5)]
MASKS = (0x01, 0x0F, 0x7F, 0xFF)
LANES = [(m, w) for w in MASKS for m in MIXES]
IDS = [f"{m.wss}-x{m.corunners}-{w:#04x}" for m, w in LANES]


def _batch(lanes, segs=TWICE, llc=WIDE):
    return interference_lane_metrics_batch(
        segs, llcs=[llc] * len(lanes), drams=[DRAM] * len(lanes),
        mixes=[m for m, _ in lanes], way_masks=[w for _, w in lanes])


def _grew(before) -> dict:
    after = tracing.counters()
    return {k: after[k] - before.get(k, 0) for k in after}


@pytest.fixture(scope="module")
def records():
    before = tracing.counters()
    out = _batch(LANES)
    return out, _grew(before)


@pytest.fixture(scope="module")
def segments():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sweep, "_records_that_pay", lambda recs: None)
        before = tracing.counters()
        out = _batch(LANES)
        return out, _grew(before)


@pytest.mark.parametrize("i", range(len(LANES)), ids=IDS)
def test_partitioned_records_match_segments_and_reference(records,
                                                          segments, i):
    mix, mask = LANES[i]
    got = records[0][i]
    assert got == segments[0][i]
    assert got == lane_reference.lane_metrics(TWICE, llc=WIDE, dram=DRAM,
                                              mix=mix, way_mask=mask)


def test_every_partitioned_lane_ran_as_records(records, segments):
    """The record batch: every lane partitioned records, built from the
    chunks, far fewer than its segments; the segment batch none."""
    grew = records[1]
    assert grew[tracing.PARTITIONED_RECORD_LANES] == len(LANES)
    assert grew[tracing.DIRECT_RECORD_LANES] == len(LANES)
    assert (grew[tracing.PARTITIONED_LANE_SEGMENTS]
            == grew[tracing.LANE_SEGMENTS])
    assert 5 * grew[tracing.LANE_SEGMENTS] < grew[tracing.LANE_SEGMENTS_RAW]
    assert segments[1].get(tracing.PARTITIONED_RECORD_LANES, 0) == 0
    # the partition decides hits: a fenced NVDLA of 7 ways keeps its
    # first pass where the shared LLC loses it to the co-runners
    by = dict(zip(LANES, records[0]))
    assert (by[(MixConfig(4, "dram"), 0x7F)].nvdla_hits
            > by[(MixConfig(4, "dram"), 0xFF)].nvdla_hits)


@pytest.mark.parametrize("mask", MASKS[:3])
@pytest.mark.parametrize("wss", ["llc", "dram"])
def test_the_fence_isolates_the_nvdla(records, wss, mask):
    """The NVDLA's lines live in its own ways and the co-runners' in
    theirs, so what the NVDLA hits does not depend on how many
    co-runners run beside it."""
    by = dict(zip(LANES, records[0]))
    solo = by[(MixConfig(0, wss), mask)]
    for k in range(1, 5):
        lane = by[(MixConfig(k, wss), mask)]
        assert lane.nvdla_hits == solo.nvdla_hits
        assert lane.nvdla_accesses == solo.nvdla_accesses


BLOCK128 = LLCConfig(size_bytes=65536, ways=8, block_bytes=128)
DIRECT = ([(f"{m.wss}-x{m.corunners}-{w:#04x}", FRAME, WIDE, m, w)
           for m in MIXES[5:] for w in (0x01, 0x0F)]
          + [(f"block128-dram-x3-{w:#04x}", FRAME, BLOCK128,
              MixConfig(3, "dram"), w) for w in (0x03, 0x7F)]
          + [("l1-x0-0x07", FRAME, WIDE, MixConfig(0, "l1"), 0x07),
             ("twice-llc-x4-0xff", TWICE, WIDE, MixConfig(4, "llc"), 0xFF)])


@pytest.mark.parametrize("segs,llc,mix,mask", [c[1:] for c in DIRECT],
                         ids=[c[0] for c in DIRECT])
def test_lane_records_match_the_folded_partitioned_lane(segs, llc, mix,
                                                        mask):
    """``lane_records`` of a partitioned lane gives, field for field and
    in the same order, the records ``compact_lane`` folds from the
    expanded lane and its allocation masks."""
    chunks = sweep.nvdla_chunks(segs)
    got = sweep.lane_records(chunks, llc, mix, mask)
    b, s, c, nv = sweep.corunner_meta(segs, llc=llc, mix=mix)
    want = sweep.compact_lane(
        b, s, c, nv, llc, 1 + (0 if mix.wss == "l1" else mix.corunners),
        sweep.partition_way_sels(nv, llc, mask))
    for f in dataclasses.fields(want):
        g, w = getattr(got, f.name), getattr(want, f.name)
        assert g.dtype == w.dtype and np.array_equal(g, w), f.name
    live = got.counts > 0
    assert set(got.masks[live & got.nv]) == {mask & 0xFF}
    assert set(got.masks[live & ~got.nv]) <= {(0xFF & ~mask) or 0xFF}


def test_a_fenced_class_of_one_way_keeps_whole_records():
    """Per class, a block a chunk boundary splits waits out only its own
    class's arrivals: the NVDLA's op 1 weights, whose boundary blocks
    the chunks share, fold into records beside four co-runners on a
    4-way LLC only once the co-runners are fenced off."""
    narrow = LLCConfig(size_bytes=16384, ways=4, block_bytes=64)
    chunks = sweep.nvdla_chunks(FRAME)
    mix = MixConfig(4, "dram")
    shared = sweep.lane_records(chunks, narrow, mix)
    fenced = sweep.lane_records(chunks, narrow, mix, 0x01)
    assert fenced.raw.shape[0] < shared.raw.shape[0]
    assert int(fenced.raw.sum()) == int(shared.raw.sum())
    got = interference_lane_metrics_batch(
        FRAME, llcs=[narrow] * 2, drams=[DRAM] * 2, mixes=[mix] * 2,
        way_masks=[0x01, 0x0E])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sweep, "_records_that_pay", lambda recs: None)
        want = interference_lane_metrics_batch(
            FRAME, llcs=[narrow] * 2, drams=[DRAM] * 2, mixes=[mix] * 2,
            way_masks=[0x01, 0x0E])
    assert got == want


# after the odd first segment every co-runner chunk starts mid-block,
# and on 4 sets each chunk reaches the shared boundary block's set
SMALL = LLCConfig(size_bytes=4096, ways=8, block_bytes=128)
ODD = [traces.Segment(0x1000_0000, 32, 99),
       traces.Segment(0x1010_0040, 32, 800)]


@pytest.mark.parametrize("corunners,mask,folds", [
    (2, 0x0F, True), (2, 0x7F, False), (3, 0x0F, False)])
def test_a_class_waits_out_only_its_own_ways(corunners, mask, folds):
    """A co-runner's boundary block, shared by its consecutive chunks,
    waits out the other co-runners' arrivals in the co-runners' ways:
    records fold only while those arrivals stay under that class's way
    count (4 of 8 at ``0x0F``, 1 at ``0x7F``), not the LLC's, and the
    lane equals the reference either way."""
    mix = MixConfig(corunners, "dram")
    before = tracing.counters()
    got = interference_lane_metrics_batch(
        ODD, llcs=[SMALL], drams=[DRAM], mixes=[mix], way_masks=[mask])
    assert _grew(before).get(tracing.PARTITIONED_RECORD_LANES, 0) == folds
    assert got[0] == lane_reference.lane_metrics(
        ODD, llc=SMALL, dram=DRAM, mix=mix, way_mask=mask)


def test_a_mixed_batch_leaves_its_unpartitioned_lanes_as_they_were():
    """Partitioned and unpartitioned lanes in one batch: the
    unpartitioned ones are bit-identical to a batch without a
    partition, and every lane to the reference."""
    lanes = [(MixConfig(4, "dram"), 0x0F), (MixConfig(4, "dram"), None),
             (MixConfig(2, "llc"), 0x01), (MixConfig(2, "llc"), None),
             (MixConfig(0, "l1"), None)]
    got = _batch(lanes)
    plain = interference_lane_metrics_batch(
        TWICE, llcs=[WIDE] * 3, drams=[DRAM] * 3,
        mixes=[m for m, w in lanes if w is None])
    assert [g for g, (_, w) in zip(got, lanes) if w is None] == plain
    for g, (mix, mask) in zip(got, lanes):
        assert g == lane_reference.lane_metrics(TWICE, llc=WIDE, dram=DRAM,
                                                mix=mix, way_mask=mask)


def test_classes_that_share_a_block_keep_the_segment_program():
    """A co-runner span the NVDLA's trace reaches into: the classes
    would touch one another's blocks, so the partitioned lane does not
    fold and runs as masked segments, exact all the same."""
    region = sweep._corunner_spans(WIDE, MixConfig(1, "llc"))[0][1]
    segs = CUT + [traces.Segment(region, 32, 64)]
    mix, apart = MixConfig(2, "llc"), MixConfig(2, "dram")
    chunks = sweep.nvdla_chunks(segs)
    assert not sweep._classes_apart(chunks, WIDE, mix)
    assert sweep._classes_apart(chunks, WIDE, apart)
    before = tracing.counters()
    got = _batch([(mix, 0x0F), (apart, 0x0F)], segs=segs)
    grew = _grew(before)
    # the bucket runs as one program: segments, as its first lane must
    assert grew.get(tracing.PARTITIONED_RECORD_LANES, 0) == 0
    for g, m in zip(got, (mix, apart)):
        assert g == lane_reference.lane_metrics(segs, llc=WIDE, dram=DRAM,
                                                mix=m, way_mask=0x0F)

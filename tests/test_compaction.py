"""Compacted interference lanes (``sweep.compact_lane``,
``cache.record_lane_scan``): the arbiter's repeating pattern of one
NVDLA chunk and one chunk per co-runner, run as records, gives every
``LaneMetrics`` field bit for bit as the uncompacted lane program and
the plain per-access reference (``bench/reference``) give them.

The frame is cut to its first two ops: three NVDLA segments end on a
short chunk, op 1's weights start mid-block (so its chunks share their
boundary blocks), and on a 64-set LLC the co-runners wrap every 16
(``llc``) or 256 (``dram``) chunks."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from repro.campaign import (CampaignSpec, GeometrySpec, MixSpec, ModelSpec,
                            PointHooks, RetryPolicy, run_campaign)
from repro.campaign import spec as spec_module
from repro.core import sweep, traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import MixConfig, interference_lane_metrics_batch
from repro.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.entries.frame_grid import seeded_regions  # noqa: E402
from bench.reference import lane as ref_lane  # noqa: E402

with open(os.path.join(ROOT, "bench/configs/nvdla-soc-yolov3.json")) as f:
    CFG = json.load(f)
FRAME = traces.network_trace(max_ops=2)
DRAM = DRAMConfig()
WIDE = LLCConfig(size_bytes=32768, ways=8, block_bytes=64)
# four ways: a block op 1's chunks share waits out four co-runner chunks
# in its set, too many to fold, so that lane keeps those runs apart
NARROW = LLCConfig(size_bytes=16384, ways=4, block_bytes=64)
MIXES = [MixConfig(k, wss) for wss in ("l1", "llc", "dram")
         for k in range(5)]
LANES = [(WIDE, m) for m in MIXES] + [(NARROW, MixConfig(4, "dram"))]


def _batch(lanes):
    return interference_lane_metrics_batch(
        FRAME, llcs=[llc for llc, _ in lanes], drams=[DRAM] * len(lanes),
        mixes=[m for _, m in lanes])


@pytest.fixture(scope="module")
def compacted():
    before = tracing.counters()
    out = _batch(LANES)
    after = tracing.counters()
    return out, {k: after[k] - before.get(k, 0) for k in after}


@pytest.fixture(scope="module")
def uncompacted():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sweep, "_records_that_pay", lambda recs: None)
        return _batch(LANES)


def _reference(llc: LLCConfig, mix: MixConfig) -> dict:
    mem = ref_lane.Memory(llc.size_bytes, llc.ways, llc.block_bytes,
                          DRAM.banks, DRAM.row_bytes, DRAM.t_cas_cycles,
                          DRAM.t_rcd_cycles, DRAM.t_rp_cycles, 20)
    layout = ref_lane.corunner_layout(CFG["corunners"], mem, mix.corunners,
                                      mix.wss)
    victim = tuple(np.asarray(a, np.int64)
                   for a in zip(*map(traces.segment_tuple, FRAME)))
    return ref_lane.lane(victim, mem, layout, chunk_bursts=16, line_bytes=64)


def _lane(llc: LLCConfig, mix: MixConfig) -> sweep.LaneRecords:
    b, s, c, nv = sweep.corunner_meta(FRAME, llc=llc, mix=mix)
    return sweep.compact_lane(b, s, c, nv, llc,
                              1 + (0 if mix.wss == "l1" else mix.corunners))


@pytest.mark.parametrize("i", range(len(LANES)), ids=[
    f"{'ways4-' if llc is NARROW else ''}{m.wss}-x{m.corunners}"
    for llc, m in LANES])
def test_compacted_lane_matches_uncompacted_and_reference(compacted,
                                                          uncompacted, i):
    llc, mix = LANES[i]
    got = compacted[0][i]
    assert got == uncompacted[i]
    assert dataclasses.asdict(got) == _reference(llc, mix)


def test_the_batch_ran_compacted(compacted):
    """The records the program scanned, against the lanes' segments."""
    _, got = compacted
    records = [_lane(llc, m) for llc, m in LANES]
    raw = [len(sweep.corunner_meta(FRAME, llc=llc, mix=m)[2])
           for llc, m in LANES]
    assert got[tracing.LANE_SEGMENTS_RAW] == sum(raw)
    assert got[tracing.LANE_SEGMENTS] == sum(r.raw.shape[0] for r in records)
    assert [int(r.raw.sum()) for r in records] == raw
    assert 10 * got[tracing.LANE_SEGMENTS] < got[tracing.LANE_SEGMENTS_RAW]
    # the narrow lane folds less: its shared-block runs stay segments
    wide = records[MIXES.index(MixConfig(4, "dram"))]
    assert records[-1].raw.shape[0] > wide.raw.shape[0]


def test_records_break_at_segment_ends_and_wraps():
    """A dram-class lane of two co-runners: every record of several
    rounds keeps to one NVDLA segment, a wrapping round stays three
    segments of its own, and a segment's short last chunk closes the
    record before it."""
    mix = MixConfig(2, "dram")
    b, s, c, nv = sweep.corunner_meta(FRAME, llc=WIDE, mix=mix)
    r = _lane(WIDE, mix)
    multi = r.counts[:, 1] > 0
    assert multi.sum() > 4 and (r.counts[multi, 0] > r.chunks[multi, 0]).all()
    # the NVDLA member of each record lies inside one frame segment
    for base, count in zip(r.bases[multi, 0], r.counts[multi, 0]):
        assert any(seg.base <= base and base + count * 32
                   <= seg.base + seg.count * 32 for seg in FRAME)
    # short last chunks: some record's last repeat is shorter
    assert (r.counts[multi, 0] % r.chunks[multi, 0] != 0).any()
    # wraps: rounds of more than three segments stay plain segments
    rounds = np.diff(np.append(np.flatnonzero(nv), len(c)))
    assert (rounds > 3).sum() >= 2
    assert (~multi).sum() >= 3 * (rounds > 3).sum()
    assert int(r.raw.sum()) == len(c)


def test_window_lanes_stay_uncompacted():
    """The Fig. 6 window interleaves three NVDLA streams chunk by chunk:
    no chunk continues the one before it, so nothing compacts."""
    window = traces.default_dbb_window(max_bursts=2048)
    mix = MixConfig(4, "dram")
    b, s, c, nv = sweep.corunner_meta(window, llc=WIDE, mix=mix)
    r = sweep.compact_lane(b, s, c, nv, WIDE, 5)
    assert r.members == 1 and r.raw.shape[0] == len(c)


def test_sweep_interference_runs_the_whole_frame(monkeypatch):
    """``window_bursts=None`` replays the whole frame (cut here to its
    first op): every lane of the grid, compacted, in one batch."""
    frame = traces.network_trace(max_ops=1)
    monkeypatch.setattr(traces, "network_trace", lambda: frame)
    grid = sweep.sweep_interference(corunners=(0, 2), window_bursts=None)
    assert grid.window_bursts == traces.total_bursts(frame) == 189307
    lanes = interference_lane_metrics_batch(
        frame, llcs=[LLCConfig()] * 2, drams=[DRAM] * 2,
        mixes=[MixConfig(0, "l1"), MixConfig(2, "dram")])
    assert grid.sim_hit_rates[("l1", 2)] == lanes[0].nvdla_hit_rate
    assert grid.sim_hit_rates[("dram", 2)] == lanes[1].nvdla_hit_rate
    assert (grid.sim_row_hit_rates[("dram", 2)]
            == lanes[1].nvdla_miss_row_hit_rate)
    assert set(grid.slowdowns) == {"l1", "llc", "dram"}


REGIONS = (2048 * 7, traces.FMAP_REGION_A + 2048 * 3,
           traces.FMAP_REGION_B + 2048 * 40)


@pytest.fixture
def frame_of_one_op(monkeypatch):
    """The whole frame, as campaigns build it, cut to its first op."""
    full = traces.network_trace
    monkeypatch.setattr(traces, "network_trace",
                        lambda regions=traces.REGIONS:
                        full(max_ops=1, regions=regions))
    spec_module._model_trace.cache_clear()
    yield full(max_ops=1, regions=REGIONS)
    spec_module._model_trace.cache_clear()


class _FailFirstAttempt(PointHooks):
    """Fails the first attempt of every point, so each point's result
    comes from its retry."""

    def in_worker(self, point, attempt, run):
        if attempt == 0:
            raise RuntimeError("injected")
        return run()


@pytest.mark.parametrize("batch_points,hooks", [
    (3, None), (1, None), (3, _FailFirstAttempt())],
    ids=["batched", "one-point-chunks", "retried"])
def test_whole_frame_campaign_runs_journaled(tmp_path, frame_of_one_op,
                                             batch_points, hooks):
    """``ModelSpec(window_bursts=None)`` through ``run_campaign``: the
    frame (its first op, at moved regions) journaled point by point,
    every point as its compacted lane gives it, whether it ran in a
    batch, in a chunk of its own or as a retry."""
    model = ModelSpec(window_bursts=None, regions=REGIONS)
    spec = CampaignSpec(
        name="frame", models=(model,),
        geometries=(GeometrySpec(size_kib=32, block=64, ways=8),),
        mixes=tuple(MixSpec(k, "dram") for k in (0, 2, 4)))
    before = tracing.counters()
    res = run_campaign(spec, str(tmp_path), batch_points=batch_points,
                       hooks=hooks,
                       policy=RetryPolicy(max_retries=1, backoff_s=0.0))
    after = tracing.counters()
    assert res.manifest["counts"]["completed"] == 3
    assert model.trace() == frame_of_one_op
    want = interference_lane_metrics_batch(
        frame_of_one_op, llcs=[WIDE] * 3, drams=[DRAM] * 3,
        mixes=[MixConfig(k, "dram") for k in (0, 2, 4)])
    got = {p["params"]["mix"]["corunners"]: p["result"]
           for p in res.manifest["points"]}
    assert [got[k] for k in (0, 2, 4)] == [m.to_record() for m in want]
    with open(tmp_path / "journal.jsonl") as f:
        kinds = [json.loads(line)["kind"] for line in f]
    assert kinds.count("point") == 3
    grew = {k: after[k] - before.get(k, 0) for k in after}
    # every lane the campaign ran went through the compacted engine
    assert (10 * grew[tracing.LANE_SEGMENTS]
            < grew[tracing.LANE_SEGMENTS_RAW])


def test_model_regions_and_cut_hash_only_when_set():
    """A moved address map hashes into the point; the default map, and
    the whole frame it is cut from, hash as they did before."""
    plain = ModelSpec(window_bursts=None)
    moved = ModelSpec(window_bursts=None, regions=[0, 2048, 4096])
    assert "regions" not in plain.to_dict()
    assert set(plain.to_dict()) == set(ModelSpec().to_dict())
    assert moved.regions == (0, 2048, 4096)
    assert ModelSpec(**moved.to_dict()) == moved
    assert moved.to_dict() != plain.to_dict()
    with pytest.raises(ValueError):
        ModelSpec(window_bursts=None, regions=(0, 1))
    with pytest.raises(ValueError):
        ModelSpec(backend="npu", regions=(0, 2048, 4096))


# NVDLA segments that continue one another: a run of full chunks
# crosses the first two segment ends, a short tail chunk closes the
# third, a stride change and a mid-block base follow
SPLICED = [traces.Segment(0, 32, 640), traces.Segment(20480, 32, 480),
           traces.Segment(35840, 32, 400), traces.Segment(48640, 32, 320),
           traces.Segment(1 << 20, 64, 1000),
           traces.Segment((1 << 21) + 32, 32, 777)]
SEEDED = traces.network_trace(max_ops=2, regions=seeded_regions(
    CFG, 2**31 + 11, 32768))
BLOCK128 = LLCConfig(size_bytes=65536, ways=8, block_bytes=128)
# the llc co-runners' span is one chunk: every round ends or wraps it
SPAN16 = LLCConfig(size_bytes=2048, ways=2, block_bytes=64)
DIRECT = ([(f"{m.wss}-x{m.corunners}", FRAME, 16, WIDE, m) for m in MIXES]
          + [(f"ways4-dram-x{k}", FRAME, 16, NARROW, MixConfig(k, "dram"))
             for k in (2, 4)]
          + [(f"block128-{wss}-x{k}", FRAME, 16, BLOCK128, MixConfig(k, wss))
             for wss, k in (("llc", 2), ("dram", 3))]
          + [(f"seeded-{wss}-x{k}", SEEDED, 16, WIDE, MixConfig(k, wss))
             for wss, k in (("llc", 3), ("dram", 4))]
          + [(f"spliced-{wss}-x{k}", SPLICED, 16, WIDE, MixConfig(k, wss))
             for wss, k in (("l1", 0), ("llc", 4), ("dram", 2))]
          + [("chunks6-dram-x2", FRAME, 6, WIDE, MixConfig(2, "dram")),
             ("span16-llc-x2", SPLICED, 16, SPAN16, MixConfig(2, "llc"))])


@pytest.mark.parametrize("segs,chunk_bursts,llc,mix",
                         [c[1:] for c in DIRECT], ids=[c[0] for c in DIRECT])
def test_lane_records_match_the_folded_lane(segs, chunk_bursts, llc, mix):
    """``lane_records`` gives, field for field and in the same order,
    the records ``compact_lane`` folds from the expanded lane."""
    chunks = sweep.nvdla_chunks(segs, chunk_bursts)
    assert sweep._direct_records(chunks, llc, mix)
    got = sweep.lane_records(chunks, llc, mix)
    b, s, c, nv = sweep.corunner_meta(segs, llc=llc, mix=mix,
                                      chunk_bursts=chunk_bursts)
    want = sweep.compact_lane(b, s, c, nv, llc, 1 + (
        0 if mix.wss == "l1" else mix.corunners))
    for f in dataclasses.fields(want):
        g, w = getattr(got, f.name), getattr(want, f.name)
        assert g.dtype == w.dtype and np.array_equal(g, w), f.name
    assert int(got.raw.sum()) == c.shape[0]


def test_the_frame_batch_built_its_records_directly(compacted, monkeypatch):
    """Every lane of the frame batch takes ``lane_records``: the batch
    never expands a lane (``corunner_meta`` raises here) and gives the
    same metrics."""
    assert compacted[1][tracing.DIRECT_RECORD_LANES] == len(LANES)

    def expanded(*args, **kw):
        raise AssertionError("a lane whose records pay was expanded")

    monkeypatch.setattr(sweep, "corunner_meta", expanded)
    assert _batch(LANES) == compacted[0]


WINDOW = traces.default_dbb_window(max_bursts=2048)
# the frame's first segments, cut short: their chunks continue
CUT = [traces.Segment(s.base, s.stride, min(s.count, 300)) for s in FRAME]
BLOCK32 = LLCConfig(size_bytes=16384, ways=4, block_bytes=32)
SPAN8 = LLCConfig(size_bytes=1024, ways=2, block_bytes=64)


@pytest.mark.parametrize("segs,lanes,way_masks,direct", [
    (WINDOW, [(WIDE, MixConfig(2, "dram")), (WIDE, MixConfig(3, "llc"))],
     None, 0),
    (CUT, [(WIDE, MixConfig(2, "dram")), (WIDE, MixConfig(3, "llc"))],
     [0x0F, None], 2),
    (CUT, [(BLOCK32, MixConfig(2, "dram"))], None, 0),
    (CUT, [(SPAN8, MixConfig(2, "llc"))], None, 0),
    (CUT, [(BLOCK32, MixConfig(1, "dram")), (BLOCK32, MixConfig(0, "l1")),
           (WIDE, MixConfig(2, "llc"))], None, 2),
], ids=["window", "way-masked", "block32", "span8", "mixed"])
def test_lanes_build_directly_only_where_they_can(segs, lanes, way_masks,
                                                  direct):
    """A Fig. 6 window (no chunk continues another), co-runner lines
    wider than a 32 B block and a span shorter than a chunk keep the
    expanded lane; a way-masked batch builds its lanes' records directly
    as an unmasked one does, and a batch that mixes them builds the
    others' records directly.  Either way every lane gives what the
    plain per-access reference gives."""
    import lane_reference

    chunks = sweep.nvdla_chunks(segs)
    before = tracing.counters()
    got = interference_lane_metrics_batch(
        segs, llcs=[llc for llc, _ in lanes], drams=[DRAM] * len(lanes),
        mixes=[m for _, m in lanes], way_masks=way_masks)
    grew = (tracing.counters().get(tracing.DIRECT_RECORD_LANES, 0)
            - before.get(tracing.DIRECT_RECORD_LANES, 0))
    assert grew == direct
    for i, (llc, mix) in enumerate(lanes):
        wm = None if way_masks is None else way_masks[i]
        assert got[i] == lane_reference.lane_metrics(
            segs, llc=llc, dram=DRAM, mix=mix, way_mask=wm)
        if not sweep._direct_records(chunks, llc, mix):
            with pytest.raises(ValueError):
                sweep.lane_records(chunks, llc, mix)


@pytest.mark.parametrize("llc,mixes", [
    # solo lanes only: their records are plain segments, merged chunks
    (WIDE, [MixConfig(0, "l1"), MixConfig(0, "dram")]),
    # 64 ways x 3 members overflow int8 hit codes: int16 ones
    (LLCConfig(size_bytes=32768, ways=64, block_bytes=64),
     [MixConfig(0, "l1"), MixConfig(2, "dram")]),
], ids=["solo-lanes", "int16-codes"])
def test_other_batches_match_the_reference(llc, mixes):
    lanes = [(llc, m) for m in mixes]
    before = tracing.counters()
    got = _batch(lanes)
    after = tracing.counters()
    assert [dataclasses.asdict(m) for m in got] == [
        _reference(llc, m) for m in mixes]
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew[tracing.LANE_SEGMENTS] < grew[tracing.LANE_SEGMENTS_RAW]

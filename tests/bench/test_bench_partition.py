"""The way-partitioned whole-frame cell (``fig6-frame-dram-cat``) at a
test size: its reference is the plain per-access partitioned LRU, a
planted fault in its answers fails its check, both its controls read
above 0, and every lane runs as partitioned records."""
from __future__ import annotations

import copy
import dataclasses
import functools

import jax
import numpy as np
import pytest
from bench_helpers import ROOT, load, mini_root, run_cell, small_traffic

CAT = "fig6-frame-dram-cat"
# the op of the frame whose weights are streamed twice: the second pass
# re-reads what the first left in the LLC, where the co-runners do not
# evict it first
REUSE_OP = 14


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return mini_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("victim_mask,kept", [(0x01, False), (0x0F, True),
                                               (0x7F, True)])
def test_reference_is_the_per_access_partitioned_lru(victim_mask, kept):
    """``bench.reference.partition`` gives the lane the tests' own
    one-access-at-a-time partitioned LRU gives.  The victim re-reads its
    first 8 blocks after 48 others, 3 to a set: a partition of 4 ways or
    more keeps them from the co-runners, where the shared LLC and a
    partition of one way do not."""
    import lane_reference

    from bench.reference import lane as ref_lane
    from bench.reference import partition as ref_partition
    from repro.core import traces
    from repro.core.cache import LLCConfig
    from repro.core.dram import DRAMConfig
    from repro.core.sweep import MixConfig

    segs = [traces.Segment(0, 32, 16), traces.Segment(1 << 20, 32, 96),
            traces.Segment(0, 32, 16)]
    llc, dram = LLCConfig(8192, 8, 64), DRAMConfig()
    mix = MixConfig(2, "dram")
    mem = ref_lane.Memory(llc.size_bytes, llc.ways, llc.block_bytes,
                          dram.banks, dram.row_bytes, dram.t_cas_cycles,
                          dram.t_rcd_cycles, dram.t_rp_cycles, 20)
    victim = tuple(np.asarray(a, np.int64)
                   for a in zip(*map(traces.segment_tuple, segs)))
    layout = ref_lane.corunner_layout(lane_reference.CORUNNERS, mem, 2,
                                      "dram")
    got = ref_partition.lane(victim, mem, layout, chunk_bursts=16,
                             line_bytes=64, victim_mask=victim_mask,
                             corunner_mask=0xFF & ~victim_mask)
    want = lane_reference.lane_metrics(segs, llc=llc, dram=dram, mix=mix,
                                       way_mask=victim_mask)
    assert got == dataclasses.asdict(want)
    shared = ref_lane.lane(victim, mem, layout, chunk_bursts=16,
                           line_bytes=64)
    assert got["nvdla_hits"] == shared["nvdla_hits"] + 8 * kept


def _misses_one_hit(manifest):
    manifest = copy.deepcopy(manifest)
    r = manifest["points"][-1]["result"]
    r["nvdla_hits"] -= 1
    r["llc_hits"] -= 1
    return manifest


@pytest.mark.parametrize("fault", ["one_hit_lost", "last_lane_dropped",
                                   "partition_dropped"])
def test_fault_in_the_partitioned_cell_makes_the_run_incorrect(
        small, monkeypatch, fault):
    from bench import generator

    build = generator.build

    def broken(*args, **kwargs):
        cell = build(*args, **kwargs)
        if fault == "partition_dropped":
            # the campaign runs on the unpartitioned LLC
            cell.spec = dataclasses.replace(cell.spec, way_masks=(None,))
            return cell
        call = cell.call

        def wrong():
            manifest = call()
            if fault == "one_hit_lost":
                return _misses_one_hit(manifest)
            manifest = copy.deepcopy(manifest)
            manifest["points"] = manifest["points"][:-1]
            return manifest
        cell.call = wrong
        return cell

    monkeypatch.setattr(generator, "build", broken)
    rc, result, err = run_cell(small, CAT)
    assert rc == 0, err
    assert result["correct"] is False, err
    assert result["failed"] == result["attempted"]


@pytest.fixture
def reuse_op(monkeypatch):
    """Campaigns over the whole frame replay op ``REUSE_OP`` alone, from
    the bases of the address map."""
    from repro.campaign import spec
    from repro.core import traces
    from repro.core.runtime import CommandStream, compile_network

    op = compile_network().accel_ops[REUSE_OP]
    full = spec._model_trace

    @functools.lru_cache(maxsize=8)
    def cut(window_bursts, chunk_bursts, layer_index, regions):
        if window_bursts is None:
            return traces.network_trace(
                CommandStream(accel_ops=(op,), cpu_ops=()),
                regions=regions or traces.REGIONS)
        return full(window_bursts, chunk_bursts, layer_index, regions)

    monkeypatch.setattr(spec, "_model_trace", cut)


def test_partitioned_cell_runs_as_records_and_its_controls_read_above_zero(
        reuse_op):
    """One call on the node's own 2 MiB LLC over the op whose weights the
    partition keeps: every lane matches the reference (0), the control
    that ignores the masks and the one with DRAM rows per master do not,
    and every lane ran as partitioned records, far fewer than its
    segments."""
    from bench import generator
    from repro.utils import tracing

    config = load(ROOT / "bench" / "configs"
                  / "nvdla-soc-yolov3-frame-cat.json")
    config["dbb_ops"] = [config["dbb_ops"][REUSE_OP]]
    cell = generator.build(config, small_traffic(CAT), 2147483659,
                           jax.devices()[:1])
    try:
        before = tracing.counters()
        out = cell.call()
        after = tracing.counters()
        program = cell.check([out])
        ignored = cell.check([out], control="masks_ignored")
        rows = cell.check([out], control="rows_per_master")
        notes = cell.notes(out)
    finally:
        cell.close()
    assert program.correct, program.numbers
    assert set(v for v, _ in program.numbers.values()) == {0}
    assert not ignored.correct and not rows.correct
    assert ignored.numbers["llc_field_mismatches"][0] > 0
    assert rows.numbers["dram_field_mismatches"][0] > 0
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew["sweep.partitioned_record_lanes"] == 5
    assert grew["sweep.partitioned_lane_segments"] == grew[
        "sweep.lane_segments"]
    assert (10 * grew["sweep.lane_segments"]
            < grew["sweep.lane_segments_raw"])
    fenced = [n for n in notes if n.startswith("fenced 0x0f / 0xf0: frame")]
    assert len(fenced) == 5 and "131.9 ms solo" in fenced[0]
    # the fence holds the NVDLA's hits whatever runs beside it
    hits = {p["result"]["nvdla_hits"] for p in out["points"]}
    assert len(hits) == 1

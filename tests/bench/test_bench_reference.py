"""The plain references against the simulator at small sizes: they agree
on the same input, disagree on a perturbed one, and the controls (the
reference with one stated guarantee broken) fail the cells' checks."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from bench_helpers import ROOT, load, small_config, small_traffic

from bench import generator
from bench.reference import dram as ref_dram
from bench.reference import lane as ref_lane
from bench.reference import llc as ref_llc
from repro.core import traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig, access_latencies
from repro.core.soc import llc_config_for
from repro.core.sweep import (MixConfig, interference_lane_metrics,
                              segment_lane_hit_counts)

CFG = load(ROOT / "bench/configs/nvdla-soc-yolov3.json")
WINDOW = traces.default_dbb_window(max_bursts=1024, chunk_bursts=16,
                                   layer_index=40)


def _tuples(segments):
    return tuple(np.asarray(a, np.int64)
                 for a in zip(*map(traces.segment_tuple, segments)))


def _lane(segs, llc, dram, n, wss, **kw):
    mem = ref_lane.Memory(llc.size_bytes, llc.ways, llc.block_bytes,
                          dram.banks, dram.row_bytes, dram.t_cas_cycles,
                          dram.t_rcd_cycles, dram.t_rp_cycles, 20)
    layout = ref_lane.corunner_layout(CFG["corunners"], mem, n, wss)
    return ref_lane.lane(_tuples(segs), mem, layout, chunk_bursts=16,
                         line_bytes=64, **kw)


@pytest.mark.parametrize("llc,dram", [
    (LLCConfig(16 * 2 * 256, 2, 256), DRAMConfig()),
    (LLCConfig(16 * 4 * 128, 4, 128), DRAMConfig(banks=8, row_bytes=1024)),
    (LLCConfig(64 * 1024, 8, 64), DRAMConfig()),
])
@pytest.mark.parametrize("n,wss", [(0, "l1"), (1, "llc"), (2, "llc"),
                                   (2, "dram")])
def test_lane_matches_the_simulator(llc, dram, n, wss):
    got = interference_lane_metrics(WINDOW, llc=llc, dram=dram,
                                    mix=MixConfig(n, wss)).to_record()
    assert _lane(WINDOW, llc, dram, n, wss) == got
    moved = [dataclasses.replace(s, base=s.base + llc.block_bytes * 7)
             for s in WINDOW]
    assert _lane(moved, llc, dram, n, wss) != got


def test_shared_rows_are_what_co_runners_disturb():
    """Keeping rows per master changes the victim's row hits only where
    co-runners reach DRAM."""
    llc, dram = LLCConfig(), DRAMConfig()
    solo = _lane(WINDOW, llc, dram, 0, "l1")
    assert _lane(WINDOW, llc, dram, 0, "l1", rows="per_master") == solo
    shared = _lane(WINDOW, llc, dram, 2, "dram")
    apart = _lane(WINDOW, llc, dram, 2, "dram", rows="per_master")
    assert apart["nvdla_miss_row_hits"] > shared["nvdla_miss_row_hits"]
    assert apart["nvdla_miss_row_hits"] == solo["nvdla_miss_row_hits"]


def test_segment_hits_match_the_lane_engine():
    flat = [s for segs in traces.network_op_segments(max_ops=5) for s in segs]
    cfgs = [llc_config_for(s, b) for s in (0.5, 64) for b in (32, 128)]
    got = segment_lane_hit_counts(flat, cfgs)
    geoms = [(c.size_bytes, c.ways, c.block_bytes) for c in cfgs]
    np.testing.assert_array_equal(
        ref_lane.segment_hits(_tuples(flat), geoms), got)


def test_llc_and_dram_match_the_per_access_scans():
    from repro.core.cache import simulate_trace

    rng = np.random.default_rng(0)
    addrs = rng.integers(0, 1 << 16, 3000) * 32
    want = np.asarray(simulate_trace(addrs // 64, sets=16, ways=4))
    np.testing.assert_array_equal(
        ref_llc.hits(addrs, sets=16, ways=4, block_bytes=64), want)
    lat = np.asarray(access_latencies(addrs, banks=8, row_bytes=1024,
                                      t_cas=14, t_rcd=14, t_rp=14))
    np.testing.assert_array_equal(
        ref_dram.row_hits(addrs, banks=8, row_bytes=1024), lat == 14)


@pytest.mark.parametrize("traffic", ["frame-grid", "fig6-grid"])
def test_control_fails_the_check(traffic):
    """The program passes its check; the control does not."""
    cfg, tr = small_config("nvdla-soc-yolov3"), small_traffic(traffic)
    if traffic == "frame-grid":
        # FIFO departs from LRU from op 12 on, in the large caches
        tr.update(max_ops=14, sizes_kib=[1024, 4096], blocks=[128])
    cell = generator.build(cfg, tr, 1, None)
    try:
        out = cell.call()
        assert cell.check([out]).correct
        assert not cell.check([out], control=True).correct
    finally:
        cell.close()


@pytest.mark.parametrize("traffic", ["frame-grid", "fig6-grid"])
def test_a_moved_op_table_fails_the_check(traffic):
    """The reference builds its own inputs from the configuration's op
    table: an op without its output stream there fails the program's
    answers."""
    cfg, tr = small_config("nvdla-soc-yolov3"), small_traffic(traffic)
    if traffic == "frame-grid":
        tr.update(sizes_kib=[64], blocks=[64])
    cell = generator.build(cfg, tr, 0, None)
    try:
        out = cell.call()
        assert cell.check([out]).correct
        op = cell.layer if hasattr(cell, "layer") else 1
        cell.config = {**cfg, "dbb_ops": [list(o) for o in cfg["dbb_ops"]]}
        cell.config["dbb_ops"][op][2] = 0
        if hasattr(cell, "ops"):
            cell.ops = cell.config["dbb_ops"][:len(cell.ops)]
        assert not cell.check([out]).correct
    finally:
        cell.close()

"""Closed-form DRAM row model: bit-exact parity with the per-access
open-row scan on stride-run segments — fixed cases, the real YOLOv3
DBB stream, and (when Hypothesis is installed) randomized segment
lists covering warm carry, wraparound revisits, and sparse strides."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import traces
from repro.core.dram import DRAMConfig, access_latencies, segment_row_hits
from repro.core.traces import Segment


def _ref_row_hits(segments, cfg: DRAMConfig) -> int:
    lats = np.asarray(access_latencies(
        jnp.asarray(traces.expand(segments)), banks=cfg.banks,
        row_bytes=cfg.row_bytes, t_cas=cfg.t_cas_cycles,
        t_rcd=cfg.t_rcd_cycles, t_rp=cfg.t_rp_cycles))
    return int((lats == cfg.t_cas_cycles).sum())


def _assert_parity(segments, cfg):
    res = segment_row_hits(segments, cfg)
    assert res.row_hits == _ref_row_hits(segments, cfg)
    assert res.accesses == traces.total_bursts(segments)
    assert res.per_segment.sum() == res.row_hits
    return res


def test_sequential_stream_rows():
    cfg = DRAMConfig()
    res = _assert_parity([Segment(0, 32, 4096)], cfg)
    # 64 accesses per 2 KiB row, first of each row activates
    assert res.row_hits == 4096 - 4096 * 32 // cfg.row_bytes


def test_carry_across_segment_boundary():
    cfg = DRAMConfig()
    # second segment continues the same row: its first access must hit,
    # so the only activation in 16 accesses is the very first one
    segs = [Segment(0, 32, 8), Segment(256, 32, 8)]
    res = segment_row_hits(segs, cfg)
    assert res.row_hits == 15 == _ref_row_hits(segs, cfg)


def test_warm_revisit_and_disjoint_banks():
    cfg = DRAMConfig(banks=8, row_bytes=512)
    segs = [Segment(0, 32, 500), Segment(1 << 20, 64, 300),
            Segment(0, 32, 500), Segment(128, 32, 4)]
    _assert_parity(segs, cfg)


def test_sparse_stride_fallback():
    from repro.core.cache import LLCConfig, simulate_trace
    from repro.core.socsim import simulate_dbb_segments, simulate_dbb_stream
    from repro.core.sweep import UnsupportedTraceError

    cfg = DRAMConfig(banks=8, row_bytes=512)
    # stride > row_bytes: gappy rows, replayed exactly
    segs = [Segment(0, 4096, 100), Segment(17, 640, 333)]
    _assert_parity(segs, cfg)
    # strides past the LLC block: the segment pipeline's lane engine
    # refuses them, naming the stride, and the per-access pipeline
    # replays them — its total is the per-access LLC scan's hits plus
    # the DRAM latencies of its misses
    llc = LLCConfig()
    with pytest.raises(UnsupportedTraceError, match="stride 4096"):
        simulate_dbb_segments(segs, llc=llc, dram=cfg)
    addrs = traces.expand(segs)
    hit = np.asarray(simulate_trace(
        jnp.asarray((addrs // llc.block_bytes).astype(np.int32)),
        sets=llc.sets, ways=llc.ways))
    dram_lats = np.asarray(access_latencies(
        jnp.asarray(addrs[~hit]), banks=cfg.banks, row_bytes=cfg.row_bytes,
        t_cas=cfg.t_cas_cycles, t_rcd=cfg.t_rcd_cycles, t_rp=cfg.t_rp_cycles))
    got = simulate_dbb_stream(addrs, llc=llc, dram=cfg)
    assert 0 < int(hit.sum()) < addrs.shape[0]
    assert int(got.total_cycles) == 20 * addrs.shape[0] + int(dram_lats.sum())


def test_unaligned_bases():
    cfg = DRAMConfig(banks=4, row_bytes=256)
    _assert_parity([Segment(191, 48, 777), Segment(13, 96, 201)], cfg)


def test_open_rows_state_continuation():
    cfg = DRAMConfig(banks=8, row_bytes=512)
    a = [Segment(0, 32, 1000)]
    b = [Segment(16000, 32, 1000)]
    r1 = segment_row_hits(a, cfg)
    r2 = segment_row_hits(b, cfg, open_rows=r1.open_rows)
    assert r1.row_hits + r2.row_hits == _ref_row_hits(a + b, cfg)


def test_yolov3_stream_window_exact():
    cfg = DRAMConfig()
    segs = traces.window(traces.network_trace(max_ops=8), 200_000)
    _assert_parity(segs, cfg)


@pytest.mark.slow
def test_yolov3_full_frame_exact():
    cfg = DRAMConfig()
    segs = traces.network_trace()
    res = segment_row_hits(segs, cfg)
    assert res.row_hits == _ref_row_hits(segs, cfg)


# --------------------------------------------------------------------------
# segment-native pipeline totals (LLC + DRAM, no per-access replay)
# --------------------------------------------------------------------------
def _assert_pipeline_parity(segs, llc, dram=None):
    from repro.core.socsim import simulate_dbb_segments, simulate_dbb_stream

    got = simulate_dbb_segments(segs, llc=llc, dram=dram)
    ref = simulate_dbb_stream(traces.expand(segs), llc=llc, dram=dram)
    assert got.total_cycles == int(ref.total_cycles)
    lats = np.asarray(ref.latencies)
    assert got.llc_hits == int((lats == 20).sum())
    return got


def test_pipeline_totals_interleaved_window():
    from repro.core.cache import LLCConfig

    _assert_pipeline_parity(
        traces.default_dbb_window(max_bursts=1500, chunk_bursts=16),
        LLCConfig(size_bytes=4096, ways=4, block_bytes=64))


def test_pipeline_totals_warm_restream():
    from repro.core.cache import LLCConfig

    segs = [Segment(0, 32, 9000), Segment(0, 32, 9000),
            Segment(1 << 20, 32, 200)]
    _assert_pipeline_parity(
        segs, LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64),
        DRAMConfig(banks=8, row_bytes=1024))


def test_pipeline_totals_network_prefix():
    from repro.core.cache import LLCConfig

    segs = traces.window(traces.network_trace(max_ops=4), 40_000)
    got = _assert_pipeline_parity(
        segs, LLCConfig(size_bytes=256 * 1024, ways=8, block_bytes=64))
    assert 0.0 < got.llc_hit_rate < 1.0


def test_pipeline_rejects_row_straddling_blocks():
    from repro.core.cache import LLCConfig
    from repro.core.socsim import simulate_dbb_segments

    with pytest.raises(ValueError, match="row_bytes"):
        simulate_dbb_segments([Segment(0, 32, 64)],
                              llc=LLCConfig(size_bytes=4096, ways=4,
                                            block_bytes=96))


def test_property_random_segment_lists():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg = DRAMConfig(banks=4, row_bytes=256)
    seg_st = st.tuples(st.integers(0, 1 << 16),
                       st.integers(1, 512),
                       st.integers(0, 200))

    @given(st.lists(seg_st, max_size=8))
    @settings(max_examples=60, deadline=None)
    def check(metas):
        segs = [Segment(b, s, c) for b, s, c in metas]
        res = segment_row_hits(segs, cfg)
        assert res.row_hits == _ref_row_hits(segs, cfg)

    check()

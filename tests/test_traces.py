"""Compressed DBB traces + the segment lane engine: bit-exact parity
with the per-access reference simulator on every path of one lane (a
cold segment's closed form, the per-set round scan, the warm prefix and
its closed-form suffix), in hit counts per segment and in the final
LLC state."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sweep, traces
from repro.core.cache import (
    LLCConfig,
    _scan_trace,
    cold_state,
    hit_rate,
    segment_lane_scan,
    simulate_trace,
)
from repro.core.sweep import (
    UnsupportedTraceError,
    _masked_lane_run,
    partition_way_sels,
    segment_lane_hit_counts,
    segment_lane_hit_rates,
)
from repro.core.traces import Segment
from repro.utils.env import as_address_array, x64_enabled

CFG = LLCConfig(size_bytes=4096, ways=4, block_bytes=64)   # 16 sets


@functools.lru_cache(maxsize=8)
def _one_lane(sets: int, ways: int):
    return jax.jit(functools.partial(
        segment_lane_scan, max_sets=sets, max_ways=ways, r_pad=ways,
        return_state=True))


def _by_recency(tags, newest_first_key):
    """Each set's tags, most recently used first (never-filled ways,
    tag -1, last)."""
    order = np.argsort(newest_first_key, axis=1, kind="stable")
    return np.take_along_axis(np.asarray(tags), order, axis=1)


def _per_segment(bits, segs):
    bounds = np.cumsum([0] + [s.count for s in segs])
    return [int(bits[a:b].sum()) for a, b in zip(bounds[:-1], bounds[1:])]


def _assert_parity(segs, cfg, *, cold=None):
    """One lane, planned as the lane engines plan it (``_lane_plan``),
    must equal expanding + exact scanning: the same hits per segment,
    the same line in every way of every set, in the same LRU order
    (the lane keeps last-touch stamps where the reference keeps ages)."""
    r_needed, plan_cold = sweep._lane_plan(segs, [cfg])
    meta = np.asarray([traces.segment_tuple(s) for s in segs], np.int32)
    hits, (tags, ts) = _one_lane(cfg.sets, cfg.ways)(
        *(jnp.asarray(meta[:, k]) for k in range(3)),
        jnp.asarray(r_needed), jnp.asarray(plan_cold),
        cfg.sets, cfg.ways, cfg.block_bytes)
    blocks = (traces.expand(segs) // cfg.block_bytes).astype(np.int32)
    (ref_tags, ref_age), bits = _scan_trace(
        cold_state(cfg.sets, cfg.ways), jnp.asarray(blocks),
        sets=cfg.sets, ways=cfg.ways)
    assert np.asarray(hits).tolist() == _per_segment(np.asarray(bits), segs)
    np.testing.assert_array_equal(np.asarray(tags).T, ref_tags)
    np.testing.assert_array_equal(
        _by_recency(np.asarray(tags).T, -np.asarray(ts).T),
        _by_recency(ref_tags, ref_age))
    if cold is not None:
        assert plan_cold.tolist() == cold
    return int(np.asarray(hits).sum())


def test_closed_form_cold_sweep():
    # disjoint full sweep -> no rounds, the cold closed form alone;
    # hits = n - blocks
    hits = _assert_parity([Segment(0, 32, 20_000)], CFG, cold=[True])
    assert hits == 20_000 - 10_000


def test_restreamed_region_splits_prefix_suffix():
    # second pass over the same bytes: warm prefix round-scanned, the
    # provably-evicted suffix closed-formed
    segs = [Segment(0, 32, 20_000), Segment(0, 32, 20_000)]
    _assert_parity(segs, CFG, cold=[True, False])
    r_needed, _ = sweep._lane_plan(segs, [CFG])
    assert r_needed.tolist() == [0, CFG.ways]


def test_small_warm_segments_round_scan():
    _assert_parity([Segment(0, 32, 40), Segment(64, 32, 10),
                    Segment(0, 32, 40)], CFG, cold=[True, False, False])


def test_unaligned_bases_and_strides():
    _assert_parity([Segment(17, 32, 1000), Segment(5000, 48, 333)], CFG)


def test_stride_above_block_expands():
    # blocks a stride apart: only the per-access scan replays them; the
    # lane engine refuses the trace, naming the stride
    segs = [Segment(0, 256, 500)]
    with pytest.raises(UnsupportedTraceError, match="stride 256"):
        segment_lane_hit_counts(segs, [CFG])
    blocks = (traces.expand(segs) // CFG.block_bytes).astype(np.int32)
    assert int(simulate_trace(jnp.asarray(blocks), sets=CFG.sets,
                              ways=CFG.ways).sum()) == 0


def test_single_set_geometry():
    _assert_parity([Segment(0, 32, 500)],
                   LLCConfig(size_bytes=128, ways=2, block_bytes=64))
    _assert_parity([Segment(0, 32, 9000), Segment(0, 32, 9000)],
                   LLCConfig(size_bytes=128, ways=2, block_bytes=64))


def test_interleaved_window_parity():
    win = traces.default_dbb_window(max_bursts=1500, chunk_bursts=16)
    _assert_parity(win, CFG)


def test_network_trace_prefix_parity():
    segs = traces.window(traces.network_trace(max_ops=6), 50_000)
    _assert_parity(segs, LLCConfig(size_bytes=64 * 1024, ways=8,
                                   block_bytes=64))


def test_hit_rate_segments_matches_hit_rate():
    segs = [Segment(0, 32, 5000), Segment(1 << 20, 32, 3000)]
    (hr_seg,) = segment_lane_hit_rates(segs, [CFG])
    blocks = (traces.expand(segs) // CFG.block_bytes).astype(np.int32)
    assert abs(hr_seg - hit_rate(blocks, CFG)) < 1e-9


def test_network_trace_burst_accounting():
    stream_bursts = traces.total_bursts(traces.network_trace())
    # every AccelOp's traffic appears, to burst rounding, in the trace
    from repro.core.runtime import compile_network
    traffic = sum(op.total_traffic for op in compile_network().accel_ops)
    assert 0 <= stream_bursts - traffic // traces.BURST_BYTES < 10_000


def test_interleave_preserves_bursts_and_content():
    segs = traces.network_trace(max_ops=4)
    inter = traces.interleave(segs, 64)
    assert traces.total_bursts(inter) == traces.total_bursts(segs)
    assert sorted(traces.expand(inter).tolist()) == \
        sorted(traces.expand(segs).tolist())
    assert max(s.count for s in inter) <= 64


def test_window_clips_exactly():
    segs = traces.network_trace(max_ops=4)
    win = traces.window(segs, 12_345)
    assert traces.total_bursts(win) == 12_345
    np.testing.assert_array_equal(traces.expand(win),
                                  traces.expand(segs)[:12_345])


def test_window_drops_zero_count_segments():
    """Windowing at an exact chunk boundary (or over an already-empty
    input segment) must drop the degenerate record, not emit a count-0
    segment that expands to an empty array."""
    segs = [Segment(0, 32, 100), Segment(9999, 32, 0),
            Segment(1 << 16, 32, 100)]
    # clip lands exactly on the first segment's boundary
    win = traces.window(segs, 100)
    assert [s.count for s in win] == [100]
    # the zero-count input segment disappears, the clip still lands
    win = traces.window(segs, 150)
    assert [s.count for s in win] == [100, 50]
    assert all(s.count > 0 for s in win)
    assert len(traces.expand(win)) == 150
    assert as_address_array(traces.expand(win)).shape == (150,)


def test_split_never_emits_zero_count_chunks():
    assert Segment(0, 32, 0).split(16) == []
    chunks = Segment(0, 32, 48).split(16)
    assert [c.count for c in chunks] == [16, 16, 16]
    assert Segment(64, 32, 1).split(16)[0].count == 1


def test_per_segment_hits_and_miss_runs():
    # the last segment: one access per block, from an unaligned base
    segs = [Segment(0, 32, 3000), Segment(0, 32, 500),
            Segment(1 << 18, 32, 64), Segment(5000, 64, 100)]
    blocks = (traces.expand(segs) // CFG.block_bytes).astype(np.int32)
    bits = np.asarray(simulate_trace(jnp.asarray(blocks), sets=CFG.sets,
                                     ways=CFG.ways))
    ref = _per_segment(bits, segs)
    assert segment_lane_hit_counts(segs, [CFG])[0].tolist() == ref
    # the collecting lane's miss runs expand to exactly the missed
    # blocks, in access order
    b, s, c = (np.asarray(a, np.int64)
               for a in zip(*map(traces.segment_tuple, segs)))
    sels = partition_way_sels(np.ones(len(segs), bool), CFG,
                              (1 << CFG.ways) - 1)
    hits, (first, n_blocks, idx) = _masked_lane_run(b, s, c, CFG, sels)
    assert hits.tolist() == ref
    got = np.concatenate([np.arange(n) + f for f, n in zip(first, n_blocks)])
    np.testing.assert_array_equal(got, blocks[~bits])
    assert all(0 <= i < len(segs) for i in idx)


def test_network_op_segments_flatten_to_network_trace():
    per_op = traces.network_op_segments(max_ops=6)
    flat = [s for segs in per_op for s in segs]
    assert flat == traces.network_trace(max_ops=6)
    assert all(s.stream in ("weight", "ifmap", "ofmap")
               for segs in per_op for s in segs)


def test_zero_stride_rejected():
    with pytest.raises(ValueError, match="stride"):
        Segment(0, 0, 10)
    # a raw tuple skips the constructor; the lane engine refuses it
    with pytest.raises(UnsupportedTraceError, match="stride 0"):
        segment_lane_hit_counts([(0, 0, 10)], [CFG])


def test_segment_constructor_validation():
    with pytest.raises(ValueError, match="count"):
        Segment(0, 32, -5)
    with pytest.raises(ValueError, match="stride"):
        Segment(0, -32, 10)
    with pytest.raises(ValueError, match="base"):
        Segment(-64, 32, 10)
    # zero-count padding segments stay constructible with any base/stride
    assert Segment(0, 32, 0).count == 0


def test_segment_rejects_address_overflow():
    from repro.core.traces import DRAM_ADDR_BITS

    top = 1 << DRAM_ADDR_BITS
    with pytest.raises(ValueError, match="address space"):
        Segment(top, 32, 1)
    with pytest.raises(ValueError, match="address space"):
        Segment(top - 32, 64, 2)       # last access crosses the limit
    # the highest representable burst is fine
    assert Segment(top - 32, 32, 1).count == 1


def test_tuple_segments_bypass_unchanged():
    """Raw (base, stride, count) tuples are still accepted by the
    engines (the hypothesis strategies build them) — constructor
    validation applies to ``Segment`` objects only."""
    assert segment_lane_hit_counts([(0, 32, 64)], [CFG]).tolist() == [[32]]
    assert segment_lane_hit_rates([(0, 32, 64)], [CFG]).tolist() == [0.5]


def test_address_array_guards_overflow():
    small = as_address_array([0, 1 << 20])
    assert small.dtype in (jnp.int32, jnp.int64)
    if not x64_enabled():
        with pytest.raises(OverflowError):
            as_address_array([1 << 40])

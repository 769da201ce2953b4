"""The round-scan miss bits a collecting lane program hands back, by
block ordinal and as wide as a round's live ordinals rounded up to 128
(``segment_lane_scan(collect=True)``, ``sweep._lane_miss_runs``).

Each case checks the narrowed program and its decode against the full
width (``collect_width = max_sets``) decoded through the set-indexed
layout the program used to return, kept here as the reference, and the
batched lanes' ``LaneMetrics`` against the sequential engine's."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sweep, traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import (MixConfig, interference_lane_metrics,
                              interference_lane_metrics_batch)


def _dense_miss_runs(base, stride, count, llc: LLCConfig, cold, dense, *,
                     full_prefix: bool = False) -> tuple:
    """Missed-block runs from set-indexed bits, (S, r_pad, max_sets):
    entry (j, k, s) is True iff round k of segment j missed in set s."""
    bb, sets, ways = llc.block_bytes, llc.sets, llc.ways
    n_seg = base.shape[0]
    live = count > 0
    b_first = base // bb
    b_last = (base + np.maximum(count - 1, 0) * stride) // bb
    nb = np.where(live, b_last - b_first + 1, 0)
    if full_prefix:
        n_pre = nb
    else:
        n_pre = np.where(np.asarray(cold[:n_seg], bool), 0,
                         np.minimum(nb, ways * sets))
    sj, kj, cj = np.nonzero(dense[:n_seg])
    ordv = ((cj.astype(np.int64) - b_first[sj]) % sets
            + kj.astype(np.int64) * sets)
    order = np.lexsort((ordv, sj))
    sj, ordv = sj[order].astype(np.int64), ordv[order]
    first = np.ones(sj.shape[0], bool)
    if sj.shape[0]:
        first[1:] = (sj[1:] != sj[:-1]) | (ordv[1:] != ordv[:-1] + 1)
    pos = np.flatnonzero(first)
    run_seg, run_ord = sj[pos], ordv[pos]
    run_len = np.diff(np.append(pos, sj.shape[0]))
    suf_seg = np.flatnonzero(live & (nb > n_pre))
    suf_len = (nb - n_pre)[suf_seg]
    at = np.searchsorted(run_seg, suf_seg, side="right") - 1
    has_pre = (at >= 0) & (run_seg[np.maximum(at, 0)] == suf_seg)
    at_m = at[has_pre]
    merge = np.zeros(suf_seg.shape[0], bool)
    merge[has_pre] = (run_ord[at_m] + run_len[at_m]) == n_pre[suf_seg[has_pre]]
    run_len[at[merge]] += suf_len[merge]
    run_seg = np.concatenate([run_seg, suf_seg[~merge]])
    run_ord = np.concatenate([run_ord, n_pre[suf_seg[~merge]]])
    run_len = np.concatenate([run_len, suf_len[~merge]])
    order = np.lexsort((run_ord, run_seg))
    run_seg, run_ord, run_len = (a[order] for a in
                                 (run_seg, run_ord, run_len))
    return b_first[run_seg] + run_ord, run_len.astype(np.int64), run_seg


def _set_indexed(full, bases, llc: LLCConfig, max_sets: int) -> np.ndarray:
    """Full-width ordinal bits (S, r_pad, max_sets) of one lane back to
    the set-indexed layout: ordinal k*sets + i lies in set b_first + i."""
    sets = llc.sets
    b_first = np.asarray(bases, np.int64) // llc.block_bytes
    where = (b_first[:, None] + np.arange(sets)[None, :]) % sets
    dense = np.zeros(full.shape[:2] + (max_sets,), bool)
    np.put_along_axis(dense, np.broadcast_to(where[:, None, :],
                                             full.shape[:2] + (sets,)),
                      full[:, :, :sets], axis=2)
    return dense


def _capture_programs(monkeypatch) -> list:
    made, got = sweep._lane_engine, []

    def engine(*static, **kw):
        program = made(*static, **kw)

        def run(*arrays):
            got.append((static, kw, arrays))
            return program(*arrays)
        return run
    monkeypatch.setattr(sweep, "_lane_engine", engine)
    return got


def _cold_program(nv, llcs, mix, chunk_bursts):
    """One shared trace over every lane, planned by ``_lane_plan`` so
    that segments disjoint from all before them run cold (no rounds)."""
    segs, _ = sweep.corunner_segments(nv, llc=llcs[0], mix=mix,
                                      chunk_bursts=chunk_bursts)
    r_needed, cold = sweep._lane_plan(segs, llcs)
    meta = np.asarray([traces.segment_tuple(s) for s in segs], np.int64)
    b, s, c = meta[:, 0], meta[:, 1], meta[:, 2]
    sets, ways, blocks, max_sets, max_ways = sweep._geometry_arrays(llcs)
    n_pre = np.zeros(c.shape[0], np.int64)
    for llc in llcs:
        nb = (b + (c - 1) * s) // llc.block_bytes - b // llc.block_bytes + 1
        n_pre = np.maximum(n_pre, np.where(cold, 0, np.minimum(nb, llc.sets)))
    width = sweep._collect_width(int(n_pre.max()), max_sets)
    static = (max_sets, max_ways, max(1, int(r_needed.max())), False)
    kw = dict(collect=True, suffix="full", collect_width=width)
    arrays = (jnp.asarray(b, jnp.int32), jnp.asarray(s, jnp.int32),
              jnp.asarray(c, jnp.int32), jnp.asarray(r_needed),
              jnp.asarray(cold), sets, ways, blocks)
    lanes = [(b, s, c, cold, False) for _ in llcs]
    return static, kw, arrays, lanes


CASES = {
    # a 16-burst chunk spans at most 16 blocks of a 512-set LLC
    "chunked-512-sets": dict(
        llcs=[LLCConfig(512 * 64 * w, w, 64) for w in (2, 4, 2)],
        mixes=[MixConfig(0, "l1"), MixConfig(2, "llc"), MixConfig(1, "dram")],
        chunk_bursts=16, max_sets=512, width=128),
    # 128 and 64 sets share a bucket: the 64-set lane leaves i >= 64 dark
    "two-set-counts": dict(
        llcs=[LLCConfig(128 * 64 * 2, 2, 64), LLCConfig(64 * 64 * 4, 4, 64),
              LLCConfig(64 * 64 * 2, 2, 64)],
        mixes=[MixConfig(1, "llc"), MixConfig(2, "dram"), MixConfig(0, "l1")],
        chunk_bursts=64, max_sets=128, width=128),
    # 1024-burst chunks of two long streams on 256 sets: 512 blocks an
    # NVDLA chunk, 1024 a co-runner's, so a masked segment needs two to
    # four rounds and a round's ordinals fill every set
    "way-masked-wide-segments": dict(
        llcs=[LLCConfig(256 * 64 * 4, 4, 64)] * 3,
        mixes=[MixConfig(1, "llc"), MixConfig(2, "dram"), MixConfig(0, "l1")],
        way_masks=[0b0011, None, 0b1000],
        nvdla=[traces.Segment(0x1000_0000 + i * 0x10_0000, 32, 2048, "w")
               for i in range(2)],
        chunk_bursts=1024, max_sets=256, width=256),
    "fewer-than-128-sets": dict(
        llcs=[LLCConfig(32 * 64 * w, w, 64) for w in (1, 4)],
        mixes=[MixConfig(2, "llc"), MixConfig(3, "dram")],
        chunk_bursts=16, max_sets=32, width=32),
    "cold-segments": dict(
        llcs=[LLCConfig(512 * 64 * w, w, 64) for w in (1, 2)],
        mixes=[MixConfig(2, "dram")] * 2,
        chunk_bursts=16, max_sets=512, width=128, cold=True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_narrow_miss_bits_decode_like_the_set_layout(monkeypatch, case):
    spec = CASES[case]
    llcs, mixes = spec["llcs"], spec["mixes"]
    way_masks = spec.get("way_masks")
    chunk = spec["chunk_bursts"]
    nv = spec.get("nvdla") or traces.default_dbb_window(max_bursts=512)
    drams = [DRAMConfig()] * len(llcs)
    (bucket,) = sweep.lane_buckets(llcs)

    got = _capture_programs(monkeypatch)
    batch = interference_lane_metrics_batch(
        nv, llcs=llcs, drams=drams, mixes=mixes, chunk_bursts=chunk,
        way_masks=way_masks)
    for i, (llc, dram, mix) in enumerate(zip(llcs, drams, mixes)):
        ref = interference_lane_metrics(
            nv, llc=llc, dram=dram, mix=mix, chunk_bursts=chunk,
            way_mask=None if way_masks is None else way_masks[i])
        assert batch[i].to_record() == ref.to_record(), f"lane {i}"
    monkeypatch.undo()

    if spec.get("cold"):
        static, kw, arrays, lanes = _cold_program(nv, llcs, mixes[0], chunk)
        assert np.asarray(arrays[4]).any()
    else:
        ((static, kw, arrays),) = got
        lanes = []
        for i in bucket:
            b, s, c, _ = sweep.corunner_meta(nv, llc=llcs[i], mix=mixes[i],
                                             chunk_bursts=chunk)
            lanes.append((b, s, c, np.zeros(c.shape[0], bool),
                          way_masks is not None
                          and way_masks[i] is not None))
    max_sets, r_pad, width = static[0], static[2], spec["width"]
    assert (max_sets, kw["collect_width"]) == (spec["max_sets"], width)
    assert (r_pad > 1) == (case == "way-masked-wide-segments")

    program = sweep._lane_engine(*static, **kw)
    full_program = sweep._lane_engine(*static,
                                      **{**kw, "collect_width": max_sets})
    shape = jax.eval_shape(program, *arrays)[1].shape
    assert shape == (len(bucket), arrays[2].shape[-1], r_pad, width)
    narrow = np.asarray(program(*arrays)[1])
    full = np.asarray(full_program(*arrays)[1])
    # no live ordinal lies past the width, and the narrow bits are the
    # leading ordinals of the full ones
    assert not full[..., width:].any()
    np.testing.assert_array_equal(narrow, full[..., :width])
    for row, i in enumerate(bucket):
        b, s, c, cold, full_prefix = lanes[row]
        want = _dense_miss_runs(
            b, s, c, llcs[i], cold,
            _set_indexed(full[row], np.asarray(arrays[0])[row]
                         if arrays[0].ndim == 2 else arrays[0],
                         llcs[i], max_sets),
            full_prefix=full_prefix)
        runs = sweep._lane_miss_runs(b, s, c, llcs[i], cold, narrow[row],
                                     full_prefix=full_prefix)
        assert want[0].shape[0] > 0
        for a, w in zip(runs, want):
            np.testing.assert_array_equal(a, w)


@pytest.mark.parametrize("max_sets, width", [
    (1, 1), (3, 3), (64, 64), (100, 100), (512, 128), (4096, 128),
    (4096, 4096)])
def test_by_ordinal_is_the_rotation_of_a_round(max_sets, width):
    """``cache._by_ordinal`` against its definition, on set counts up to
    ``max_sets`` that need not be powers of two."""
    from repro.core.cache import _by_ordinal

    rng = np.random.default_rng(max_sets * 7919 + width)
    narrow = jax.jit(_by_ordinal, static_argnums=3)
    i = np.arange(width)
    for _ in range(12):
        sets = int(rng.integers(1, max_sets + 1))
        b_first = int(rng.integers(0, 1 << 24))
        miss = np.zeros(max_sets, bool)
        miss[:sets] = rng.random(sets) < 0.5
        got = narrow(jnp.asarray(miss), jnp.int32(b_first), jnp.int32(sets),
                     width)
        want = np.where(i < sets, miss[(b_first + i) % sets], False)
        np.testing.assert_array_equal(np.asarray(got), want)

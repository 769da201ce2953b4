"""Batched sweep engine + early-exit FAME-1 scheduler.

Parity requirements (no Hypothesis — these must run everywhere):
* vmapped padded-geometry simulation == per-config unbatched scans,
  bit for bit;
* early-exit chunked FAME-1 replay == the seed's fixed schedule,
  bit for bit, with and without stalls (including all-stall cycles
  that pre-compaction drops);
* sweep drivers keep the paper-anchored closed-form grids intact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lane_reference
from repro.core import traces
from repro.core.cache import LLCConfig, simulate_trace
from repro.core.fame1 import Component, FAME1Pipeline
from repro.core.socsim import simulate_dbb_stream
from repro.core.sweep import (
    LaneMetrics,
    MixConfig,
    SweepGrid,
    batched_hits,
    batched_hit_rates,
    corunner_segments,
    grid_configs,
    interference_lane_metrics,
    interference_lane_metrics_batch,
    step_lane_metrics,
    segment_lane_hit_counts,
    segment_lane_hit_rates,
    sweep_interference,
    sweep_llc,
)

# the expanded-trace lanes stay in service as parity oracles — their
# deprecation warning is expected here (and asserted explicitly below)
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

LLC = LLCConfig(size_bytes=4096, ways=4, block_bytes=64)


def _window(n=768):
    return traces.expand(traces.default_dbb_window(max_bursts=n))


# --------------------------------------------------------------------------
# vmapped sweeps
# --------------------------------------------------------------------------
def test_batched_hits_bitwise_parity_with_per_config_loop():
    addrs = _window()
    configs = list(grid_configs((0.5, 8, 64), (32, 64, 128)).values())
    got = np.asarray(batched_hits(addrs, configs))
    for i, c in enumerate(configs):
        blocks = jnp.asarray((addrs // c.block_bytes).astype(np.int32))
        ref = np.asarray(simulate_trace(blocks, sets=c.sets, ways=c.ways))
        np.testing.assert_array_equal(got[i], ref, err_msg=str(c))


def test_batched_hit_rates_block_size_ordering():
    addrs = _window()
    configs = [LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=b)
               for b in (32, 64, 128)]
    r32, r64, r128 = np.asarray(batched_hit_rates(addrs, configs))
    assert r32 < r64 < r128, "spatial locality must grow with block size"


def test_segment_sweep_matches_expanded_scans():
    segs = traces.window(traces.network_trace(max_ops=3), 30_000)
    addrs = traces.expand(segs)
    configs = list(grid_configs((0.5, 64), (32, 128)).values())
    got = segment_lane_hit_rates(segs, configs)
    for i, c in enumerate(configs):
        blocks = jnp.asarray((addrs // c.block_bytes).astype(np.int32))
        ref = float(jnp.mean(simulate_trace(
            blocks, sets=c.sets, ways=c.ways).astype(jnp.float32)))
        assert abs(got[i] - ref) < 1e-6, c


# --------------------------------------------------------------------------
# segment-lane engine (traced geometry)
# --------------------------------------------------------------------------
def test_segment_lanes_bitwise_parity_every_grid_geometry():
    """The satellite parity requirement: segment-lane sweep hit rates
    equal ``batched_hit_rates`` on the expanded trace for every grid
    geometry — counts bit-identical, not approximately."""
    segs = traces.window(traces.network_trace(max_ops=4), 25_000)
    addrs = traces.expand(segs)
    configs = list(grid_configs((0.5, 8, 64, 1024),
                                (32, 64, 128, 256)).values())
    counts = segment_lane_hit_counts(segs, configs)
    bits = np.asarray(batched_hits(addrs, configs))
    np.testing.assert_array_equal(counts.sum(axis=1), bits.sum(axis=1))
    rates = segment_lane_hit_rates(segs, configs)
    np.testing.assert_allclose(
        rates, bits.mean(axis=1, dtype=np.float64), atol=0)


def test_segment_lanes_per_segment_attribution():
    segs = [traces.Segment(0, 32, 3000), traces.Segment(0, 32, 500),
            traces.Segment(1 << 18, 32, 64)]
    addrs = traces.expand(segs)
    configs = [LLCConfig(4096, 4, 64), LLCConfig(64 * 1024, 8, 128)]
    counts = segment_lane_hit_counts(segs, configs)
    for i, c in enumerate(configs):
        blocks = jnp.asarray((addrs // c.block_bytes).astype(np.int32))
        bits = np.asarray(simulate_trace(blocks, sets=c.sets, ways=c.ways))
        o, ref = 0, []
        for s in segs:
            ref.append(int(bits[o:o + s.count].sum()))
            o += s.count
        assert counts[i].tolist() == ref


def test_segment_lanes_per_lane_traces():
    """Fig. 6 shape: one geometry, per-lane traces padded to the
    longest lane with no-op segments."""
    llc = LLCConfig(64 * 1024, 8, 64)
    nv = traces.default_dbb_window(max_bursts=768)
    lanes, refs = [], []
    for n in (0, 2):
        segs, _ = corunner_segments(nv, llc=llc, mix=MixConfig(n, "dram"),
                                    chunk_bursts=16)
        lanes.append(segs)
        blocks = (traces.expand(segs) // llc.block_bytes).astype(np.int32)
        refs.append(int(np.asarray(simulate_trace(
            jnp.asarray(blocks), sets=llc.sets, ways=llc.ways)).sum()))
    counts = segment_lane_hit_counts(lanes, [llc, llc])
    assert counts.sum(axis=1).tolist() == refs


def test_segment_lanes_rejects_sparse_strides():
    with np.testing.assert_raises(ValueError):
        segment_lane_hit_counts([traces.Segment(0, 256, 100)],
                                [LLCConfig(4096, 4, 64)])


def test_sweep_llc_full_trace_mode():
    """window_bursts=None runs the whole-network compressed trace."""
    sw = sweep_llc(sizes_kib=(8,), blocks=(64,), window_bursts=None)
    frame_bursts = traces.total_bursts(traces.network_trace())
    assert sw.window_bursts == frame_bursts
    (rate,) = sw.sim_hit_rates.values()
    assert 0.0 < rate < 1.0


def test_sweep_llc_keeps_closed_form_grid_and_adds_sim():
    from repro.core.soc import llc_sweep

    sizes, blocks = (0.5, 1024), (32, 64)
    sw = sweep_llc(sizes_kib=sizes, blocks=blocks, window_bursts=512)
    ref = llc_sweep(sizes_kib=sizes, blocks=blocks)
    assert sw.kind == "llc"
    assert sw.no_llc_s == ref["no_llc_s"]
    assert sw.speedups == ref["grid"]
    assert set(sw.sim_hit_rates) == set(ref["grid"])
    assert all(0.0 <= v <= 1.0 for v in sw.sim_hit_rates.values())


def test_sweep_interference_keeps_closed_form_and_degrades_rows():
    sw = sweep_interference(corunners=(0, 4), window_bursts=1024)
    assert sw.kind == "interference"
    assert all(abs(v - 1.0) < 1e-9 for v in sw.slowdowns["l1"].values())
    assert sw.slowdowns["dram"][4] > sw.slowdowns["llc"][4] > 1.0
    # simulated DRAM row locality: untouched by L1-fitting co-runners,
    # degraded by DRAM-fitting ones
    rh = sw.sim_row_hit_rates
    assert rh[("l1", 4)] == rh[("l1", 0)]
    assert rh[("dram", 4)] < rh[("dram", 0)]


# --------------------------------------------------------------------------
# typed sweep-result API + batched lane programs
# --------------------------------------------------------------------------
def test_expanded_trace_lanes_emit_deprecation_warning():
    addrs = _window(256)
    configs = [LLC]
    with pytest.warns(DeprecationWarning, match="expanded-trace"):
        batched_hits(addrs, configs)
    with pytest.warns(DeprecationWarning, match="expanded-trace"):
        batched_hit_rates(addrs, configs)


def test_lane_metrics_record_round_trip():
    nv = traces.default_dbb_window(max_bursts=512)
    from repro.core.dram import DRAMConfig

    m = interference_lane_metrics(nv, llc=LLC, dram=DRAMConfig(),
                                  mix=MixConfig(2, "llc"))
    rec = m.to_record()
    assert isinstance(rec, dict) and set(rec) == set(
        LaneMetrics._INT_FIELDS) | set(LaneMetrics._FLOAT_FIELDS)
    # json round-trip (what the campaign journal does) is lossless
    import json

    back = LaneMetrics.from_record(json.loads(json.dumps(rec)))
    assert back == m
    for f in LaneMetrics._INT_FIELDS:
        assert isinstance(getattr(back, f), int), f
    for f in LaneMetrics._FLOAT_FIELDS:
        assert isinstance(getattr(back, f), float), f
    with pytest.raises(KeyError):
        LaneMetrics.from_record({k: v for k, v in rec.items()
                                 if k != "accesses"})


def test_sweep_grid_record_round_trip():
    import json

    sw = sweep_interference(corunners=(0, 2), window_bursts=256)
    back = SweepGrid.from_record(json.loads(json.dumps(sw.to_record())))
    assert back == sw
    sw = sweep_llc(sizes_kib=(8,), blocks=(64,), window_bursts=256)
    back = SweepGrid.from_record(json.loads(json.dumps(sw.to_record())))
    assert back == sw


def test_batched_lane_metrics_bit_identical_to_sequential():
    """The tentpole parity requirement: one vmapped lane program over a
    mixed bucket of geometries/mixes/DRAM specs returns *exactly* the
    LaneMetrics the per-access reference computes for each lane alone,
    field for field."""
    from repro.core.dram import DRAMConfig

    nv = traces.default_dbb_window(max_bursts=512)
    llcs, drams, mixes = [], [], []
    for w in (1, 2, 4, 8):
        for mix in (MixConfig(0, "l1"), MixConfig(2, "llc"),
                    MixConfig(3, "dram")):
            llcs.append(LLCConfig(64 * 64 * w, w, 64))
            drams.append(DRAMConfig())
            mixes.append(mix)
    # a second bucket: different sets count + non-default DRAM timing
    llcs.append(LLCConfig(128 * 64 * 2, 2, 64))
    drams.append(DRAMConfig(banks=16, row_bytes=1024))
    mixes.append(MixConfig(1, "llc"))
    batch = interference_lane_metrics_batch(nv, llcs=llcs, drams=drams,
                                            mixes=mixes)
    for i, (llc, dram, mix) in enumerate(zip(llcs, drams, mixes)):
        ref = lane_reference.lane_metrics(nv, llc=llc, dram=dram, mix=mix)
        assert batch[i] == ref, f"lane {i}: {llc} {mix}"


@pytest.mark.parametrize("way_mask", [None, 0x3], ids=["shared", "masked"])
def test_narrow_block_corunner_lane_matches_reference(way_mask):
    """Co-runners write 64 B lines; beside a 32 B LLC block each line is
    a sparse stride, which the lane entry points replay one access a
    segment: the lane and the farm's per-chunk latencies equal the
    per-access reference, and the lane keeps the unsplit segment count."""
    from repro.core.dram import DRAMConfig
    from repro.core.sweep import lane_request_latencies, nvdla_chunks

    nv = traces.default_dbb_window(max_bursts=256)
    llc, dram = LLCConfig(8192, 4, 32), DRAMConfig()
    for mix in (MixConfig(2, "llc"), MixConfig(1, "dram")):
        ref = lane_reference.lane_metrics(nv, llc=llc, dram=dram, mix=mix,
                                          way_mask=way_mask)
        assert interference_lane_metrics(nv, llc=llc, dram=dram, mix=mix,
                                         way_mask=way_mask) == ref, mix
        lat, got = lane_request_latencies(nv, llc=llc, dram=dram, mix=mix,
                                          way_mask=way_mask)
        assert got == ref, mix
        assert lat.shape[0] == nvdla_chunks(nv)[0].shape[0]


def test_corunner_meta_matches_corunner_segments():
    """The array-native trace builder must emit the same interleaved
    lane, segment for segment, as the Segment-object builder — across
    wss classes, co-runner counts, chunk sizes, and spans small enough
    to hit the multi-wrap fallback."""
    from repro.core.sweep import corunner_meta
    from repro.core.traces import segment_tuple

    nv = traces.default_dbb_window(max_bursts=256)
    for size in (512, 2048, 65536):
        for mix in (MixConfig(0, "l1"), MixConfig(1, "llc"),
                    MixConfig(3, "llc"), MixConfig(2, "dram")):
            for chunk in (4, 16, 33):
                llc = LLCConfig(size, 2, 64)
                segs, nv_ref = corunner_segments(nv, llc=llc, mix=mix,
                                                 chunk_bursts=chunk)
                ref = np.asarray([segment_tuple(s) for s in segs],
                                 np.int64).reshape(-1, 3)
                b, s, c, m = corunner_meta(nv, llc=llc, mix=mix,
                                           chunk_bursts=chunk)
                label = f"size={size} mix={mix} chunk={chunk}"
                np.testing.assert_array_equal(b, ref[:, 0], err_msg=label)
                np.testing.assert_array_equal(s, ref[:, 1], err_msg=label)
                np.testing.assert_array_equal(c, ref[:, 2], err_msg=label)
                np.testing.assert_array_equal(
                    m, np.asarray(nv_ref, bool), err_msg=label)


def test_batched_lane_metrics_empty_and_length_checks():
    assert interference_lane_metrics_batch(
        traces.default_dbb_window(max_bursts=64),
        llcs=[], drams=[], mixes=[]) == []
    from repro.core.dram import DRAMConfig

    with pytest.raises(ValueError):
        interference_lane_metrics_batch(
            traces.default_dbb_window(max_bursts=64),
            llcs=[LLC], drams=[DRAMConfig(), DRAMConfig()],
            mixes=[MixConfig()])


# --------------------------------------------------------------------------
# early-exit FAME-1 scheduler
# --------------------------------------------------------------------------
def _pipeline():
    accel = Component("nvdla", lambda s, x: (s + 1, x * 2.0),
                      jnp.int32(0), jnp.float32(0.0))
    mem = Component("memmodel", lambda s, x: (s + x, x + s),
                    jnp.float32(0.0), jnp.float32(0.0))
    return FAME1Pipeline([accel, mem])


def test_early_exit_equals_fixed_schedule_no_stalls():
    tokens = jnp.arange(1.0, 33.0)
    pipe = _pipeline()
    s_ref, out_ref, n_ref = pipe.run(tokens, early_exit=False)
    fixed_cycles = pipe.last_host_cycles
    s_fast, out_fast, n_fast = pipe.run(tokens, early_exit=True)
    assert int(n_ref) == int(n_fast) == 32
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_fast))
    np.testing.assert_array_equal(np.asarray(s_ref[0]), np.asarray(s_fast[0]))
    np.testing.assert_array_equal(np.asarray(s_ref[1]), np.asarray(s_fast[1]))
    assert pipe.last_host_cycles < fixed_cycles / 3, \
        "early exit must skip most of the 4*T*(n+1) schedule"


def test_early_exit_equals_fixed_under_random_stalls():
    tokens = jnp.arange(1.0, 17.0)
    pipe = _pipeline()
    for seed in range(6):
        stalls = jax.random.bernoulli(
            jax.random.PRNGKey(seed), 0.45, (16 * 8, 2))
        _, out_ref, n_ref = pipe.run(tokens, host_stalls=stalls,
                                     early_exit=False)
        _, out_fast, n_fast = pipe.run(tokens, host_stalls=stalls,
                                       early_exit=True)
        assert int(n_ref) == int(n_fast)
        np.testing.assert_array_equal(np.asarray(out_ref),
                                      np.asarray(out_fast))


def test_all_stall_cycles_are_compacted_away():
    tokens = jnp.arange(1.0, 9.0)
    pipe = _pipeline()
    h = 8 * 8
    # every other host cycle stalls *all* components
    stalls = jnp.zeros((h, 2), bool).at[::2].set(True)
    _, out_ref, n_ref = pipe.run(tokens, host_stalls=stalls,
                                 early_exit=False)
    _, out_fast, n_fast = pipe.run(tokens, host_stalls=stalls,
                                   early_exit=True)
    assert int(n_ref) == int(n_fast) == 8
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_fast))
    assert pipe.last_host_cycles <= h // 2, \
        "compaction must drop the all-stall cycles before simulating"


def test_dbb_stream_early_exit_parity_and_host_cycles():
    addrs = traces.expand(traces.default_dbb_window(max_bursts=96))
    ref = simulate_dbb_stream(addrs, llc=LLC, early_exit=False)
    fast = simulate_dbb_stream(addrs, llc=LLC, early_exit=True)
    np.testing.assert_array_equal(np.asarray(ref.latencies),
                                  np.asarray(fast.latencies))
    assert int(ref.total_cycles) == int(fast.total_cycles)
    assert fast.host_cycles < ref.host_cycles / 3


# --------------------------------------------------------------------------
# step_lane_metrics: the serving engine's step-latency entry point
# --------------------------------------------------------------------------
def test_step_lane_metrics_cold_is_interference_lane():
    segs = traces.default_dbb_window(max_bursts=512)
    from repro.core.dram import DRAMConfig

    dram = DRAMConfig()
    assert (step_lane_metrics(segs, llc=LLC, dram=dram)
            == interference_lane_metrics(segs, llc=LLC, dram=dram,
                                         mix=MixConfig())
            == lane_reference.lane_metrics(segs, llc=LLC, dram=dram,
                                           mix=MixConfig()))


def test_step_lane_metrics_marginal_matches_warmed_pipeline():
    """The marginal claim, checked against an independent engine: the
    FAME-1 per-access pipeline run on the expanded prefix+step trace
    minus the same pipeline on the prefix alone."""
    from repro.core.dram import DRAMConfig

    dram = DRAMConfig()
    prefix = [traces.Segment(0, 32, 64, "w"),
              traces.Segment(1 << 20, 32, 48, "kv0")]
    step = [traces.Segment(0, 32, 64, "w"),
            traces.Segment(1 << 21, 32, 32, "kv1")]
    m = step_lane_metrics(step, llc=LLC, dram=dram, warm_prefix=prefix)
    full = simulate_dbb_stream(traces.expand(prefix + step), llc=LLC,
                               dram=dram)
    warm = simulate_dbb_stream(traces.expand(prefix), llc=LLC, dram=dram)
    assert m.total_cycles == int(full.total_cycles) - int(warm.total_cycles)
    assert m.accesses == sum(s.count for s in step)


def test_step_lane_metrics_steady_state_occupancy_effect():
    """A periodic working set that fits the LLC re-hits fully at steady
    state; adding a co-resident stream past capacity breaks the cyclic
    re-reference pattern (the serving-side Fig. 6 story)."""
    from repro.core.dram import DRAMConfig

    dram = DRAMConfig()
    fits = [traces.Segment(0, 32, 64, "w")]              # 2 KiB < 4 KiB LLC
    m1 = step_lane_metrics(fits, llc=LLC, dram=dram, warm_prefix=fits)
    assert m1.hit_rate == 1.0
    over = fits + [traces.Segment(1 << 20, 32, 96, "kv0")]   # 5 KiB > LLC
    m2 = step_lane_metrics(over, llc=LLC, dram=dram, warm_prefix=over)
    assert m2.hit_rate < m1.hit_rate
    assert m2.total_cycles > m1.total_cycles


def test_step_lane_metrics_marginal_satisfies_closed_form():
    """The counter-wise subtraction preserves the closed-form latency
    identity (it is linear in the counters) — the same invariant the
    campaign journal enforces on fresh records."""
    from repro.core.dram import DRAMConfig
    from repro.core.socsim import check_segment_totals

    dram = DRAMConfig()
    trace = traces.default_dbb_window(max_bursts=768)
    m = step_lane_metrics(trace, llc=LLC, dram=dram, warm_prefix=trace,
                          mix=MixConfig(2, "llc"))
    check_segment_totals(accesses=m.accesses, llc_hits=m.llc_hits,
                         dram_row_hits=m.dram_row_hits,
                         total_cycles=m.total_cycles, dram=dram,
                         t_llc_hit=m.t_llc_hit)


def test_deprecated_wrappers_attribute_warning_to_caller():
    """The one-release wrappers pass stacklevel=2, so the deprecation
    points at the calling file, not at sweep.py internals."""
    addrs = _window(128)
    with pytest.warns(DeprecationWarning) as rec:
        batched_hits(addrs, [LLC])
    assert any(w.filename == __file__ for w in rec)
    with pytest.warns(DeprecationWarning) as rec:
        batched_hit_rates(addrs, [LLC])
    assert any(w.filename == __file__ for w in rec)


def test_socsim_positional_configs_deprecated():
    """socsim entry points accept configs keyword-only; positional use
    warns for one release and double/missing configs raise."""
    from repro.core.dram import DRAMConfig
    from repro.core.socsim import simulate_dbb_segments

    segs = traces.default_dbb_window(max_bursts=128)
    addrs = traces.expand(segs)
    ref_seg = simulate_dbb_segments(segs, llc=LLC)
    with pytest.warns(DeprecationWarning, match="positional"):
        legacy = simulate_dbb_segments(segs, LLC)
    assert legacy.total_cycles == ref_seg.total_cycles
    ref_str = simulate_dbb_stream(addrs, llc=LLC)
    with pytest.warns(DeprecationWarning, match="positional"):
        legacy = simulate_dbb_stream(addrs, LLC, DRAMConfig())
    assert int(legacy.total_cycles) == int(ref_str.total_cycles)
    with pytest.raises(TypeError, match="missing required keyword"):
        simulate_dbb_segments(segs)
    with pytest.raises(TypeError, match="both positionally"):
        simulate_dbb_segments(segs, LLC, llc=LLC)

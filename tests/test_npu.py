"""Differential parity harness for the systolic-array NPU backend.

The NPU earns its place next to NVDLA only if every path is provably
exact: segment replay bit-identical to a naive per-access scan across a
(rows, cols, buffers) config grid, tiled-GEMM segment expansion covering
exactly the operand footprint (no gaps, no double counts beyond the
schedule's declared re-stream passes), and hypothesis-checked compiler
invariants — counting (hits <= accesses, row hits <= misses), 40-bit
address-overflow rejection, and tiling invariance (traffic and cycle
totals independent of tile-visit order for weight-stationary schedules).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import npu, traces
from repro.core.accelerator import MemSystemConfig
from repro.core.cache import LLCConfig, simulate_trace
from repro.core.sweep import segment_lane_hit_counts
from repro.core.traces import BURST_BYTES

# the (rows, cols, ifm, wgt, acc) config grid: square/rectangular PE
# arrays, buffers from starved (forcing re-stream passes) to roomy
CONFIG_GRID = [
    npu.NPUConfig(rows=4, cols=4, ifm_buf_bytes=256, wgt_buf_bytes=128,
                  acc_buf_bytes=256),
    npu.NPUConfig(rows=4, cols=8, ifm_buf_bytes=128, wgt_buf_bytes=64,
                  acc_buf_bytes=128),
    npu.NPUConfig(rows=8, cols=4, ifm_buf_bytes=1024, wgt_buf_bytes=4096,
                  acc_buf_bytes=512),
    npu.NPUConfig(rows=16, cols=16, ifm_buf_bytes=4096, wgt_buf_bytes=512,
                  acc_buf_bytes=2048),
]
OPS = [
    npu.GemmOp("square", m=12, k=12, n=12),
    npu.GemmOp("ragged", m=10, k=9, n=7),
    npu.GemmOp("tall", m=37, k=5, n=3),
    npu.GemmOp("wide", m=3, k=6, n=41),
]
LLC_SMALL = LLCConfig(size_bytes=4096, ways=4, block_bytes=32)


def _scan_reference(segs, llc):
    """The naive per-access reference: expand every segment to byte
    addresses and replay them one at a time through the serial LRU.
    Returns the per-segment hit counts."""
    blocks = (traces.expand(segs) // llc.block_bytes).astype(np.int32)
    bits = np.asarray(simulate_trace(blocks, sets=llc.sets, ways=llc.ways))
    bounds = np.cumsum([0] + [s.count for s in segs])
    return [int(bits[a:b].sum()) for a, b in zip(bounds[:-1], bounds[1:])]


def _lane_hits(segs, llc):
    """Per-segment hit counts of the segment lane engine, one lane."""
    return segment_lane_hit_counts(segs, [llc])[0, :len(segs)].tolist()


def _stream_bursts(segs, stream):
    return sum(s.count for s in segs if s.stream == stream)


def _burst_set(segs, stream):
    out = set()
    for s in segs:
        if s.stream == stream:
            out.update(range(s.base, s.base + s.count * s.stride, s.stride))
    return out


class TestSegmentParity:
    @pytest.mark.parametrize("cfg", CONFIG_GRID,
                             ids=lambda c: f"{c.rows}x{c.cols}")
    @pytest.mark.parametrize("op", OPS, ids=lambda o: o.name)
    def test_replay_bit_identical_to_per_access_scan(self, cfg, op):
        segs = npu.op_segments(op, cfg, 0, 1 << 20, 2 << 20)
        assert _lane_hits(segs, LLC_SMALL) == _scan_reference(segs,
                                                              LLC_SMALL)

    @pytest.mark.parametrize("cfg", CONFIG_GRID[:2],
                             ids=lambda c: f"{c.rows}x{c.cols}")
    def test_interleaved_workload_parity(self, cfg):
        ops = [npu.GemmOp("a", 9, 8, 7), npu.GemmOp("b", 7, 7, 9)]
        chunks = npu.npu_chunks(ops, cfg, chunk_bursts=4)
        assert _lane_hits(chunks, LLC_SMALL) == _scan_reference(chunks,
                                                                LLC_SMALL)

    def test_window_is_exact_prefix(self):
        cfg = CONFIG_GRID[0]
        ops = [npu.GemmOp("a", 9, 8, 7), npu.GemmOp("b", 7, 7, 9)]
        full = traces.expand(npu.npu_chunks(ops, cfg, chunk_bursts=4))
        assert len(full) > 15
        win = traces.expand(npu.npu_chunks(ops, cfg, chunk_bursts=4,
                                           max_bursts=15))
        assert len(win) == 15
        np.testing.assert_array_equal(win, full[:15])


class TestFootprintCoverage:
    @pytest.mark.parametrize("cfg", CONFIG_GRID,
                             ids=lambda c: f"{c.rows}x{c.cols}")
    @pytest.mark.parametrize("op", OPS, ids=lambda o: o.name)
    def test_streams_cover_exact_operand_footprint(self, cfg, op):
        """Every stream's unique bursts tile [base, base + footprint)
        exactly — no gaps, no bursts outside the packed layout — and
        total bursts match the schedule's declared traffic (i.e. double
        reads happen only as declared re-stream passes)."""
        s = npu.schedule(op, cfg)
        bases = {"weight": 0, "ifmap": 1 << 20, "ofmap": 2 << 20}
        segs = npu.op_segments(op, cfg, *bases.values())
        for stream, footprint, traffic in (
                ("weight", s.weight_footprint, s.weight_traffic),
                ("ifmap", s.ifmap_footprint, s.ifmap_traffic),
                ("ofmap", s.ofmap_footprint, s.ofmap_traffic)):
            uniq = _burst_set(segs, stream)
            base = bases[stream]
            expect = set(range(base, base + footprint, BURST_BYTES))
            assert uniq == expect, f"{stream} coverage has gaps/strays"
            assert _stream_bursts(segs, stream) * BURST_BYTES == traffic

    def test_footprint_padding_is_burst_granular(self):
        """Packed footprints only ever exceed the raw operand bytes by
        per-tile burst alignment."""
        cfg, op = CONFIG_GRID[1], OPS[1]
        s = npu.schedule(op, cfg)
        raw_w = op.k * op.n * cfg.elem_bytes
        assert raw_w <= s.weight_footprint \
            < raw_w + s.n_k * s.n_n * BURST_BYTES

    def test_restreaming_multiplies_weight_traffic(self):
        """A stripe that outgrows the weight SRAM re-streams once per
        M block — the NVDLA weight_passes analogy."""
        cfg = npu.NPUConfig(rows=4, cols=4, ifm_buf_bytes=64,
                            wgt_buf_bytes=64, acc_buf_bytes=64)
        op = npu.GemmOp("restream", m=12, k=64, n=4)
        s = npu.schedule(op, cfg)
        assert s.n_m > 1 and s.weight_passes == (s.n_m,)
        segs = npu.op_segments(op, cfg, 0, 1 << 20, 2 << 20)
        assert (_stream_bursts(segs, "weight") * BURST_BYTES
                == s.weight_footprint * s.n_m)


class TestVisitOrderInvariance:
    def test_traffic_and_cycles_order_invariant(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(m=st.integers(1, 24), k=st.integers(1, 24),
               n=st.integers(1, 24),
               rows=st.sampled_from([2, 4, 8]),
               cols=st.sampled_from([2, 4, 8]),
               wgt=st.sampled_from([32, 128, 4096]),
               acc=st.sampled_from([32, 256]),
               seed=st.integers(0, 2**31 - 1))
        def prop(m, k, n, rows, cols, wgt, acc, seed):
            cfg = npu.NPUConfig(rows=rows, cols=cols, ifm_buf_bytes=256,
                                wgt_buf_bytes=wgt, acc_buf_bytes=acc)
            op = npu.GemmOp("p", m=m, k=k, n=n)
            s = npu.schedule(op, cfg)
            perm = s.visits("nm")
            np.random.RandomState(seed).shuffle(perm)
            ref = npu.op_segments(op, cfg, 0, 1 << 20, 2 << 20, order="nm")
            for order in ("mn", perm):
                got = npu.op_segments(op, cfg, 0, 1 << 20, 2 << 20,
                                      order=order)
                for stream in ("weight", "ifmap", "ofmap"):
                    assert (_stream_bursts(got, stream)
                            == _stream_bursts(ref, stream))
                    assert _burst_set(got, stream) == _burst_set(ref, stream)
            # compute cycles are a sum over the tile set: replaying the
            # permuted visit order tile by tile reproduces the closed
            # form
            explicit = sum(
                s.m_szs[mi] + s.k_szs[ki] + s.n_szs[ni]
                + cfg.tile_overhead_cycles
                for ni, mi in perm for ki in range(s.n_k))
            assert explicit == s.compute_cycles

        prop()

    def test_non_permutation_order_rejected(self):
        op, cfg = OPS[0], CONFIG_GRID[0]
        s = npu.schedule(op, cfg)
        bad = s.visits("nm")[:-1]
        with pytest.raises(ValueError, match="permutation"):
            npu.op_segments(op, cfg, 0, 1 << 20, 2 << 20, order=bad)


class TestCompilerProperties:
    def test_counting_invariants_through_the_lane_engine(self):
        """The full sweep lane on an NPU trace obeys the same counting
        laws as NVDLA lanes: hits <= accesses, DRAM row hits <= misses,
        and the accelerator-stream counters are a subset of the lane."""
        pytest.importorskip("hypothesis")
        from hypothesis import example, given, settings, strategies as st

        from repro.core.dram import DRAMConfig
        from repro.core.sweep import MixConfig, interference_lane_metrics

        @settings(max_examples=30, deadline=None)
        @given(m=st.integers(1, 20), k=st.integers(1, 20),
               n=st.integers(1, 20),
               grid=st.sampled_from([(2, 2), (4, 8), (8, 4)]))
        @example(m=1, k=1, n=1, grid=(2, 2))
        def prop(m, k, n, grid):
            cfg = npu.NPUConfig(rows=grid[0], cols=grid[1],
                                ifm_buf_bytes=128, wgt_buf_bytes=128,
                                acc_buf_bytes=128)
            trace = npu.npu_chunks([npu.GemmOp("p", m, k, n)], cfg,
                                   chunk_bursts=4)
            res = interference_lane_metrics(
                trace, llc=LLC_SMALL, dram=DRAMConfig(),
                mix=MixConfig(corunners=0, wss="l1"))
            assert 0 <= res.llc_hits <= res.accesses
            assert res.dram_row_hits <= res.accesses - res.llc_hits
            assert res.nvdla_accesses == sum(s.count for s in trace)
            assert res.nvdla_hits <= res.llc_hits

        prop()

    def test_40bit_overflow_rejected(self):
        op, cfg = OPS[0], CONFIG_GRID[0]
        with pytest.raises(ValueError, match="40-bit"):
            npu.op_segments(op, cfg, (1 << 40) - BURST_BYTES,
                            1 << 20, 2 << 20)

    def test_weight_heap_budget_rejected(self):
        huge = npu.GemmOp("huge", m=1, k=1 << 15, n=1 << 15)  # 1 GiB
        with pytest.raises(ValueError, match="weight heap"):
            npu.workload_op_segments([huge], npu.NPUConfig())

    def test_fmap_region_overrun_rejected(self):
        wide = npu.GemmOp("wide", m=1 << 14, k=1, n=1 << 14)  # 256 MiB out
        with pytest.raises(ValueError, match="fmap region"):
            npu.workload_op_segments([wide], npu.NPUConfig())

    def test_bad_config_and_op_rejected(self):
        with pytest.raises(ValueError, match="positive"):
            npu.NPUConfig(rows=0)
        with pytest.raises(ValueError, match="positive"):
            npu.GemmOp("bad", m=1, k=0, n=1)
        with pytest.raises(ValueError, match="unknown NPU workload"):
            npu.workload("resnet99")


class TestTiming:
    def test_simulated_rates_match_manual_fold(self):
        cfg = CONFIG_GRID[0]
        ops = [npu.GemmOp("a", 9, 8, 7), npu.GemmOp("b", 7, 7, 9)]
        mem = MemSystemConfig(llc=LLC_SMALL)
        rates = npu.op_stream_hit_rates(ops, cfg, mem)
        assert len(rates) == 2
        per_op = npu.workload_op_segments(ops, cfg)
        flat = [s for segs in per_op for s in segs]
        per_segment_hits = _scan_reference(flat, LLC_SMALL)
        k = 0
        for segs, op_rates in zip(per_op, rates):
            tot = {"weight": [0, 0], "ifmap": [0, 0], "ofmap": [0, 0]}
            for s in segs:
                tot[s.stream][0] += per_segment_hits[k]
                tot[s.stream][1] += s.count
                k += 1
            for (h, a), r in zip(
                    (tot["weight"], tot["ifmap"], tot["ofmap"]), op_rates):
                assert 0.0 <= r <= 1.0
                assert r == pytest.approx(h / a if a else 0.0)

    def test_simulated_mode_bounded_by_perfect_and_coldest(self):
        cfg = CONFIG_GRID[0]
        ops = [npu.GemmOp("a", 16, 16, 16)]
        mem = MemSystemConfig(llc=LLC_SMALL)
        sim = npu.npu_time_s(ops, npu=cfg, mem=mem, mode="simulated")
        hot = npu.npu_time_s(ops, npu=cfg, mem=mem,
                             hit_rates=[(1.0, 1.0, 1.0)])
        cold = npu.npu_time_s(ops, npu=cfg, mem=mem,
                              hit_rates=[(0.0, 0.0, 0.0)])
        assert hot["cycles"] <= sim["cycles"] <= cold["cycles"]
        assert sim["mode"] == "simulated"

    def test_utilization_bounded_and_overheads_count(self):
        cfg = npu.NPUConfig()
        res = npu.op_cycles(npu.GemmOp("g", 512, 512, 512), cfg,
                            MemSystemConfig())
        assert 0.0 < res["utilization"] <= 1.0
        assert res["total"] >= res["compute"] >= 512  # M cycles minimum

    def test_mode_and_hit_rate_validation(self):
        ops = [npu.GemmOp("a", 4, 4, 4)]
        with pytest.raises(ValueError, match="unknown mode"):
            npu.npu_time_s(ops, mode="oracle")
        with pytest.raises(ValueError, match="must cover every op"):
            npu.npu_time_s(ops, hit_rates=[])


class TestZooWorkloads:
    @pytest.mark.parametrize("name", sorted(npu.WORKLOADS))
    def test_workloads_build_and_window(self, name):
        ops = npu.workload(name)
        assert len(ops) > 0 and all(o.macs > 0 for o in ops)
        win = npu.default_npu_window(name, max_bursts=128)
        assert sum(s.count for s in win) == 128
        # every address fits the lane engine's int32 metadata
        assert all(s.base + s.stride * s.count < 2**31 for s in win)

    def test_yolov3_gemms_match_conv_layers(self):
        from repro.core import yolov3

        convs = [la for la in yolov3.LAYERS if la.kind == "conv"]
        gemms = npu.yolov3_gemms()
        assert len(gemms) == len(convs)
        for la, g in zip(convs, gemms):
            assert (g.m, g.k, g.n) == (la.out_h * la.out_w,
                                       la.cin * la.ksize ** 2, la.cout)
            assert g.macs == la.macs


class TestDecodeWeightStream:
    def test_single_pass_covers_exactly_the_heap(self):
        cfg = npu.NPUConfig()
        segs = npu.decode_weight_segments(1 << 20, cfg, m=8)
        assert all(s.stream == "weight" for s in segs)
        total = sum(s.count for s in segs) * BURST_BYTES
        assert (1 << 20) <= total < (1 << 20) + (1 << 16)  # pad only
        uniq = _burst_set(segs, "weight")
        assert len(uniq) * BURST_BYTES == total  # single pass: no rereads

    def test_wide_batch_with_starved_sram_restreams(self):
        cfg = npu.NPUConfig(rows=8, cols=8, wgt_buf_bytes=1024,
                            acc_buf_bytes=64, ifm_buf_bytes=64)
        one = npu.decode_weight_segments(1 << 16, cfg, m=1)
        wide = npu.decode_weight_segments(1 << 16, cfg, m=64)
        assert (sum(s.count for s in wide)
                > sum(s.count for s in one))  # re-stream passes appeared


class TestServingOracle:
    def _ws(self):
        from repro.configs import get_smoke_config
        from repro.models import decode_working_set

        return decode_working_set(get_smoke_config("qwen2-0.5b"))

    def _kv(self, ws):
        from repro.serve.kvcache import PagedKVCache

        kv = PagedKVCache(num_blocks=32, block_size=16,
                          token_bytes=max(1, ws.kv_token_bytes))
        kv.admit(0, prompt_tokens=8, max_new=8)
        kv.admit(1, prompt_tokens=8, max_new=8)
        return kv

    def test_npu_backend_prices_steps(self):
        from repro.serve.oracle import SoCLatencyOracle

        ws = self._ws()
        kv = self._kv(ws)
        o = SoCLatencyOracle(ws, llc=LLCConfig(), weight_bytes=1 << 20,
                             backend="npu")
        lat = o.decode_step(kv, [0, 1])
        assert lat.cycles > 0 and lat.seconds > 0
        assert o.decode_step(kv, [0, 1]) is lat  # memoized

    def test_contiguous_single_pass_matches_nvdla_stream(self):
        """With roomy SRAMs the NPU fetches its stripes once, in order,
        contiguously — the burst stream degenerates to NVDLA's
        sequential read, so the simulated step cost is identical (the
        cross-backend differential anchor)."""
        from repro.serve.oracle import SoCLatencyOracle

        ws = self._ws()
        kv = self._kv(ws)
        lat = {}
        for backend in ("nvdla", "npu"):
            o = SoCLatencyOracle(ws, llc=LLCConfig(), weight_bytes=1 << 20,
                                 backend=backend)
            lat[backend] = o.decode_step(kv, [0, 1]).cycles
        assert lat["nvdla"] == lat["npu"]

    def test_backend_validation(self):
        from repro.serve.oracle import SoCLatencyOracle

        ws = self._ws()
        with pytest.raises(ValueError, match="unknown backend"):
            SoCLatencyOracle(ws, weight_bytes=1 << 20, backend="tpu")
        with pytest.raises(ValueError, match="only applies"):
            SoCLatencyOracle(ws, weight_bytes=1 << 20,
                             npu=npu.NPUConfig())

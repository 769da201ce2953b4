"""Cycle-token NoC switch + SoC farm: bit-exactness and QoS shape.

The acceptance bar for the farm subsystem: the FAME-1 token-bundle
switch must be bit-identical to the per-cycle reference for *every*
bundle size — including bundles that do not divide the cycle count —
and the farm's victim tail must show the Fig. 6 QoS story (superlinear
p99 in co-runner nodes, way partitioning recovering it).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import lane_reference
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.fame1 import chunked_scan
from repro.core.farm import FarmConfig, farm_schedule, simulate_farm
from repro.core.noc import (NoCConfig, NoCOverflowError, NoCSwitch,
                            simulate_reference)
from repro.core.sweep import MixConfig

GEOMETRIES = ((3, 40, 0), (4, 33, 2))        # (ports, T, link_latency)
BUNDLES = (1, 7, 64)                          # 7 divides nothing here


def _random_schedule(rng, ports: int, cycles: int) -> np.ndarray:
    """Each port injects ~60% of cycles toward a random egress."""
    dests = rng.integers(-2, ports, size=(cycles, ports))
    return np.where(dests >= 0, dests, -1)


def _assert_same(a, b, ctx: str) -> None:
    for f in ("deliver_cycle", "egress", "src", "latency"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f"{ctx}: {f} diverged")


class TestSwitchParity:
    def test_bundles_match_reference(self):
        rng = np.random.default_rng(0)
        for ports, cycles, link in GEOMETRIES:
            cfg = NoCConfig(ports=ports, link_latency=link,
                            queue_depth=cycles)
            for trial in range(3):
                sched = _random_schedule(rng, ports, cycles)
                ref = simulate_reference(sched, cfg)
                assert ref.deliver_cycle.shape[0] == int(
                    (sched >= 0).sum())
                for bundle in BUNDLES:
                    got = NoCSwitch(cfg).simulate(sched,
                                                  bundle_cycles=bundle)
                    _assert_same(got, ref,
                                 f"ports={ports} link={link} "
                                 f"trial={trial} bundle={bundle}")

    def test_farm_schedule_parity_nondividing_bundle(self):
        farm = FarmConfig(nodes=2)
        sched = farm_schedule(40, farm)
        cfg = NoCConfig(ports=4, link_latency=farm.link_latency)
        ref = simulate_reference(sched, cfg)
        for bundle in (5, 13):
            got = NoCSwitch(cfg).simulate(sched, bundle_cycles=bundle)
            _assert_same(got, ref, f"farm bundle={bundle}")
            assert got.host_steps < ref.cycles_run   # batching happened

    def test_source_latencies_in_fifo_order(self):
        cfg = NoCConfig(ports=3, link_latency=1, queue_depth=16)
        sched = np.full((12, 3), -1)
        sched[::2, 0] = 2     # victim every other cycle
        sched[:, 1] = 2       # co-runner every cycle, same egress
        res = NoCSwitch(cfg).simulate(sched)
        lat = res.source_latencies(0)
        assert lat.shape[0] == 6
        assert np.all(lat >= cfg.link_latency)

    def test_overflow_raises_in_both_implementations(self):
        # two saturating sources, one egress, depth 1: the loser of
        # round-robin accumulates a backlog its FIFO cannot hold
        cfg = NoCConfig(ports=2, link_latency=0, queue_depth=1)
        sched = np.full((8, 2), 1)
        with pytest.raises(NoCOverflowError):
            simulate_reference(sched, cfg)
        with pytest.raises(NoCOverflowError):
            NoCSwitch(cfg).simulate(sched)

    def test_schedule_validation(self):
        cfg = NoCConfig(ports=2)
        with pytest.raises(ValueError):
            simulate_reference(np.full((4, 3), -1), cfg)   # wrong width
        with pytest.raises(ValueError):
            simulate_reference(np.full((4, 2), 2), cfg)    # egress >= ports


class TestChunkedScan:
    """fame1.chunked_scan: bundle-size invariance of the host batching."""

    @staticmethod
    def _step(carry, x, active):
        i, acc = carry
        return (i + active.astype(jnp.int32),
                acc + jnp.where(active, x, 0)), acc + x

    def test_invariant_to_chunk_len(self):
        xs = jnp.arange(13, dtype=jnp.int32)
        ref = None
        for chunk in (1, 3, 8, 64):
            carry, ys, bundles = chunked_scan(
                self._step, (jnp.int32(0), jnp.int32(0)), xs,
                cont_fn=lambda c: jnp.bool_(True), chunk_len=chunk)
            got = (int(carry[0]), int(carry[1]),
                   np.asarray(ys)[:13].tolist())
            if ref is None:
                ref = got
                assert ref[0] == 13 and ref[1] == int(np.arange(13).sum())
            assert got == ref, f"chunk_len={chunk} diverged"

    def test_early_exit_stops_on_bundle_boundary(self):
        xs = jnp.ones(20, dtype=jnp.int32)
        carry, _, bundles = chunked_scan(
            self._step, (jnp.int32(0), jnp.int32(0)), xs,
            cont_fn=lambda c: c[0] < 7, chunk_len=3)
        # bundles run until the predicate fails at a bundle boundary
        assert int(bundles) == 3 and int(carry[0]) == 9


class TestFarmTail:
    def test_qos_shape_and_solo_identity(self):
        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        dram = DRAMConfig()
        p99 = {}
        for n, mask in ((0, None), (2, None), (2, 0x0F)):
            res = simulate_farm(llc=llc, dram=dram,
                                farm=FarmConfig(nodes=n, way_mask=mask),
                                max_bursts=512)
            steady = np.sort(res.steady())
            p99[(n, mask)] = steady[min(steady.shape[0] - 1,
                                        int(np.ceil(steady.shape[0]
                                                    * 0.99)) - 1)]
            np.testing.assert_array_equal(
                res.total_latency, res.noc_latency + res.mem_latency)
            if n == 0:
                from repro.core.farm import victim_window

                ref = lane_reference.lane_metrics(
                    victim_window("nvdla", max_bursts=512) * 2,
                    llc=llc, dram=dram, mix=MixConfig(0, "l1"))
                assert res.metrics == ref
        assert p99[(2, None)] > p99[(0, None)]
        assert p99[(2, 0x0F)] < p99[(2, None)]

    def test_npu_victim_backend(self):
        llc = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
        res = simulate_farm(llc=llc, dram=DRAMConfig(),
                            farm=FarmConfig(nodes=1, passes=1),
                            backend="npu", max_bursts=256)
        assert res.requests == res.total_latency.shape[0] > 0

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="backend"):
            simulate_farm(llc=LLCConfig(), dram=DRAMConfig(),
                          backend="tpu")

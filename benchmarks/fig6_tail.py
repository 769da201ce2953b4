"""Fig. 6 extended to the tail — victim latency QoS under an SoC farm.

The base Fig. 6 sweep reports *mean* NVDLA slowdown under co-runner
counts; real QoS targets are quantiles.  This suite runs the
``repro.core.farm`` multi-node composition — victim DBB requests
through the cycle-token NoC switch plus the shared LLC/DRAM lane —
and reports the steady-state victim request-latency distribution
(p50 / p99 / WCET, nearest-rank) versus co-runner node count, with and
without LLC way partitioning.

The farm nodes model edge SoCs with a small shared LLC (256 KiB) so
co-runner traffic genuinely evicts the victim's cross-pass working
set; way partitioning (victim fenced into half the ways) protects that
reuse, recovering the memory half of the tail, while the NoC half
(egress saturation past offered load 1.0) is policy-free — exactly the
CAT-style story the suite quantifies.

Emits ``BENCH_noc.json`` (override with ``BENCH_NOC_JSON``) and
asserts the acceptance properties inline: p99 degrades superlinearly
in node count, partitioning strictly recovers p99 at max contention,
the solo-farm lane record is bit-identical to
``interference_lane_metrics`` and to the per-access
``simulate_dbb_stream``, and the token-bundle switch matches the
per-cycle reference on this suite's own schedules.
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.farm import (FarmConfig, farm_schedule, simulate_farm,
                             victim_window)
from repro.core.noc import NoCConfig, NoCSwitch, simulate_reference
from repro.core import traces
from repro.core.socsim import simulate_dbb_stream
from repro.core.sweep import (MixConfig, interference_lane_metrics,
                              nvdla_chunks)
from repro.utils.stats import latency_summary

# edge-node shared LLC: small enough that "llc"-sized co-runner working
# sets overflow the victim's ways without a partition (the smoke window
# is 4x shorter, so its LLC shrinks 4x to keep the per-set pressure)
LLC = LLCConfig(size_bytes=256 * 1024, ways=8, block_bytes=64)
LLC_SMOKE = LLCConfig(size_bytes=64 * 1024, ways=8, block_bytes=64)
WAY_MASK = 0x0F                     # victim keeps half the ways


def _summaries(counts, *, llc: LLCConfig, max_bursts: int,
               way_mask: int | None, dram: DRAMConfig) -> dict:
    out = {}
    for n in counts:
        res = simulate_farm(
            llc=llc, dram=dram,
            farm=FarmConfig(nodes=n, way_mask=way_mask),
            max_bursts=max_bursts)
        s = latency_summary(res.steady())
        s["noc_mean"] = float(res.noc_latency.mean())
        s["mem_mean"] = float(res.mem_latency.mean())
        s["host_steps"] = res.noc.host_steps
        out[n] = (s, res)
    return out


def _check_bundle_parity(counts, *, max_bursts: int) -> int:
    """The suite's own schedules through the token-bundle switch vs the
    per-cycle reference — every result array must be element-wise
    equal, for a bundle size that does not divide the horizon."""
    checked = 0
    requests = 2 * max_bursts // 16          # passes * chunks
    for n in counts:
        farm = FarmConfig(nodes=n)
        sched = farm_schedule(requests, farm)
        cfg = NoCConfig(ports=n + 2, link_latency=farm.link_latency)
        ref = simulate_reference(sched, cfg)
        for bundle in (1, 7, 64):
            got = NoCSwitch(cfg).simulate(sched, bundle_cycles=bundle)
            for f in ("deliver_cycle", "egress", "src", "latency"):
                if not np.array_equal(getattr(got, f), getattr(ref, f)):
                    raise AssertionError(
                        f"token-bundle switch (bundle={bundle}, n={n}) "
                        f"diverged from the per-cycle reference on {f}")
            checked += 1
    return checked


def _check_solo_per_access(solo, lane_segs, *, llc: LLCConfig,
                           dram: DRAMConfig, t_llc_hit: int = 20) -> None:
    """The solo farm lane against ``simulate_dbb_stream``, which replays
    the lane access by access: its per-access latencies (a hit costs
    ``t_llc_hit``, a row hit ``+ tCAS``) give the lane's counters, and
    summed over each victim chunk, the farm's request latencies."""
    lats = np.asarray(simulate_dbb_stream(traces.expand(lane_segs),
                                          llc=llc, dram=dram).latencies,
                      np.int64)
    per_chunk = nvdla_chunks(lane_segs)[2]
    want = np.add.reduceat(lats, np.cumsum(per_chunk) - per_chunk)
    m = solo.metrics
    got = (m.accesses, m.llc_hits, m.dram_row_hits, m.total_cycles)
    ref = (lats.shape[0], int((lats == t_llc_hit).sum()),
           int((lats == t_llc_hit + dram.t_cas_cycles).sum()),
           int(lats.sum()))
    if got != ref or not np.array_equal(solo.mem_latency, want):
        raise AssertionError(
            f"solo farm lane diverged from the per-access pipeline: "
            f"(accesses, hits, row hits, cycles) {got} vs {ref}")


def run(smoke: bool = False) -> list[tuple]:
    dram = DRAMConfig()
    counts = (0, 1, 2) if smoke else (0, 1, 2, 4)
    max_bursts = 512 if smoke else 2048
    llc = LLC_SMOKE if smoke else LLC
    base = _summaries(counts, llc=llc, max_bursts=max_bursts,
                      way_mask=None, dram=dram)
    part = _summaries(counts, llc=llc, max_bursts=max_bursts,
                      way_mask=WAY_MASK, dram=dram)

    # acceptance: the tail degrades superlinearly with node count …
    nmax, mid = counts[-1], counts[len(counts) // 2]
    p99 = {n: base[n][0]["p99"] for n in counts}
    if not (p99[nmax] - p99[mid] > p99[mid] - p99[counts[0]]):
        raise AssertionError(
            f"victim p99 not superlinear in co-runner nodes: {p99}")
    # … way partitioning measurably recovers the victim's p99 …
    if not part[nmax][0]["p99"] < p99[nmax]:
        raise AssertionError(
            f"way partitioning did not recover p99 at n={nmax}: "
            f"{part[nmax][0]['p99']} vs {p99[nmax]}")
    # … the solo farm's lane is exactly the Fig. 6 solo lane, and both
    # agree with the per-access pipeline, access by access …
    solo = base[0][1]
    lane_segs = victim_window("nvdla", max_bursts=max_bursts) * 2
    ref = interference_lane_metrics(lane_segs, llc=llc, dram=dram,
                                    mix=MixConfig(0, "l1"))
    if solo.metrics != ref:
        raise AssertionError("solo farm lane diverged from "
                             "interference_lane_metrics")
    _check_solo_per_access(solo, lane_segs, llc=llc, dram=dram)
    # … and the token-bundle switch is bit-identical to per-cycle.
    parity = _check_bundle_parity((counts[0], nmax),
                                  max_bursts=512 if smoke else 1024)

    rows = []
    for n in counts:
        for tag, res in (("", base), ("part_", part)):
            s = res[n][0]
            for k in ("p50", "p99", "wcet"):
                rows.append((f"fig6_tail/{tag}{k}_x{n}", round(s[k], 1),
                             "steady-state victim request cycles"))
        rows.append((f"fig6_tail/noc_mean_x{n}",
                     round(base[n][0]["noc_mean"], 1),
                     "switch queueing + link, all passes"))
    rows.append(("fig6_tail/bundle_parity_checks", parity,
                 "token-bundle vs per-cycle reference schedules"))

    payload = {
        "llc": {"size_bytes": llc.size_bytes, "ways": llc.ways,
                "block_bytes": llc.block_bytes},
        "way_mask": WAY_MASK,
        "max_bursts": max_bursts,
        "nodes": list(counts),
        "unpartitioned": {str(n): base[n][0] for n in counts},
        "partitioned": {str(n): part[n][0] for n in counts},
    }
    path = os.environ.get("BENCH_NOC_JSON", "BENCH_noc.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    rows.append(("fig6_tail/json", path, "QoS distributions"))
    return rows

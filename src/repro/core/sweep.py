"""Batched memory-system sweeps: one compiled program per grid.

The seed path ran every sweep point through its own ``lax.scan`` —
and because ``simulate_trace`` specializes on (sets, ways), every
geometry was a fresh XLA compile.  Two batched engines fix that, both
padding state to the largest geometry and ``jax.vmap``-ing the exact
LLC update over per-lane (sets, ways, block_bytes) scalars so a whole
grid compiles once and runs as a single device program:

* the **per-access engine** (``batched_hits``/``batched_hits_per_trace``)
  scans an expanded byte trace — per-access hit *bits*, serial depth
  O(accesses);
* the **segment-lane engine** (``segment_lane_hit_counts``/``_rates``)
  replays the *compressed* trace of ``repro.core.traces`` directly —
  the geometry-traced segment kernel of ``repro.core.cache`` retires a
  whole (base, stride, count) run per step, so serial depth is
  O(segments * max_ways) and full-frame multi-config sweeps (the trace
  lengths Fig. 5/6 actually need) fit in one program.

Padded ways are masked out of both tag match and victim selection, so
each lane is bit-identical to the unbatched simulator at that geometry
(tests/test_sweep.py).

Public API:
* ``segment_lane_hit_counts``  — (configs, segments) compressed-trace
                                 hit counts, shared or per-lane traces;
* ``segment_lane_hit_rates``   — the per-lane rates thereof;
* ``MixConfig``           — a co-runner mix (count + working-set size);
* ``LaneMetrics``         — frozen typed record of one interference
                            lane (``to_record``/``from_record`` for
                            JSON journaling);
* ``SweepGrid``           — frozen typed result of the figure sweeps;
* ``interference_lane_metrics``       — one lane -> ``LaneMetrics``,
                            optionally LLC way-partitioned
                            (``way_mask=``);
* ``interference_lane_metrics_batch`` — many lanes as vmapped lane
                            programs, optionally sharded over a
                            ``jax.sharding`` mesh (the campaign
                            executor's data-parallel path) and
                            optionally per-lane way-partitioned
                            (``way_masks=``);
* ``compact_lane``        — one interleaved lane as ``LaneRecords``:
                            repeats of the arbiter's pattern, each run
                            in one step of ``cache.record_lane_scan``;
* ``partition_way_sels``  — victim/co-runner allocation masks for an
                            Intel-CAT-style two-class way partition;
* ``lane_request_latencies`` — per-victim-chunk memory latencies (the
                            farm's memory-side tail distribution);
* ``sweep_llc``           — Fig. 5 grid: closed-form speedups + exact
                            segment-lane hit rates, windowed or full
                            frame;
* ``sweep_interference``  — Fig. 6 grid: closed-form slowdowns + exact
                            segment-lane hit rates and closed-form DRAM
                            row-hit rates under BwWrite co-runners,
                            windowed or full frame.

The expanded-trace per-access lanes (``batched_hits`` /
``batched_hits_per_trace``) are deprecated: they serialize on burst
count and exist only as a parity oracle for the segment-lane engine.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import traces
from repro.core.cache import LLCConfig, _append_block_runs
from repro.utils import tracing
from repro.utils.env import as_address_array


# --------------------------------------------------------------------------
# typed sweep results
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MixConfig:
    """A co-runner mix: how many BwWrite cores run beside the NVDLA and
    how large their working sets are ("l1" never reaches the shared
    fabric, "llc" occupies half the LLC, "dram" streams far past it —
    the three Fig. 6 regimes)."""
    corunners: int = 0
    wss: str = "l1"

    def __post_init__(self):
        if self.wss not in ("l1", "llc", "dram"):
            raise ValueError(f"unknown working-set size {self.wss!r} "
                             "(expected 'l1', 'llc' or 'dram')")
        if self.corunners < 0:
            raise ValueError("corunners must be >= 0")


@dataclasses.dataclass(frozen=True)
class LaneMetrics:
    """One interference lane's exact metric record — the typed currency
    between the sweep engine and the campaign executor (guardrails
    consume attributes, journals store ``to_record()`` dicts).

    Every field is a plain int/float: deterministic, JSON-stable, and
    internally consistent (``total_cycles`` satisfies the closed-form
    latency identity the executor re-checks)."""
    segments: int
    accesses: int
    llc_hits: int
    dram_row_hits: int
    t_llc_hit: int
    total_cycles: int
    hit_rate: float
    nvdla_accesses: int
    nvdla_hits: int
    nvdla_hit_rate: float
    nvdla_misses: int
    nvdla_miss_row_hits: int
    nvdla_miss_row_hit_rate: float

    _INT_FIELDS = ("segments", "accesses", "llc_hits", "dram_row_hits",
                   "t_llc_hit", "total_cycles", "nvdla_accesses",
                   "nvdla_hits", "nvdla_misses", "nvdla_miss_row_hits")
    _FLOAT_FIELDS = ("hit_rate", "nvdla_hit_rate",
                     "nvdla_miss_row_hit_rate")

    def to_record(self) -> dict:
        """Flat JSON-stable dict, keys == field names (the journaled
        point-record format of ``repro.campaign.manifest``)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_record(cls, record: dict) -> "LaneMetrics":
        """Rebuild from a journaled dict.  Raises ``KeyError`` on a
        missing field and ``TypeError``/``ValueError`` on a non-numeric
        one — the executor's replay validation relies on that."""
        kw = {f: int(record[f]) for f in cls._INT_FIELDS}
        kw.update({f: float(record[f]) for f in cls._FLOAT_FIELDS})
        return cls(**kw)


@dataclasses.dataclass(frozen=True)
class SweepGrid:
    """Typed result of a figure sweep (``sweep_llc`` /
    ``sweep_interference``): the closed-form curves plus the simulated
    per-point rates, with tuple-keyed dicts instead of the old ad-hoc
    string-keyed blob.  ``to_record()`` flattens tuple keys into JSON
    rows ([*key, value]); ``from_record`` restores them exactly."""
    kind: str                              # "llc" | "interference"
    sim_hit_rates: dict                    # (size,block) | (wss,n) -> rate
    window_bursts: int | None = None
    no_llc_s: float | None = None          # Fig. 5 baseline runtime
    speedups: dict | None = None           # (size_kib, block) -> speedup
    slowdowns: dict | None = None          # wss -> {n: slowdown}
    sim_row_hit_rates: dict | None = None  # (wss, n) -> DRAM row-hit rate

    def to_record(self) -> dict:
        rec: dict = {"kind": self.kind, "window_bursts": self.window_bursts,
                     "sim_hit_rates": [[*k, v] for k, v
                                       in self.sim_hit_rates.items()]}
        if self.no_llc_s is not None:
            rec["no_llc_s"] = self.no_llc_s
        if self.speedups is not None:
            rec["speedups"] = [[*k, v] for k, v in self.speedups.items()]
        if self.slowdowns is not None:
            rec["slowdowns"] = [[wss, n, v]
                                for wss, curve in self.slowdowns.items()
                                for n, v in curve.items()]
        if self.sim_row_hit_rates is not None:
            rec["sim_row_hit_rates"] = [[*k, v] for k, v
                                        in self.sim_row_hit_rates.items()]
        return rec

    @classmethod
    def from_record(cls, record: dict) -> "SweepGrid":
        def keyed(rows):
            return {tuple(r[:-1]): r[-1] for r in rows}

        slowdowns = None
        if "slowdowns" in record:
            slowdowns = {}
            for wss, n, v in record["slowdowns"]:
                slowdowns.setdefault(wss, {})[n] = v
        return cls(
            kind=record["kind"],
            window_bursts=record.get("window_bursts"),
            no_llc_s=record.get("no_llc_s"),
            sim_hit_rates=keyed(record["sim_hit_rates"]),
            speedups=(keyed(record["speedups"])
                      if "speedups" in record else None),
            slowdowns=slowdowns,
            sim_row_hit_rates=(keyed(record["sim_row_hit_rates"])
                               if "sim_row_hit_rates" in record else None))


@functools.partial(jax.jit, static_argnames=("max_sets", "max_ways"))
def _simulate_padded(byte_addrs, sets, ways, block_bytes,
                     *, max_sets: int, max_ways: int):
    """Exact LLC scan with *runtime* geometry on padded state.

    sets/ways/block_bytes are traced scalars <= the static paddings.
    LRU is tracked as a last-touch timestamp instead of the reference
    simulator's per-set age counters: the recency *order* (and so every
    victim choice, including the first-index tie-break among untouched
    ways) is identical, but the state update touches one scalar per
    access instead of a whole way row.  Ways >= `ways` never match
    (masked) and never win victim selection (timestamp pinned to
    int32 max), so hits are bit-identical to the unpadded simulator for
    the same geometry."""
    block = byte_addrs // block_bytes
    set_idx = (block % sets).astype(jnp.int32)
    tag = (block // sets).astype(jnp.int32)
    way_mask = jnp.arange(max_ways) < ways
    imax = jnp.iinfo(jnp.int32).max

    def step(carry, inp):
        tags, ts = carry                     # (max_sets, max_ways)
        s, t, k = inp
        row_tags = tags[s]
        row_ts = ts[s]
        match = (row_tags == t) & way_mask
        hit = jnp.any(match)
        victim_ts = jnp.where(way_mask, row_ts, imax)
        way = jnp.where(hit, jnp.argmax(match), jnp.argmin(victim_ts))
        tags = tags.at[s, way].set(t)
        ts = ts.at[s, way].set(k)
        return (tags, ts), hit

    init = (jnp.full((max_sets, max_ways), -1, jnp.int32),
            jnp.zeros((max_sets, max_ways), jnp.int32))
    stamps = jnp.arange(1, byte_addrs.shape[0] + 1, dtype=jnp.int32)
    _, hits = jax.lax.scan(step, init, (set_idx, tag, stamps))
    return hits


def _geometry_arrays(configs):
    sets = jnp.asarray([c.sets for c in configs], jnp.int32)
    ways = jnp.asarray([c.ways for c in configs], jnp.int32)
    blocks = jnp.asarray([c.block_bytes for c in configs], jnp.int32)
    max_sets = max(c.sets for c in configs)
    max_ways = max(c.ways for c in configs)
    return sets, ways, blocks, max_sets, max_ways


_EXPANDED_TRACE_DEPRECATION = (
    "the expanded-trace per-access lanes are deprecated: serial depth is "
    "O(accesses) per lane.  Use the segment-lane API "
    "(segment_lane_hit_counts / segment_lane_hit_rates / "
    "interference_lane_metrics_batch) which replays the compressed trace "
    "directly.")


def batched_hits(byte_addrs, configs: list[LLCConfig]) -> jax.Array:
    """(n_cfg, T) per-access hit bits — every lane bit-identical to the
    unbatched ``simulate_trace`` at that geometry, one compile total.

    .. deprecated:: kept only as a parity oracle for the segment-lane
       engine; use ``segment_lane_hit_counts``."""
    warnings.warn(_EXPANDED_TRACE_DEPRECATION, DeprecationWarning,
                  stacklevel=2)
    return _batched_hits(byte_addrs, configs)


def _batched_hits(byte_addrs, configs: list[LLCConfig]) -> jax.Array:
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(configs)
    addrs = as_address_array(byte_addrs, what="DBB trace")
    sim = jax.vmap(
        functools.partial(_simulate_padded,
                          max_sets=max_sets, max_ways=max_ways),
        in_axes=(None, 0, 0, 0))
    return sim(addrs, sets, ways, blocks)


def batched_hit_rates(byte_addrs, configs: list[LLCConfig]) -> jax.Array:
    warnings.warn(_EXPANDED_TRACE_DEPRECATION, DeprecationWarning,
                  stacklevel=2)
    return jnp.mean(_batched_hits(byte_addrs, configs).astype(jnp.float32),
                    axis=1)


def segment_sweep_hit_rates(segments, configs: list[LLCConfig]
                            ) -> np.ndarray:
    """(n_cfg,) exact hit rates of one *compressed* trace — each config
    replayed through the segment engine (closed form / per-set rounds),
    so whole-network windows are feasible where per-access expansion is
    not.  Exactly ``hit_rate`` of the expanded trace, per config."""
    from repro.core.cache import simulate_segments

    return np.asarray([simulate_segments(segments, c).hit_rate
                       for c in configs], np.float64)


# --------------------------------------------------------------------------
# segment-lane engine: vmapped segment replay over runtime geometry
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=32)
def _lane_engine(max_sets: int, max_ways: int, r_pad: int,
                 per_lane_trace: bool, collect: bool = False,
                 suffix: str = "full", masked: bool = False,
                 collect_width: int | None = None):
    from repro.core.cache import segment_lane_scan

    if masked and not per_lane_trace:
        raise ValueError("way-masked lanes need per-lane traces "
                         "(each lane carries its own way_sels)")
    in_axes = ((0, 0, 0, 0, 0, 0, 0, 0) if per_lane_trace
               else (None, None, None, None, None, 0, 0, 0))
    if masked:
        in_axes = in_axes + (0,)
    return jax.jit(jax.vmap(
        functools.partial(segment_lane_scan, max_sets=max_sets,
                          max_ways=max_ways, r_pad=r_pad, collect=collect,
                          collect_width=collect_width, suffix=suffix),
        in_axes=in_axes))


@functools.lru_cache(maxsize=32)
def _single_lane_engine(max_sets: int, max_ways: int, r_pad: int,
                        suffix: str, return_state: bool = False,
                        collect_width: int | None = None):
    """One jitted (unvmapped) masked lane — the way-partitioned QoS
    path and the per-request latency attribution both run single
    lanes at exact geometry."""
    from repro.core.cache import segment_lane_scan

    return jax.jit(functools.partial(
        segment_lane_scan, max_sets=max_sets, max_ways=max_ways,
        r_pad=r_pad, collect=True, collect_width=collect_width,
        suffix=suffix, return_state=return_state))


_LANE_WIDTH = 128   # the TPU's lane width: a narrower minor axis pads to it


def _collect_width(live_per_round: int, max_sets: int) -> int:
    """The miss-bit width of a collecting lane program
    (``segment_lane_scan(collect_width=)``): the most ordinals any
    segment retires in one round, ``max(min(n_pre, sets))``, rounded up
    to the lane width, and never past ``max_sets``."""
    lanes = -(-max(1, int(live_per_round)) // _LANE_WIDTH)
    return min(max_sets, _LANE_WIDTH * lanes)


def _lane_plan(trace: list, configs: list[LLCConfig]
               ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side execution plan for one segment stream over a lane
    bucket: per segment, the round-scan rounds needed (max across the
    bucket's geometries — extra rounds in other lanes are masked no-ops)
    and whether the segment is provably cold (byte range disjoint, with
    block-alignment slack, from every earlier segment — all its arrivals
    miss in every lane, so the closed form needs no rounds at all)."""
    from repro.core.cache import _TouchedBlocks

    metas = [_segment_tuple(s) for s in trace]
    base = np.asarray([m[0] for m in metas], np.int64)
    stride = np.asarray([m[1] for m in metas], np.int64)
    count = np.asarray([m[2] for m in metas], np.int64)
    live = count > 0
    last = base + np.maximum(count - 1, 0) * stride
    slack = max(c.block_bytes for c in configs) - 1
    touched = _TouchedBlocks()
    cold = np.zeros(len(metas), bool)
    for j in range(len(metas)):
        if not live[j]:
            continue
        lo, hi = int(base[j] - slack), int(last[j] + slack)
        cold[j] = not touched.overlaps(lo, hi)
        touched.add(lo, hi)
    r = np.zeros(len(metas), np.int64)
    for c in configs:
        nb = last // c.block_bytes - base // c.block_bytes + 1
        r = np.maximum(r, np.minimum(c.ways, -(-nb // c.sets)))
    r = np.where(live & ~cold, r, 0)
    return r.astype(np.int32), cold


_segment_tuple = traces.segment_tuple


def _lane_meta_arrays(lanes: list[list]) -> tuple:
    """Per-lane segment streams -> (n_lane, max_segments) int32 metadata
    arrays, padded with count == 0 no-op segments."""
    n_seg = max((len(t) for t in lanes), default=0)
    shape = (len(lanes), max(1, n_seg))
    bases = np.zeros(shape, np.int32)
    strides = np.ones(shape, np.int32)
    counts = np.zeros(shape, np.int32)
    for i, trace in enumerate(lanes):
        for j, seg in enumerate(trace):
            bases[i, j], strides[i, j], counts[i, j] = _segment_tuple(seg)
    return jnp.asarray(bases), jnp.asarray(strides), jnp.asarray(counts)


class UnsupportedTraceError(ValueError):
    """A lane trace the segment-lane engine cannot replay (a segment
    stride outside ``(0, block_bytes]``).  The only batch failure the
    campaign executor answers with the sequential path, which expands
    such segments exactly."""


def _check_lane_support(lanes, configs) -> None:
    int32_max = np.iinfo(np.int32).max
    min_block = min(c.block_bytes for c in configs)
    for trace in lanes:
        total = 0
        for seg in trace:
            base, stride, count = _segment_tuple(seg)
            if count <= 0:
                continue
            total += count
            if stride <= 0 or stride > min_block:
                raise UnsupportedTraceError(
                    f"segment stride {stride} outside (0, {min_block}] — "
                    "the segment-lane engine needs stride <= block_bytes "
                    "in every lane; use segment_sweep_hit_rates for "
                    "sparse-stride traces")
            if base + count * stride > int32_max:
                raise OverflowError(
                    "segment addresses exceed int32 — the lane engine "
                    "keeps metadata in 32-bit; rebase the trace")
        if total > int32_max:
            raise OverflowError(
                f"lane trace has {total} accesses — the lane engine's "
                "global LRU timestamp is int32; split multi-frame sweeps "
                "into per-frame lane calls")


def _check_lane_support_meta(lanes_meta, configs) -> None:
    """`_check_lane_support` over (bases, strides, counts) array lanes —
    the same constraints, vectorized."""
    int32_max = np.iinfo(np.int32).max
    min_block = min(c.block_bytes for c in configs)
    for base, stride, count in lanes_meta:
        live = count > 0
        bad = live & ((stride <= 0) | (stride > min_block))
        if np.any(bad):
            raise UnsupportedTraceError(
                f"segment stride {int(stride[bad][0])} outside "
                f"(0, {min_block}] — the segment-lane engine needs "
                "stride <= block_bytes in every lane; use "
                "segment_sweep_hit_rates for sparse-stride traces")
        if np.any(live & (base + count * stride > int32_max)):
            raise OverflowError(
                "segment addresses exceed int32 — the lane engine "
                "keeps metadata in 32-bit; rebase the trace")
        if int(count[live].sum()) > int32_max:
            raise OverflowError(
                f"lane trace has {int(count[live].sum())} accesses — "
                "the lane engine's global LRU timestamp is int32; split "
                "multi-frame sweeps into per-frame lane calls")


def lane_buckets(configs: list[LLCConfig], waste: int = 2) -> list[list[int]]:
    """Partition lane indices into buckets of comparable set counts so a
    2-set lane doesn't pay a 4096-set lane's padding: lanes sorted by
    descending sets, a new bucket whenever a lane has fewer than
    1/`waste` of its bucket's maximum.  A homogeneous grid stays one
    bucket (one compiled program).  Deterministic for a given config
    list — the campaign executor (``repro.campaign``) also uses it to
    shard sweep points into lane-shaped work units."""
    order = sorted(range(len(configs)), key=lambda i: -configs[i].sets)
    buckets: list[list[int]] = []
    bucket_max = None
    for i in order:
        if bucket_max is None or configs[i].sets * waste < bucket_max:
            buckets.append([])
            bucket_max = configs[i].sets
        buckets[-1].append(i)
    return buckets


def segment_lane_hit_counts(segments, configs: list[LLCConfig]
                            ) -> np.ndarray:
    """(n_cfg, n_segments) exact per-segment LLC hit counts of a
    compressed trace, geometry lanes vmapped into compiled device
    programs.

    ``segments`` is either one shared trace (list of ``Segment``/tuples,
    the Fig. 5 shape: one DBB stream, many geometries) or a list of
    per-lane traces (the Fig. 6 shape: one geometry, many co-runner
    mixes) — per-lane streams are padded to the longest lane with
    count-0 no-op segments.  Unlike ``batched_hits`` the trace is never
    expanded: serial depth is O(segments * max_ways), not O(accesses),
    so full-frame multi-config sweeps are feasible.  Lanes with wildly
    different set counts are bucketed (``_lane_buckets``) so padding
    waste stays bounded — a homogeneous grid is exactly one program.
    Hit counts are bit-identical to the expanded-trace ``batched_hits``
    per lane (tests/test_sweep.py)."""
    per_lane = bool(segments) and isinstance(segments[0], list)
    lanes = segments if per_lane else [list(segments)] * len(configs)
    if per_lane and len(lanes) != len(configs):
        raise ValueError(f"{len(lanes)} lane traces for "
                         f"{len(configs)} configs")
    with tracing.span(tracing.LANE_PLAN):
        _check_lane_support(lanes, configs)
    n_seg = max((len(t) for t in lanes), default=0)
    out = np.zeros((len(configs), max(1, n_seg)), np.int64)
    for bucket in lane_buckets(configs):
        with tracing.span(tracing.LANE_BATCH):
            with tracing.span(tracing.LANE_PLAN):
                cfgs_b = [configs[i] for i in bucket]
                sets, ways, blocks, max_sets, max_ways = _geometry_arrays(
                    cfgs_b)
                engine = _lane_engine(max_sets, max_ways, max_ways, per_lane)
                if per_lane:
                    traces_b = [lanes[i] for i in bucket]
                    bases, strides, counts = _lane_meta_arrays(traces_b)
                    plans = [_lane_plan(t, cfgs_b) for t in traces_b]
                    s_pad = bases.shape[1]
                    r_needed = np.zeros((len(bucket), s_pad), np.int32)
                    cold = np.zeros((len(bucket), s_pad), bool)
                    for row, (r, c) in enumerate(plans):
                        r_needed[row, :len(r)] = r
                        cold[row, :len(c)] = c
                    rounds = np.minimum(r_needed, max_ways).max(axis=0).sum()
                else:
                    bases, strides, counts = (a[0] for a in
                                              _lane_meta_arrays(lanes[:1]))
                    r, c = _lane_plan(lanes[0], cfgs_b)
                    s_pad = int(bases.shape[0])  # >= 1 even for [] traces
                    r_needed = np.zeros(s_pad, np.int32)
                    cold = np.zeros(s_pad, bool)
                    r_needed[:len(r)] = r
                    cold[:len(c)] = c
                    rounds = np.minimum(r_needed, max_ways).sum()
            with tracing.span(tracing.DISPATCH):
                r_needed, cold = jnp.asarray(r_needed), jnp.asarray(cold)
                hits_dev = engine(bases, strides, counts, r_needed, cold,
                                  sets, ways, blocks)
            tracing.count(tracing.PROGRAMS, 1)
            tracing.count(tracing.SCAN_ROUNDS, rounds)
            tracing.count(tracing.FETCH_BYTES, hits_dev.nbytes)
            scanned = sum(len(lanes[i]) for i in bucket)   # nothing compacts
            tracing.count(tracing.LANE_SEGMENTS, scanned)
            tracing.count(tracing.LANE_SEGMENTS_RAW, scanned)
            with tracing.span(tracing.FETCH):
                hits = np.asarray(hits_dev, np.int64)
            for row, i in enumerate(bucket):
                out[i, :hits.shape[1]] = hits[row]
    return out


def segment_lane_hit_rates(segments, configs: list[LLCConfig]
                           ) -> np.ndarray:
    """(n_cfg,) exact hit rates — ``segment_lane_hit_counts`` over the
    per-lane access totals."""
    per_lane = bool(segments) and isinstance(segments[0], list)
    lanes = segments if per_lane else [list(segments)] * len(configs)
    hits = segment_lane_hit_counts(segments, configs).sum(axis=1)
    accesses = np.asarray(
        [max(1, sum(max(0, _segment_tuple(s)[2]) for s in t))
         for t in lanes], np.int64)
    return hits / accesses


def batched_hits_per_trace(byte_addrs_2d, configs: list[LLCConfig]
                           ) -> jax.Array:
    """Like ``batched_hits`` but with one trace per lane (n_cfg, T).

    .. deprecated:: the interference sweep now feeds compressed
       co-runner lanes to the segment engine
       (``interference_lane_metrics_batch``)."""
    warnings.warn(_EXPANDED_TRACE_DEPRECATION, DeprecationWarning,
                  stacklevel=2)
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(configs)
    sim = jax.vmap(
        functools.partial(_simulate_padded,
                          max_sets=max_sets, max_ways=max_ways),
        in_axes=(0, 0, 0, 0))
    return sim(as_address_array(byte_addrs_2d, what="DBB trace"),
               sets, ways, blocks)


# --------------------------------------------------------------------------
# Fig. 5 — LLC geometry sweep
# --------------------------------------------------------------------------
def grid_configs(sizes_kib, blocks) -> dict[tuple, LLCConfig]:
    """The Fig. 5 grid's (size, block) -> LLCConfig mapping — delegates
    to ``repro.core.soc.llc_config_for`` so the simulated and
    closed-form sweeps always describe the same geometry."""
    from repro.core.soc import llc_config_for

    return {(size, block): llc_config_for(size, block)
            for block in blocks for size in sizes_kib}


def sweep_llc(sizes_kib=(0.5, 2, 8, 64, 512, 1024, 4096),
              blocks=(32, 64, 128), *, soc=None,
              window_bursts: int | None = 4096) -> SweepGrid:
    """Fig. 5, batched: the closed-form timing grid (``.speedups``,
    ``.no_llc_s``) plus exact simulated hit rates for every geometry
    (``.sim_hit_rates``) from a single vmapped segment-lane program,
    as a typed ``SweepGrid``.

    ``window_bursts=None`` simulates the *entire* YOLOv3 frame (at
    stream granularity — the whole-network compressed trace); an integer
    clips to an arbiter-interleaved window of a representative layer as
    before.  Either way the trace stays compressed end to end: serial
    depth scales with segment count, not burst count."""
    from repro.core.soc import SoCConfig, llc_sweep as _closed_form

    soc = soc or SoCConfig()
    cf = _closed_form(sizes_kib=sizes_kib, blocks=blocks, soc=soc)
    cfgs = grid_configs(sizes_kib, blocks)
    if window_bursts is None:
        win = traces.network_trace()
    else:
        win = traces.default_dbb_window(max_bursts=window_bursts)
    rates = segment_lane_hit_rates(win, list(cfgs.values()))
    return SweepGrid(
        kind="llc",
        no_llc_s=cf["no_llc_s"],
        speedups=cf["grid"],
        sim_hit_rates={key: float(r) for key, r in zip(cfgs, rates)},
        window_bursts=traces.total_bursts(win))


# --------------------------------------------------------------------------
# Fig. 6 — interference sweep
# --------------------------------------------------------------------------
def corunner_segments(nvdla_segs: list, *, llc: LLCConfig,
                      mix: MixConfig, chunk_bursts: int = 16
                      ) -> tuple[list, np.ndarray]:
    """One lane's interleaved trace, *compressed*: a `chunk_bursts`-burst
    NVDLA chunk, then `chunk_bursts` 64 B write lines from each of the
    mix's `corunners` BwWrite cores, round-robin — the DBB/front-bus
    arbiter at chunk granularity.  Returns (segments,
    nvdla_label_mask); each co-runner's stream stays a valid stride run
    (wraps in its working-set span split at the wrap point).  Working
    sets: "llc" wraps inside half the LLC (occupies it), "dram" streams
    far past it (sweeps it), "l1" never reaches the shared fabric (no
    co-runner accesses)."""
    n = 0 if mix.wss == "l1" else mix.corunners
    chunks = [c for s in nvdla_segs for c in s.split(chunk_bursts)]
    spans_regions = _corunner_spans(llc, mix)
    cursors = [0] * n
    segs: list[traces.Segment] = []
    labels: list[bool] = []
    for chunk in chunks:
        segs.append(chunk)
        labels.append(True)
        for w in range(n):
            left = chunk.count
            span_lines, region = spans_regions[w]
            while left > 0:                   # split at working-set wrap
                start = cursors[w] % span_lines
                take = min(left, span_lines - start)
                segs.append(traces.Segment(region + start * 64, 64, take,
                                           f"bw{w}"))
                labels.append(False)
                cursors[w] += take
                left -= take
    return segs, np.asarray(labels)


def _corunner_spans(llc: LLCConfig, mix: MixConfig) -> list[tuple[int, int]]:
    """Each co-runner's (span_lines, region_base) — the one definition
    ``corunner_segments`` and ``corunner_meta`` share."""
    n = 0 if mix.wss == "l1" else mix.corunners
    spans_regions = []
    for w in range(n):
        if mix.wss == "llc":
            span = max(64, llc.size_bytes // 2)
            region = 0x4000_0000 + w * 0x0100_0000
        else:                                             # "dram"
            span = llc.size_bytes * 8
            region = 0x6000_0000 + w * 0x0800_0000
        # stagger start banks (2 KiB row offsets) like the NVDLA regions
        # in repro.core.traces — co-runners don't all start on bank 0
        region += (5 + 7 * w) * 2048
        spans_regions.append((span // 64, region))
    return spans_regions


def nvdla_chunks(nvdla_segs: list, chunk_bursts: int = 16) -> tuple:
    """The chunked NVDLA stream as ``(bases, strides, counts)`` int64
    arrays — ``Segment.split(chunk_bursts)`` over the whole window,
    array-native.  Depends only on the trace, not the lane's geometry
    or mix, so batched callers compute it once per shard and pass it to
    every ``corunner_meta`` call (``_chunks``)."""
    cb, cs, cc = [], [], []
    for s in nvdla_segs:
        base, stride, count = _segment_tuple(s)
        if count <= 0:
            continue
        n_ch = -(-count // chunk_bursts)
        idx = np.arange(n_ch, dtype=np.int64)
        cb.append(base + idx * (chunk_bursts * stride))
        cs.append(np.full(n_ch, stride, np.int64))
        cnt = np.full(n_ch, chunk_bursts, np.int64)
        cnt[-1] = count - (n_ch - 1) * chunk_bursts
        cc.append(cnt)
    if not cb:
        z = np.zeros(0, np.int64)
        return z, z.copy(), z.copy()
    return tuple(np.concatenate(a) for a in (cb, cs, cc))


def corunner_meta(nvdla_segs: list, *, llc: LLCConfig, mix: MixConfig,
                  chunk_bursts: int = 16, _chunks: tuple | None = None
                  ) -> tuple:
    """Array-native twin of ``corunner_segments``: the same interleaved
    lane trace as ``(bases, strides, counts, nvdla_mask)`` int64/bool
    numpy arrays — segment for segment identical to
    ``[segment_tuple(s) for s in corunner_segments(...)[0]]`` — built
    with no per-segment Python objects, so the batched lane path's
    trace construction is O(numpy) instead of O(segments) interpreter
    work.  ``_chunks`` takes a precomputed ``nvdla_chunks`` result
    (lane-invariant, so batch callers share one).  Falls back to
    materializing ``corunner_segments`` when a co-runner chunk wraps
    its working set more than once (spans smaller than a chunk)."""
    n, wss = mix.corunners, mix.wss
    if wss == "l1":
        n = 0
    cb, cs, cc = (_chunks if _chunks is not None
                  else nvdla_chunks(nvdla_segs, chunk_bursts))
    if cb.shape[0] == 0:
        z = np.zeros(0, np.int64)
        return z, z.copy(), z.copy(), np.zeros(0, bool)
    n_ch = cb.shape[0]
    if n == 0:
        return cb, cs, cc, np.ones(n_ch, bool)
    pre = np.concatenate([[0], np.cumsum(cc)[:-1]])   # cursor before chunk
    chunk_i = np.arange(n_ch, dtype=np.int64)
    parts = [(cb, cs, cc, chunk_i, np.zeros(n_ch, np.int64), True)]
    for w, (span_lines, region) in enumerate(_corunner_spans(llc, mix)):
        start = pre % span_lines
        take1 = np.minimum(cc, span_lines - start)
        rest = cc - take1
        if np.any(rest > span_lines):     # >2 wraps: rare tiny spans
            segs, nv = corunner_segments(nvdla_segs, llc=llc, mix=mix,
                                         chunk_bursts=chunk_bursts)
            m = np.asarray([_segment_tuple(sg) for sg in segs],
                           np.int64).reshape(-1, 3)
            return m[:, 0], m[:, 1], m[:, 2], np.asarray(nv, bool)
        s64 = np.full(n_ch, 64, np.int64)
        parts.append((region + start * 64, s64, take1, chunk_i,
                      np.full(n_ch, 1 + 2 * w, np.int64), False))
        j2 = np.flatnonzero(rest > 0)
        if j2.size:
            parts.append((np.full(j2.size, region, np.int64),
                          np.full(j2.size, 64, np.int64), rest[j2], j2,
                          np.full(j2.size, 2 + 2 * w, np.int64), False))
    bases = np.concatenate([p[0] for p in parts])
    strides = np.concatenate([p[1] for p in parts])
    counts = np.concatenate([p[2] for p in parts])
    chunks = np.concatenate([p[3] for p in parts])
    slots = np.concatenate([p[4] for p in parts])
    nv = np.concatenate([np.full(p[0].shape[0], p[5], bool)
                         for p in parts])
    order = np.lexsort((slots, chunks))   # chunk-major, arbiter slots
    return bases[order], strides[order], counts[order], nv[order]


@dataclasses.dataclass(frozen=True)
class LaneRecords:
    """One lane compacted into records (``cache.record_lane_scan``):
    per record and member (S, P) int64 ``bases``, ``strides``,
    ``counts`` (the member's accesses in the record), ``chunks`` (its
    accesses per repeat) and ``offsets`` (the chunks of the members
    before it); per record (S,) ``periods`` (accesses per repeat) and
    ``raw`` (the uncompacted segments it stands for); ``nv`` (S, P)
    marks NVDLA members.  Live members come first in every record."""
    bases: np.ndarray
    strides: np.ndarray
    counts: np.ndarray
    chunks: np.ndarray
    offsets: np.ndarray
    periods: np.ndarray
    raw: np.ndarray
    nv: np.ndarray

    @property
    def members(self) -> int:
        """Members of the widest record: 1 when every record is one
        plain segment."""
        live = (self.counts > 0).sum(axis=1)
        return max(1, int(live.max(initial=1)))


def compact_lane(b, s, c, nv, llc: LLCConfig, members: int) -> LaneRecords:
    """Compact one interleaved lane (``corunner_meta``'s arrays) into
    records: each maximal run of two or more arbiter rounds in which
    every member — the NVDLA chunk, then each co-runner's — continues
    its own stride run with the same chunk becomes one record (the last
    round may be shorter).  A run breaks where an NVDLA segment ends, a
    co-runner wraps in its working set (its round has more pieces) or
    chunk sizes change.  Every other segment stays a record of its own.

    A run of several members is kept only where the record engine's
    merge is exact at ``llc``: the members' block ranges are disjoint,
    each chunk spans at least one block, and a block a member's chunks
    share sees fewer than ``ways`` arrivals of the other members in its
    set in between (at most one chunk of each).  The records replay
    exactly the lane's access order.  ``members`` is the lane's segments
    per round without a wrap: 1 + its co-runners."""
    b, s, c = (np.asarray(a, np.int64) for a in (b, s, c))
    nv = np.asarray(nv, bool)
    n_seg = c.shape[0]
    starts = np.flatnonzero(nv)          # every round opens with the NVDLA
    if n_seg == 0 or starts.size == 0 or starts[0] != 0:
        raise ValueError("a lane is a sequence of arbiter rounds, each "
                         "opened by one NVDLA chunk")
    size = np.diff(np.append(starts, n_seg))
    p = members
    normal = size == p
    at = np.minimum(starts[:, None] + np.arange(p)[None, :], n_seg - 1)
    B, St, Cn = b[at], s[at], c[at]
    cont = (normal[:-1] & normal[1:] & (St[1:] == St[:-1]).all(1)
            & (B[1:] == B[:-1] + Cn[:-1] * St[:-1]).all(1))
    eq = cont & (Cn[1:] == Cn[:-1]).all(1)
    tail = cont & (Cn[1:] < Cn[:-1]).all(1) & ~np.append(eq[1:], False)
    link = eq | tail
    link[1:] &= ~tail[:-1]
    edge = np.diff(np.concatenate([[0], link.astype(np.int8), [0]]))
    first, last = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    if p > 1 and first.size:
        first, last = _exact_runs(first, last, B, St, Cn, llc)
    covered = np.zeros(starts.shape[0] + 1, np.int64)
    np.add.at(covered, first, 1)
    np.add.at(covered, last + 1, -1)
    in_run = np.repeat(np.cumsum(covered[:-1]) > 0, size)
    # the runs: members from their first round, accesses summed
    cum = np.vstack([np.zeros((1, p), np.int64), np.cumsum(Cn, axis=0)])
    r_count = cum[last + 1] - cum[first]
    r_chunk = Cn[first] if p > 1 else r_count
    plain = np.flatnonzero(~in_run)
    z = np.zeros((plain.size, p), np.int64)
    one = np.zeros((plain.size, p), np.int64)
    one[:, 0] = 1

    def stack(run_part, seg_part):
        return np.concatenate([run_part, seg_part])

    order = np.argsort(stack(starts[first], plain), kind="stable")
    counts = stack(r_count, z + one * c[plain, None])
    chunks = stack(r_chunk, z + one * c[plain, None])
    return LaneRecords(
        bases=stack(B[first], z + one * b[plain, None])[order],
        strides=stack(St[first], np.where(one, s[plain, None], 1))[order],
        counts=counts[order],
        chunks=chunks[order],
        offsets=(np.cumsum(chunks, axis=1) - chunks)[order],
        periods=chunks.sum(axis=1)[order],
        raw=stack((last - first + 1) * p, np.ones(plain.size, np.int64)
                  )[order],
        nv=stack(np.arange(p)[None, :].repeat(first.size, 0) == 0,
                 (one > 0) & nv[plain, None])[order])


def _exact_runs(first, last, B, St, Cn, llc: LLCConfig):
    """The runs of several members the record engine replays exactly at
    ``llc`` (``compact_lane``)."""
    bb, sets, ways = llc.block_bytes, llc.sets, llc.ways
    width = Cn[first] * St[first]                     # bytes per chunk
    lo = B[first] // bb
    hi = (B[last] + (Cn[last] - 1) * St[last]) // bb
    ok = (width >= bb).all(1)
    p = B.shape[1]
    for m in range(p):
        for m2 in range(m + 1, p):
            ok &= (hi[:, m] < lo[:, m2]) | (hi[:, m2] < lo[:, m])
    # a shared boundary block waits out one chunk of every other member
    arrivals = -(-(-(-width // bb) + 1) // sets)
    shares = ((B[first] % bb) != 0) | ((width % bb) != 0)
    others = arrivals.sum(1, keepdims=True) - arrivals
    ok &= ~(shares & (others >= ways)).any(1)
    return first[ok], last[ok]


def _lane_metrics_from_runs(*, n_segments, accesses, hits, runs, bb, nv,
                            dram, t_llc_hit, nv_acc, nv_hits) -> LaneMetrics:
    """The shared lane reduction: exact LLC counts + miss runs
    ((first_block, n_blocks, seg_idx) triples in access order, either a
    list of tuples or a tuple of three aligned int64 arrays) ->
    closed-form DRAM row hits -> closed-form latency total -> the typed
    record.  Both the sequential and the batched path end here, so
    their metrics are bit-identical by construction."""
    from repro.core.dram import segment_row_hits

    if isinstance(runs, tuple):
        fb, nbk, sidx = (np.asarray(a, np.int64) for a in runs)
    else:
        arr = np.asarray(runs, np.int64).reshape(-1, 3)
        fb, nbk, sidx = arr[:, 0], arr[:, 1], arr[:, 2]
    row = segment_row_hits((fb * bb, np.full(fb.shape[0], bb, np.int64),
                            nbk), dram)
    run_is_nv = np.asarray(nv, bool)[sidx]
    nv_miss = int(nbk[run_is_nv].sum())
    nv_row_hits = int(row.per_segment[run_is_nv].sum())
    misses = accesses - hits
    row_misses = misses - row.row_hits
    total = (accesses * t_llc_hit + misses * dram.t_cas_cycles
             + row_misses * (dram.t_rp_cycles + dram.t_rcd_cycles))
    return LaneMetrics(
        segments=n_segments,
        accesses=int(accesses),
        llc_hits=int(hits),
        dram_row_hits=int(row.row_hits),
        t_llc_hit=int(t_llc_hit),
        total_cycles=int(total),
        hit_rate=hits / max(1, accesses),
        nvdla_accesses=nv_acc,
        nvdla_hits=nv_hits,
        nvdla_hit_rate=nv_hits / max(1, nv_acc),
        nvdla_misses=nv_miss,
        nvdla_miss_row_hits=nv_row_hits,
        nvdla_miss_row_hit_rate=(nv_row_hits / nv_miss
                                 if nv_miss else 1.0))


def _check_row_block(llc: LLCConfig, dram) -> None:
    if dram.row_bytes % llc.block_bytes:
        raise ValueError("row_bytes must be a multiple of block_bytes "
                         "for the segment-native interference lane")


def partition_way_sels(nv_mask, llc: LLCConfig, way_mask: int) -> np.ndarray:
    """Per-segment allocation masks for an LLC way partition: the
    victim (NVDLA/NPU) segments allocate only into ``way_mask``'s ways,
    co-runner segments into the complement — Intel-CAT-style two-class
    partitioning.  ``way_mask == (1 << ways) - 1`` (the full mask)
    means *no* partition: both classes allocate anywhere, bit-exactly
    the unpartitioned scan (the invariant tests/test_waymask.py pins).

    Raises ``ValueError`` when the victim mask selects no real way —
    an empty partition cannot allocate."""
    full = (1 << llc.ways) - 1
    vm = int(way_mask) & full
    if vm == 0:
        raise ValueError(
            f"way_mask {way_mask:#x} selects none of the {llc.ways} "
            "ways — the victim partition must hold at least one way")
    co = full & ~vm
    if co == 0:
        co = full        # full victim mask == unpartitioned for everyone
    return np.where(np.asarray(nv_mask, bool), vm, co).astype(np.int32)


def _masked_lane_run(b, s, c, llc: LLCConfig, way_sels,
                     *, return_state: bool = False):
    """One way-partitioned lane through the masked segment kernel:
    every segment carries a non-zero allocation mask, so the plan gives
    every segment its full ``ceil(n_blocks / sets)`` rounds (no closed
    -form suffix — the suffix assumes unrestricted victim cycling) and
    miss runs are reconstructed with ``full_prefix=True``.  Returns
    (per_segment_hits, miss_run_arrays[, final_state])."""
    bb, sets, ways = llc.block_bytes, llc.sets, llc.ways
    live = c > 0
    last = b + np.maximum(c - 1, 0) * s
    nb = np.where(live, last // bb - b // bb + 1, 0)
    r_needed = (-(-nb // sets)).astype(np.int32)
    r_pad = max(1, int(r_needed.max(initial=1)))
    width = _collect_width(np.minimum(nb, sets).max(initial=0), sets)
    cold = np.zeros(b.shape[0], bool)
    engine = _single_lane_engine(sets, ways, r_pad, "none",
                                 return_state=return_state,
                                 collect_width=width)
    out = engine(jnp.asarray(b, jnp.int32), jnp.asarray(s, jnp.int32),
                 jnp.asarray(c, jnp.int32), jnp.asarray(r_needed),
                 jnp.asarray(cold), sets, ways, bb,
                 jnp.asarray(way_sels, jnp.int32))
    hits = np.asarray(out[0], np.int64)
    runs = _lane_miss_runs(b, s, c, llc, cold, np.asarray(out[1]),
                           full_prefix=True)
    if return_state:
        return hits, runs, jax.tree.map(np.asarray, out[2])
    return hits, runs


def interference_lane_metrics(nvdla_segs: list, *, llc: LLCConfig,
                              dram, mix: MixConfig,
                              chunk_bursts: int = 16,
                              t_llc_hit: int = 20,
                              way_mask: int | None = None) -> LaneMetrics:
    """One interference lane, simulated exactly and reduced to the typed
    ``LaneMetrics`` record a campaign point journals
    (``repro.campaign``): the co-runner-interleaved compressed trace
    goes once through the exact segment LLC engine (per-segment hit
    attribution + exact miss runs), the miss runs through the
    closed-form DRAM row model, and the latency total through the same
    closed form as ``socsim.simulate_dbb_segments`` — so every field is
    deterministic and internally consistent (the executor's guardrails
    recompute the total from the counts and reject any record where
    they disagree).

    ``mix.corunners=0`` (or ``mix.wss="l1"``) is the solo-NVDLA lane.

    ``way_mask`` turns on LLC way partitioning (``partition_way_sels``):
    victim segments allocate only into ``way_mask``'s ways, co-runners
    into the complement.  The full mask is bit-exactly the
    unpartitioned lane."""
    from repro.core.cache import simulate_segments

    bb = llc.block_bytes
    _check_row_block(llc, dram)
    if way_mask is not None:
        b, s, c, nv = corunner_meta(nvdla_segs, llc=llc, mix=mix,
                                    chunk_bursts=chunk_bursts)
        _check_lane_support_meta([(b, s, c)], [llc])
        way_sels = partition_way_sels(nv, llc, way_mask)
        hits, runs = _masked_lane_run(b, s, c, llc, way_sels)
        n_seg = c.shape[0]
        accesses = int(c.sum())
        lane_hits = int(hits[:n_seg].sum())
        if int(runs[1].sum()) != accesses - lane_hits:
            raise RuntimeError(
                "masked lane miss-run reconstruction disagrees with the "
                f"kernel: {int(runs[1].sum())} missed blocks vs "
                f"{accesses - lane_hits} misses")
        return _lane_metrics_from_runs(
            n_segments=n_seg, accesses=accesses, hits=lane_hits,
            runs=runs, bb=bb, nv=nv, dram=dram, t_llc_hit=t_llc_hit,
            nv_acc=int(c[nv].sum()),
            nv_hits=int(hits[:n_seg][nv].sum()))
    segs, nv = corunner_segments(nvdla_segs, llc=llc, mix=mix,
                                 chunk_bursts=chunk_bursts)
    res = simulate_segments(segs, llc, per_segment=True,
                            collect_miss_runs=True)
    counts = np.asarray([s.count for s in segs], np.int64)
    return _lane_metrics_from_runs(
        n_segments=len(segs), accesses=int(res.accesses),
        hits=int(res.hits), runs=res.miss_runs, bb=bb,
        nv=nv, dram=dram, t_llc_hit=t_llc_hit,
        nv_acc=int(counts[nv].sum()),
        nv_hits=int(res.per_segment_hits[nv].sum()))


def lane_request_latencies(nvdla_segs: list, *, llc: LLCConfig, dram,
                           mix: MixConfig, chunk_bursts: int = 16,
                           t_llc_hit: int = 20,
                           way_mask: int | None = None
                           ) -> tuple[np.ndarray, LaneMetrics]:
    """Per-victim-chunk memory latencies of one interference lane — the
    memory half of the farm's tail-latency distribution
    (``repro.core.farm``).

    The lane's closed-form latency identity is linear in per-segment
    counters (``accesses * t_llc_hit + misses * tCAS + row_misses *
    (tRP + tRCD)``), so it distributes exactly over segments: each
    segment's share uses its own access/hit counts plus its row hits
    (attributed from the lane's miss runs).  ``corunner_segments``
    emits exactly one victim segment per ``chunk_bursts``-burst chunk,
    so the victim rows *are* the per-chunk service latencies — returned
    in stream order alongside the lane's ``LaneMetrics``.  The
    per-chunk latencies provably sum to ``metrics.total_cycles`` (the
    identity's linearity; asserted here).

    ``way_mask`` partitions the LLC as in
    ``interference_lane_metrics``."""
    from repro.core.cache import simulate_segments
    from repro.core.dram import segment_row_hits

    bb = llc.block_bytes
    _check_row_block(llc, dram)
    if way_mask is not None:
        b, s, c, nv = corunner_meta(nvdla_segs, llc=llc, mix=mix,
                                    chunk_bursts=chunk_bursts)
        _check_lane_support_meta([(b, s, c)], [llc])
        way_sels = partition_way_sels(nv, llc, way_mask)
        hits, runs = _masked_lane_run(b, s, c, llc, way_sels)
        counts = np.asarray(c, np.int64)
        hits = np.asarray(hits[:counts.shape[0]], np.int64)
    else:
        segs, nv = corunner_segments(nvdla_segs, llc=llc, mix=mix,
                                     chunk_bursts=chunk_bursts)
        res = simulate_segments(segs, llc, per_segment=True,
                                collect_miss_runs=True)
        counts = np.asarray([sg.count for sg in segs], np.int64)
        hits = np.asarray(res.per_segment_hits, np.int64)
        runs = res.miss_runs
    if isinstance(runs, tuple):
        fb, nbk, sidx = (np.asarray(a, np.int64) for a in runs)
    else:
        arr = np.asarray(runs, np.int64).reshape(-1, 3)
        fb, nbk, sidx = arr[:, 0], arr[:, 1], arr[:, 2]
    row = segment_row_hits((fb * bb, np.full(fb.shape[0], bb, np.int64),
                            nbk), dram)
    seg_row = np.zeros(counts.shape[0], np.int64)
    np.add.at(seg_row, sidx, np.asarray(row.per_segment, np.int64))
    misses = counts - hits
    per_seg = (counts * t_llc_hit + misses * dram.t_cas_cycles
               + (misses - seg_row) * (dram.t_rp_cycles
                                       + dram.t_rcd_cycles))
    metrics = _lane_metrics_from_runs(
        n_segments=counts.shape[0], accesses=int(counts.sum()),
        hits=int(hits.sum()), runs=(fb, nbk, sidx), bb=bb, nv=nv,
        dram=dram, t_llc_hit=t_llc_hit, nv_acc=int(counts[nv].sum()),
        nv_hits=int(hits[nv].sum()))
    if int(per_seg.sum()) != metrics.total_cycles:
        raise RuntimeError(
            "per-segment latency attribution does not sum to the lane "
            f"total: {int(per_seg.sum())} vs {metrics.total_cycles}")
    return per_seg[np.asarray(nv, bool)], metrics


def _marginal_lane_metrics(full: LaneMetrics, warm: LaneMetrics
                           ) -> LaneMetrics:
    """Counter-wise difference of two lane records (full − warm), with
    the derived rates recomputed from the differenced counters.  Exact
    whenever ``warm``'s trace is a prefix of ``full``'s: the LLC engine
    and the DRAM open-row carry are both left-to-right, so the prefix's
    counters are unchanged by what follows and subtraction isolates the
    suffix — including the closed-form latency identity, which is linear
    in the counters."""
    d = {f: getattr(full, f) - getattr(warm, f)
         for f in LaneMetrics._INT_FIELDS if f != "t_llc_hit"}
    if full.t_llc_hit != warm.t_llc_hit:
        raise ValueError("marginal lane metrics need matching t_llc_hit")
    nv_miss = d["nvdla_misses"]
    return LaneMetrics(
        t_llc_hit=full.t_llc_hit,
        hit_rate=d["llc_hits"] / max(1, d["accesses"]),
        nvdla_hit_rate=d["nvdla_hits"] / max(1, d["nvdla_accesses"]),
        nvdla_miss_row_hit_rate=(d["nvdla_miss_row_hits"] / nv_miss
                                 if nv_miss else 1.0),
        **d)


def step_lane_metrics(segments: list, *, llc: LLCConfig, dram,
                      mix: MixConfig | None = None,
                      warm_prefix: list | None = None,
                      chunk_bursts: int = 16,
                      t_llc_hit: int = 20) -> LaneMetrics:
    """One scheduler step's DBB stream reduced to a typed lane record —
    the reusable step-latency entry point behind ``repro.serve``.

    Without ``warm_prefix`` this is a cold-cache
    ``interference_lane_metrics`` lane.  With it, the step is simulated
    *after* the prefix (LLC state and DRAM open rows warmed by it, the
    co-runner interleave continuing causally across the boundary) and
    the returned record is the exact marginal cost of the step:
    ``sim(prefix + step) − sim(prefix)``.  Passing the step trace itself
    as its own warm prefix yields the steady-state per-step cost of a
    periodic working set — which is how a serving engine's decode step
    sees occupancy-dependent LLC contention (the Fig. 6 effect): working
    sets that fit the LLC re-hit across steps, and each admitted
    co-resident sequence grows the cyclic re-reference distance until
    the shared cache stops covering it.

    The subtraction is exact, not approximate: ``corunner_segments``
    chunks per segment so the prefix's interleaved trace is a prefix of
    the combined interleaved trace, and every counter (LLC hits, DRAM
    row hits, the latency total) is a left-to-right fold over that
    trace.  ``tests/test_sweep.py`` asserts the identity against an
    explicitly warmed reference."""
    mix = mix or MixConfig()
    if warm_prefix is None:
        return interference_lane_metrics(
            segments, llc=llc, dram=dram, mix=mix,
            chunk_bursts=chunk_bursts, t_llc_hit=t_llc_hit)
    full = interference_lane_metrics(
        list(warm_prefix) + list(segments), llc=llc, dram=dram, mix=mix,
        chunk_bursts=chunk_bursts, t_llc_hit=t_llc_hit)
    warm = interference_lane_metrics(
        list(warm_prefix), llc=llc, dram=dram, mix=mix,
        chunk_bursts=chunk_bursts, t_llc_hit=t_llc_hit)
    return _marginal_lane_metrics(full, warm)


def _lane_miss_runs(base, stride, count, llc: LLCConfig, cold: np.ndarray,
                    miss_bits: np.ndarray, *,
                    full_prefix: bool = False) -> tuple:
    """Reconstruct one lane's exact missed-block runs from the vmapped
    kernel's round-scan miss bits plus the analytically-known suffix
    (every block past the round-scanned prefix misses; a cold segment
    is all suffix).  Runs come out in segment order with blocks
    ascending within a segment — the same access order
    ``simulate_segments(collect_miss_runs=True)`` emits, up to
    adjacent-run splits *within* a segment, which the closed-form row
    model is invariant to (identical expanded access sequence).

    ``miss_bits`` is the lane's (S, r_pad, W) ordinal layout
    (``segment_lane_scan(collect=True)``): bit (j, k, i) is the block at
    ordinal ``k*sets + i`` of segment j, and ``i < sets`` for every set
    bit, so ``np.nonzero`` yields the missed ordinals already in
    (segment, ordinal) order.  ``W`` is whatever the program was given
    (``_collect_width``): any width that covers each round's live
    ordinals decodes the same.

    ``base/stride/count`` are the lane's (n_segments,) metadata arrays;
    returns ``(first_blocks, n_blocks, seg_idx)`` int64 arrays, fully
    vectorized — no per-segment interpreter work.

    ``full_prefix`` matches a way-masked lane's plan: every segment
    retired entirely in the round scan (the kernel forces
    n_pre == n_blocks for mask != 0 segments), so there is no analytic
    suffix and every miss is a collected bit."""
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
    sj, kj, ij = np.nonzero(miss_bits[:n_seg])
    sj = sj.astype(np.int64)
    ordv = kj.astype(np.int64) * sets + ij
    first = np.ones(sj.shape[0], bool)
    if sj.shape[0]:
        first[1:] = (sj[1:] != sj[:-1]) | (ordv[1:] != ordv[:-1] + 1)
    pos = np.flatnonzero(first)
    run_seg = sj[pos]
    run_ord = ordv[pos]
    run_len = np.diff(np.append(pos, sj.shape[0]))
    # the analytic suffix is one contiguous run [n_pre, nb) per segment,
    # merged into the last round-scan run when it abuts it
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


def _mesh_shard_lanes(arrays, mesh, zero=(2, 3, 4)):
    """Pad the lane axis to a multiple of the mesh size with no-op lanes
    (geometry and metadata repeated so traced scalars stay in range, the
    arrays at positions ``zero`` — counts and round plans — zeroed) and
    place every operand lane-sharded, so the jitted vmap runs one lane
    shard per device (computation follows data)."""
    from jax.sharding import NamedSharding, PartitionSpec

    arrays = [np.asarray(a) for a in arrays]
    n_dev = int(np.prod(list(mesh.shape.values())))
    pad = (-arrays[0].shape[0]) % n_dev
    if pad:
        arrays = [np.concatenate(
            [a, np.zeros((pad,) + a.shape[1:], a.dtype) if k in zero
             else np.repeat(a[:1], pad, axis=0)])
            for k, a in enumerate(arrays)]
    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    return [jax.device_put(a, sharding) for a in arrays]


def interference_lane_metrics_batch(nvdla_segs: list, *, llcs, drams,
                                    mixes, chunk_bursts: int = 16,
                                    t_llc_hit: int = 20,
                                    mesh=None,
                                    way_masks=None) -> list[LaneMetrics]:
    """Many interference lanes as vmapped lane programs — the campaign
    executor's data-parallel path (``repro.campaign.executor``).

    ``llcs``/``drams``/``mixes`` are equal-length per-lane config
    sequences; lanes are bucketed by set count (``lane_buckets``) so
    padding waste stays bounded, and each bucket runs as ONE compiled
    program: the geometry-traced segment kernel with miss-bit
    collection (``segment_lane_scan(collect=True)``), vmapped over
    lanes.  Per lane, the host reconstructs the exact missed-block runs
    (``_lane_miss_runs``) and finishes with the same closed-form
    DRAM/latency reduction as the sequential path, so every
    ``LaneMetrics`` is bit-identical to
    ``interference_lane_metrics`` for that lane — the executor
    journals batch results interchangeably with sequential ones.

    Each unmasked lane is compacted first (``compact_lane``): where the
    records shorten a bucket's padded scan by more than their members
    cost (``_records_that_pay``), the bucket runs as one
    ``record_lane_scan`` program instead, and its miss runs are decoded
    in the uncompacted trace's order (``_record_miss_runs``) — the same
    metrics, bit for bit.  The whole frame's lanes take it; the Fig. 6
    windows, whose NVDLA chunks never continue one another, do not.

    ``mesh`` (a 1-D ``jax.sharding.Mesh``, see
    ``repro.launch.mesh.make_sweep_mesh``) shards the lane axis across
    devices; ``mesh=None`` runs the same program on one device.

    Raises ``UnsupportedTraceError`` (a ``ValueError``) if any lane's
    trace falls outside the segment engine's support (stride >
    block_bytes) — callers fall back to the sequential path, which
    expands such segments exactly.

    ``way_masks`` is an equal-length sequence of per-lane LLC way
    partitions (``int`` victim masks, or ``None`` for unpartitioned
    lanes) — masked and unmasked lanes mix freely in one compiled
    batch via the kernel's zero-mask sentinel."""
    lanes_n = len(llcs)
    if not (len(drams) == len(mixes) == lanes_n):
        raise ValueError(
            f"llcs/drams/mixes lengths disagree: {lanes_n}/"
            f"{len(drams)}/{len(mixes)}")
    if way_masks is not None and len(way_masks) != lanes_n:
        raise ValueError(
            f"way_masks length {len(way_masks)} != lanes {lanes_n}")
    if lanes_n == 0:
        return []
    with tracing.span(tracing.LANE_PLAN):
        chunks = nvdla_chunks(nvdla_segs, chunk_bursts)
        lanes, nv_masks, lane_sels = [], [], []
        for i, (llc, dram, mix) in enumerate(zip(llcs, drams, mixes)):
            _check_row_block(llc, dram)
            b, s, c, nv = corunner_meta(nvdla_segs, llc=llc, mix=mix,
                                        chunk_bursts=chunk_bursts,
                                        _chunks=chunks)
            lanes.append((b, s, c))
            nv_masks.append(nv)
            wm = way_masks[i] if way_masks is not None else None
            lane_sels.append(None if wm is None
                             else partition_way_sels(nv, llc, wm))
        masked = way_masks is not None
        if masked and mesh is not None:
            raise ValueError("way-masked batches do not support mesh "
                             "sharding yet — pass mesh=None")
        _check_lane_support_meta(lanes, llcs)
    records = [None] * lanes_n
    with tracing.span(tracing.COMPACT):
        # way-masked lanes replay every segment in the round scan, so
        # only unmasked batches compact; a record folds repeats of an
        # NVDLA chunk that continues the one before, so a trace with
        # none (the Fig. 6 windows) has nothing to fold
        if not masked and _chunks_continue(chunks):
            records = [compact_lane(*lanes[i], nv_masks[i], llcs[i],
                                    1 + (0 if m.wss == "l1" else m.corunners))
                       for i, m in enumerate(mixes)]
    out: list[LaneMetrics | None] = [None] * lanes_n
    for bucket in lane_buckets(llcs):
        with tracing.span(tracing.LANE_BATCH):
            with tracing.span(tracing.LANE_PLAN):
                cfgs_b = [llcs[i] for i in bucket]
                recs = _records_that_pay([records[i] for i in bucket],
                                         [lanes[i] for i in bucket])
                raw = [lanes[i][2].shape[0] for i in bucket]
                tracing.count(tracing.LANE_SEGMENTS_RAW, sum(raw))
                tracing.count(tracing.LANE_SEGMENTS, sum(
                    raw if recs is None else [r.raw.shape[0] for r in recs]))
                if recs is not None and max(r.members for r in recs) > 1:
                    run = _record_program(recs, cfgs_b)
                else:
                    views = ([(*lanes[i], nv_masks[i]) for i in bucket]
                             if recs is None else
                             # one member each: plain segments
                             [(r.bases[:, 0], r.strides[:, 0],
                               r.counts[:, 0], r.nv[:, 0]) for r in recs])
                    run = _segment_program(views, cfgs_b,
                                           [lane_sels[i] for i in bucket],
                                           masked)
            got = run([drams[i] for i in bucket], t_llc_hit, mesh)
        for i, n, m in zip(bucket, raw, got):
            out[i] = dataclasses.replace(m, segments=n)
    return out


def _chunks_continue(chunks) -> bool:
    """Whether any of the NVDLA's arbiter chunks (``nvdla_chunks``)
    continues the stride run of the chunk before it."""
    b, s, c = chunks
    return bool(np.any((s[1:] == s[:-1])
                       & (b[1:] == b[:-1] + c[:-1] * s[:-1])))


def _records_that_pay(recs: list, lanes: list) -> list | None:
    """A bucket's records where they pay, else None.  A record of P
    members costs up to P times a segment's step in the round scan, so
    compaction must shorten the padded scan by more than that."""
    if recs[0] is None:
        return None
    members = max(r.members for r in recs)
    longest = max(r.raw.shape[0] for r in recs)
    if longest * members >= max(c.shape[0] for _, _, c in lanes):
        return None
    return recs


def _segment_program(views, cfgs_b, sels_b, masked: bool):
    """The plan of one bucket of plain-segment lanes, ``(bases,
    strides, counts, nvdla_mask)`` each, as one collecting
    ``segment_lane_scan`` program; returns the function that runs it
    and reduces each lane to its ``LaneMetrics``."""
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(cfgs_b)
    s_pad = max(1, max(v[2].shape[0] for v in views))
    shape = (len(views), s_pad)
    bases = np.zeros(shape, np.int32)
    strides = np.ones(shape, np.int32)
    counts = np.zeros(shape, np.int32)
    r_needed = np.zeros(shape, np.int32)
    way_sels = np.zeros(shape, np.int32)
    suffix = "none"
    live_per_round = 0
    for row, ((b, s, c, _), cfg) in enumerate(zip(views, cfgs_b)):
        k = c.shape[0]
        bases[row, :k], strides[row, :k], counts[row, :k] = b, s, c
        bb = cfg.block_bytes
        last = b + np.maximum(c - 1, 0) * s
        nb = np.where(c > 0, last // bb - b // bb + 1, 0)
        # a round retires at most one block per set
        live_per_round = max(live_per_round,
                             int(np.minimum(nb, cfg.sets).max(initial=0)))
        sel = sels_b[row]
        if sel is not None:
            # way-partitioned lane: every segment retires entirely in
            # the round scan (no analytic suffix for restricted
            # allocation), so the plan is the full ceil(nb / sets)
            way_sels[row, :k] = sel
            r_needed[row, :k] = (-(-nb // cfg.sets)).astype(np.int32)
            continue
        # per-lane tight plan: enough rounds to retire the
        # min(nb, ways*sets)-block prefix; no cold short-circuit
        # (conservative cold=False is exact either way, and skipping the
        # host-side interval tracker keeps the plan O(numpy))
        r_needed[row, :k] = np.minimum(
            cfg.ways, -(-nb // cfg.sets)).astype(np.int32)
        overflow = nb - np.minimum(nb, cfg.ways * cfg.sets)
        if np.any(overflow > cfg.sets):
            suffix = "full"
        elif suffix == "none" and np.any(overflow > 0):
            suffix = "one"
    cold = np.zeros(shape, bool)
    # the static round-buffer depth only needs to cover this batch's
    # actual plan, not max_ways — chunked interference traces need 1
    r_pad = max(1, int(r_needed.max()))
    rounds = r_needed.max(axis=0).sum()
    width = _collect_width(live_per_round, max_sets)

    def run(drams_b, t_llc_hit, mesh) -> list[LaneMetrics]:
        with tracing.span(tracing.DISPATCH):
            arrays = [jnp.asarray(bases), jnp.asarray(strides),
                      jnp.asarray(counts), jnp.asarray(r_needed),
                      jnp.asarray(cold), sets, ways, blocks]
            if mesh is not None:
                arrays = _mesh_shard_lanes(arrays, mesh)
            if masked:
                # the zero-mask sentinel keeps unpartitioned rows on the
                # standard plan inside the same compiled program
                arrays = arrays + [jnp.asarray(way_sels)]
            engine = _lane_engine(max_sets, max_ways, r_pad, True,
                                  collect=True, suffix=suffix,
                                  masked=masked, collect_width=width)
            hits_dev, miss_dev = engine(*arrays)
        tracing.count(tracing.PROGRAMS, 1)
        tracing.count(tracing.MISS_WIDTH, width)
        tracing.count(tracing.SCAN_ROUNDS, rounds)
        tracing.count(tracing.FETCH_BYTES, hits_dev.nbytes + miss_dev.nbytes)
        with tracing.span(tracing.FETCH):
            hits = np.asarray(hits_dev, np.int64)
            miss_bits = np.asarray(miss_dev)
        out = []
        for row, ((b, s, c, nv), cfg) in enumerate(zip(views, cfgs_b)):
            n_seg = c.shape[0]
            with tracing.span(tracing.MISS_RUNS):
                runs = _lane_miss_runs(b, s, c, cfg, cold[row],
                                       miss_bits[row],
                                       full_prefix=sels_b[row] is not None)
            out.append(_lane_metrics_checked(
                runs, n_segments=n_seg, accesses=int(c.sum()),
                hits=int(hits[row, :n_seg].sum()), bb=cfg.block_bytes,
                nv=nv, dram=drams_b[row], t_llc_hit=t_llc_hit,
                nv_acc=int(c[nv].sum()),
                nv_hits=int(hits[row, :n_seg][nv].sum())))
        return out
    return run


def _lane_metrics_checked(runs, *, accesses, hits, **kw) -> LaneMetrics:
    """``_lane_metrics_from_runs`` after checking that the decoded miss
    runs hold exactly the lane's misses."""
    run_total = int(runs[1].sum())
    if run_total != accesses - hits:
        raise RuntimeError(
            "lane miss-run reconstruction disagrees with the kernel: "
            f"{run_total} missed blocks vs {accesses - hits} misses")
    with tracing.span(tracing.DRAM_ROWS):
        return _lane_metrics_from_runs(accesses=accesses, hits=hits,
                                       runs=runs, **kw)


def _record_program(recs, cfgs_b):
    """The plan of one bucket of compacted lanes as one
    ``record_lane_scan`` program; returns the function that runs it and
    takes each lane's miss runs, in the uncompacted trace's order,
    through the DRAM row model."""
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(cfgs_b)
    n_mem = max(r.members for r in recs)
    s_pad = max(r.raw.shape[0] for r in recs)
    shape = (len(recs), s_pad, n_mem)
    arrays = [np.zeros(shape, np.int32), np.ones(shape, np.int32),
              np.zeros(shape, np.int32), np.ones(shape, np.int32),
              np.zeros(shape, np.int32), np.ones(shape[:2], np.int32),
              np.zeros(shape[:2], np.int32)]
    for row, (r, cfg) in enumerate(zip(recs, cfgs_b)):
        k, p = r.counts.shape
        for a, v in zip(arrays, (r.bases, r.strides, r.counts, r.chunks,
                                 r.offsets)):
            a[row, :k, :p] = v
        arrays[5][row, :k] = r.periods
        arrays[6][row, :k] = _record_rounds(r, cfg)
    r_pad = max(1, int(arrays[6].max()))
    rounds = arrays[6].max(axis=0).sum()

    def run(drams_b, t_llc_hit, mesh) -> list[LaneMetrics]:
        with tracing.span(tracing.DISPATCH):
            dev = [jnp.asarray(a) for a in arrays] + [sets, ways, blocks]
            if mesh is not None:
                dev = _mesh_shard_lanes(dev, mesh, zero=(2, 6))
            engine = _record_engine(max_sets, max_ways, r_pad)
            hits_dev, codes_dev = engine(*dev)
        tracing.count(tracing.PROGRAMS, 1)
        tracing.count(tracing.SCAN_ROUNDS, rounds)
        tracing.count(tracing.FETCH_BYTES, hits_dev.nbytes + codes_dev.nbytes)
        with tracing.span(tracing.FETCH):
            hits = np.asarray(hits_dev, np.int64)
            codes = np.asarray(codes_dev)

        def lane(row) -> LaneMetrics:
            r, cfg = recs[row], cfgs_b[row]
            k, p = r.counts.shape
            h = hits[row, :k, :p]
            with tracing.span(tracing.MISS_RUNS):
                runs = _record_miss_runs(r, cfg, codes[row, :k], max_ways)
            return _lane_metrics_checked(
                runs, n_segments=int(r.raw.sum()),
                accesses=int(r.counts.sum()), hits=int(h.sum()),
                bb=cfg.block_bytes, nv=r.nv.reshape(-1), dram=drams_b[row],
                t_llc_hit=t_llc_hit, nv_acc=int(r.counts[r.nv].sum()),
                nv_hits=int(h[r.nv].sum()))

        # a lane's decode and DRAM rows are numpy passes over millions of
        # chunks, which run with the interpreter lock released: the lanes
        # go in threads
        with concurrent.futures.ThreadPoolExecutor() as pool:
            return list(pool.map(lane, range(len(recs))))
    return run


def _record_rounds(r: LaneRecords, llc: LLCConfig) -> np.ndarray:
    """Round-scan rounds per record: its first ``ways`` arrivals per
    set, bounded by the arrivals its members bring to any one set."""
    bb, sets = llc.block_bytes, llc.sets
    last = r.bases + np.maximum(r.counts - 1, 0) * r.strides
    nb = np.where(r.counts > 0, last // bb - r.bases // bb + 1, 0)
    return np.minimum(llc.ways, (-(-nb // sets)).sum(axis=1))


def _record_miss_runs(r: LaneRecords, llc: LLCConfig, codes: np.ndarray,
                      max_ways: int) -> tuple:
    """A compacted lane's missed-block runs in the order of its
    uncompacted trace: every chunk's newly touched blocks (a block its
    previous chunk already touched is a hit), less the round scan's
    hits (``cache.record_lane_scan``'s codes).  Returns
    ``(first_blocks, n_blocks, member)`` int64 arrays, ``member`` the
    flat (record, member) index."""
    bb, sets = llc.block_bytes, llc.sets
    n_rec, p = r.counts.shape
    base, stride, count, chunk = r.bases, r.strides, r.counts, r.chunks
    b_first = base // bb
    # chunks in trace order: per record, repeat-major over live members
    live = (count > 0).sum(axis=1)
    reps = np.where(live > 0, -(-count[:, 0] // np.maximum(chunk[:, 0], 1)),
                    0)
    per_rec = reps * live
    q_start = np.cumsum(per_rec) - per_rec
    n_q = int(per_rec.sum())
    rec = np.repeat(np.arange(n_rec), per_rec)
    q = np.arange(n_q) - q_start[rec]
    rep, mem = q // live[rec], q % live[rec]
    flat = rec * p + mem
    base_q, stride_q = base.reshape(-1)[flat], stride.reshape(-1)[flat]
    chunk_q, count_q = chunk.reshape(-1)[flat], count.reshape(-1)[flat]
    j0 = rep * chunk_q
    j1 = np.minimum(j0 + chunk_q, count_q) - 1
    first_q = b_first.reshape(-1)[flat]
    o0 = (base_q + j0 * stride_q) // bb - first_q
    shared = (j0 > 0) & ((base_q + (j0 - 1) * stride_q) // bb - first_q == o0)
    lo = o0 + shared
    hi = (base_q + j1 * stride_q) // bb - first_q + 1
    # the round scan's hits, each in the chunk of its block's first access
    hr, hk, hs = np.nonzero(codes)
    v = codes[hr, hk, hs].astype(np.int64) - 1
    hm, hq = v // max_ways, v % max_ways
    h_ord = (hs - b_first[hr, hm]) % sets + hq * sets
    hf = hr * p + hm
    h_base, h_stride = base.reshape(-1)[hf], stride.reshape(-1)[hf]
    lo_b = (b_first.reshape(-1)[hf] + h_ord) * bb - h_base
    j_first = np.where(lo_b <= 0, 0, -(-lo_b // h_stride))
    h_q = (q_start[hr] + (j_first // chunk.reshape(-1)[hf]) * live[hr]
           + hm)
    order = np.lexsort((h_ord, h_q))
    h_q, h_ord = h_q[order], h_ord[order]
    # split each chunk's range at its hits: chunk q's pieces are
    # [lo, h1), [h1+1, h2), ..., [hk+1, hi)
    n_hits = np.bincount(h_q, minlength=n_q)
    pieces = 1 + n_hits
    at = np.cumsum(pieces) - pieces
    rank = np.arange(h_q.shape[0]) - (np.cumsum(n_hits) - n_hits)[h_q]
    start = np.empty(int(pieces.sum()), np.int64)
    end = np.empty_like(start)
    start[at] = lo
    end[at + pieces - 1] = hi
    start[at[h_q] + rank + 1] = h_ord + 1
    end[at[h_q] + rank] = h_ord
    owner = np.repeat(flat, pieces)
    keep = end > start
    return (b_first.reshape(-1)[owner[keep]] + start[keep],
            end[keep] - start[keep], owner[keep])


@functools.lru_cache(maxsize=32)
def _record_engine(max_sets: int, max_ways: int, r_pad: int):
    from repro.core.cache import record_lane_scan

    return jax.jit(jax.vmap(functools.partial(
        record_lane_scan, max_sets=max_sets, max_ways=max_ways,
        r_pad=r_pad)))


def sweep_interference(*, soc=None, corunners=(0, 1, 2, 3, 4),
                       window_bursts: int | None = 4096,
                       chunk_bursts: int = 16) -> SweepGrid:
    """Fig. 6, batched: closed-form slowdown curves (``.slowdowns``)
    plus, per (wss, n), the *simulated* NVDLA LLC hit rate with
    co-runner write streams physically interleaved into the trace
    (``.sim_hit_rates``) — every lane a compressed segment stream,
    returned as a typed ``SweepGrid``.  All interference lanes share
    one LLC geometry and run as one ``interference_lane_metrics_batch``
    call, which yields per-segment hit attribution *and* the exact
    LLC-miss runs together.  DRAM row-hit rates come from the
    closed-form row model over each lane's miss runs (misses of *all*
    masters mix in the banks, so co-runner misses break the NVDLA
    stream's row locality — the FR-FCFS disruption Fig. 6 attributes
    the "dram" slowdown to).

    ``window_bursts=None`` simulates the *entire* YOLOv3 frame: its
    lanes compact into records (``compact_lane``), so the lane
    program's scan length follows the records, not the chunks."""
    from repro.core.dram import DRAMConfig
    from repro.core.soc import SoCConfig, interference_sweep as _closed_form

    soc = soc or SoCConfig()
    cf = _closed_form(soc=soc, corunners=corunners)
    llc = soc.mem.llc or LLCConfig()
    dram = soc.mem.dram or DRAMConfig()
    if window_bursts is None:
        nvdla_segs = traces.network_trace()
    else:
        nvdla_segs = traces.default_dbb_window(max_bursts=window_bursts)
    # l1-fitting co-runners never reach the shared fabric, so every
    # ('l1', n) lane is the solo-NVDLA trace — simulate it once and fan
    # the result out to all n below
    keys = [("l1", 0)] + [(wss, n) for wss in ("llc", "dram")
                          for n in corunners]
    lanes = interference_lane_metrics_batch(
        nvdla_segs, llcs=[llc] * len(keys), drams=[dram] * len(keys),
        mixes=[MixConfig(corunners=n, wss=wss) for wss, n in keys],
        chunk_bursts=chunk_bursts)
    sim_hit_rates: dict = {}
    sim_row_hit_rates: dict = {}
    for (wss, n), m in zip(keys, lanes):
        for key in ([(wss, n)] if wss != "l1"
                    else [("l1", k) for k in corunners]):
            sim_hit_rates[key] = m.nvdla_hit_rate
            sim_row_hit_rates[key] = m.nvdla_miss_row_hit_rate
    return SweepGrid(
        kind="interference",
        slowdowns={wss: cf[wss] for wss in ("l1", "llc", "dram")},
        sim_hit_rates=sim_hit_rates,
        sim_row_hit_rates=sim_row_hit_rates,
        window_bursts=traces.total_bursts(nvdla_segs))

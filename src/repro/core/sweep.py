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

Every interference lane runs through one entry point,
``interference_lane_metrics_batch`` (whole-frame lanes compacted into
records for ``cache.record_lane_scan``); a single lane is a batch of
one.

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
* ``interference_lane_metrics_batch`` — the one interference entry
                            point: many lanes as vmapped lane
                            programs, optionally sharded over a
                            ``jax.sharding`` mesh (the campaign
                            executor's data-parallel path) and
                            optionally per-lane way-partitioned
                            (``way_masks=``), compacted either way;
* ``interference_lane_metrics``       — one lane -> ``LaneMetrics``,
                            the batch of one, optionally LLC
                            way-partitioned (``way_mask=``);
* ``step_lane_metrics``   — a serving step's marginal lane record;
* ``compact_lane``        — one interleaved lane as ``LaneRecords``:
                            repeats of the arbiter's pattern, each run
                            in one step of ``cache.record_lane_scan``;
* ``lane_records``        — the same records built from the NVDLA
                            chunks, the lane never expanded;
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

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import traces
from repro.core.cache import LLCConfig
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
    miss in every lane, so the closed form needs no rounds at all).
    The test is conservative: a revisit of a long-evicted range still
    takes rounds, exact either way."""
    metas = [_segment_tuple(s) for s in trace]
    base = np.asarray([m[0] for m in metas], np.int64)
    stride = np.asarray([m[1] for m in metas], np.int64)
    count = np.asarray([m[2] for m in metas], np.int64)
    live = count > 0
    last = base + np.maximum(count - 1, 0) * stride
    slack = max(c.block_bytes for c in configs) - 1
    touched: list[tuple[int, int]] = []   # merged, sorted byte intervals
    cold = np.zeros(len(metas), bool)
    for j in np.flatnonzero(live):
        lo, hi = int(base[j] - slack), int(last[j] + slack)
        cold[j] = not any(a <= hi and lo <= b for a, b in touched)
        kept = []
        for a, b in touched:
            if a <= hi + 1 and lo <= b + 1:
                lo, hi = min(a, lo), max(b, hi)
            else:
                kept.append((a, b))
        touched = sorted(kept + [(lo, hi)])
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
    """A lane trace the lane engines cannot replay: a segment stride
    outside ``(0, block_bytes]``.  Only the per-access paths
    (``socsim.simulate_dbb_stream``, ``cache.simulate_trace``) replay
    such a trace."""


def _check_lane_support(lanes, configs) -> None:
    """`_check_lane_support_meta` over lanes of ``Segment``/tuples."""
    _check_lane_support_meta(
        [np.asarray([_segment_tuple(s) for s in trace],
                    np.int64).reshape(-1, 3).T for trace in lanes], configs)


def _check_lane_support_meta(lanes_meta, configs) -> None:
    """Raise unless every lane of (bases, strides, counts) arrays is in
    the lane engines' support: strides in ``(0, block_bytes]`` of the
    smallest block (``UnsupportedTraceError``), addresses and the
    lane's access total in int32 (``OverflowError``)."""
    min_block = min(c.block_bytes for c in configs)
    for base, stride, count in lanes_meta:
        live = count > 0
        bad = live & ((stride <= 0) | (stride > min_block))
        if np.any(bad):
            raise UnsupportedTraceError(
                f"segment stride {int(stride[bad][0])} outside "
                f"(0, {min_block}] — the lane engines need stride <= "
                "block_bytes in every lane; replay sparse-stride traces "
                "access by access (socsim.simulate_dbb_stream, "
                "cache.simulate_trace)")
        _check_int32(int((base + count * stride)[live].max(initial=0)),
                     int(count[live].sum()))


def _check_int32(end: int, accesses: int) -> None:
    """Raise ``OverflowError`` unless a lane's highest segment end
    address and its access total fit int32."""
    int32_max = np.iinfo(np.int32).max
    if end > int32_max:
        raise OverflowError(
            "segment addresses exceed int32 — the lane engine "
            "keeps metadata in 32-bit; rebase the trace")
    if accesses > int32_max:
        raise OverflowError(
            f"lane trace has {accesses} accesses — "
            "the lane engine's global LRU timestamp is int32; split "
            "multi-frame sweeps into per-frame lane calls")


def _check_records_support(chunks, llc: LLCConfig, mix: MixConfig,
                           checked: set) -> None:
    """``_check_lane_support_meta`` of the lane of ``mix`` at ``llc``
    over these NVDLA chunks, without expanding it: the chunks once per
    block size (``checked`` holds those done), then each co-runner's
    highest line end (its span's end once its cursor reaches it) and
    the lane's access total."""
    if llc.block_bytes not in checked:
        _check_lane_support_meta([chunks], [llc])
        checked.add(llc.block_bytes)
    total = int(chunks[2].sum())
    spans = _corunner_spans(llc, mix)
    _check_int32(max((region + min(span, total) * 64
                      for span, region in spans), default=0),
                 total * (1 + len(spans)))


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
        _check_lane_support(lanes if per_lane else lanes[:1], configs)
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


def _split_sparse_corunners(b, s, c, nv, llc: LLCConfig) -> tuple:
    """A lane's arrays as the lane engines take them.  Co-runners write
    64 B lines, a stride past an LLC block narrower than a line, which
    the engines do not replay as a run: each such line becomes a
    one-access segment of stride ``block_bytes`` (a stride no record
    continues).  The accesses and their order stay; only the segment
    count grows, so callers report the unsplit lane's segments."""
    split = ~nv & (s > llc.block_bytes)
    if not split.any():
        return b, s, c, nv
    reps = np.where(split, np.maximum(c, 1), 1)
    idx = np.repeat(np.arange(c.shape[0]), reps)
    k = np.arange(idx.shape[0]) - np.repeat(np.cumsum(reps) - reps, reps)
    one = split[idx]
    return (b[idx] + k * s[idx], np.where(one, llc.block_bytes, s[idx]),
            np.where(one, np.minimum(c[idx], 1), c[idx]), nv[idx])


@dataclasses.dataclass(frozen=True)
class LaneRecords:
    """One lane compacted into records (``cache.record_lane_scan``):
    per record and member (S, P) int64 ``bases``, ``strides``,
    ``counts`` (the member's accesses in the record), ``chunks`` (its
    accesses per repeat) and ``offsets`` (the chunks of the members
    before it); per record (S,) ``periods`` (accesses per repeat) and
    ``raw`` (the uncompacted segments it stands for); ``nv`` (S, P)
    marks NVDLA members, and ``masks`` (S, P) holds each member's LLC
    allocation mask (``partition_way_sels``; 0, unpartitioned, in a lane
    without a partition).  Live members come first in every record."""
    bases: np.ndarray
    strides: np.ndarray
    counts: np.ndarray
    chunks: np.ndarray
    offsets: np.ndarray
    periods: np.ndarray
    raw: np.ndarray
    nv: np.ndarray
    masks: np.ndarray

    @property
    def members(self) -> int:
        """Members of the widest record: 1 when every record is one
        plain segment."""
        live = (self.counts > 0).sum(axis=1)
        return max(1, int(live.max(initial=1)))


def compact_lane(b, s, c, nv, llc: LLCConfig, members: int,
                 sels=None) -> LaneRecords:
    """Compact one interleaved lane (``corunner_meta``'s arrays) into
    records: each maximal run of two or more arbiter rounds in which
    every member — the NVDLA chunk, then each co-runner's — continues
    its own stride run with the same chunk becomes one record (the last
    round may be shorter).  A run breaks where an NVDLA segment ends, a
    co-runner wraps in its working set (its round has more pieces) or
    chunk sizes change.  Every other segment stays a record of its own.

    A run of several members is kept only where the record engine's
    merge is exact at ``llc``: the members' block ranges are disjoint,
    each chunk spans a whole number of blocks, and a block a member's
    chunks share sees fewer than ``ways`` arrivals of the other members
    in its set in between (at most one chunk of each).  The records replay
    exactly the lane's access order.  ``members`` is the lane's segments
    per round without a wrap: 1 + its co-runners.  ``sels`` gives a
    way-partitioned lane's allocation mask per segment
    (``partition_way_sels``); the records carry them, and the arrivals a
    shared block waits out count per class, against the class's ways.
    ``lane_records`` builds the same records without the expanded lane
    where it can."""
    b, s, c = (np.asarray(a, np.int64) for a in (b, s, c))
    nv = np.asarray(nv, bool)
    sels = (np.zeros(c.shape[0], np.int64) if sels is None
            else np.asarray(sels, np.int64))
    n_seg = c.shape[0]
    starts = np.flatnonzero(nv)          # every round opens with the NVDLA
    if n_seg == 0 or starts.size == 0 or starts[0] != 0:
        raise ValueError("a lane is a sequence of arbiter rounds, each "
                         "opened by one NVDLA chunk")
    size = np.diff(np.append(starts, n_seg))
    p = members
    normal = size == p
    at = np.minimum(starts[:, None] + np.arange(p)[None, :], n_seg - 1)
    B, St, Cn, M = b[at], s[at], c[at], sels[at]
    cont = (normal[:-1] & normal[1:] & (St[1:] == St[:-1]).all(1)
            & (B[1:] == B[:-1] + Cn[:-1] * St[:-1]).all(1))
    eq = cont & (Cn[1:] == Cn[:-1]).all(1)
    tail = cont & (Cn[1:] < Cn[:-1]).all(1) & ~np.append(eq[1:], False)
    link = eq | tail
    link[1:] &= ~tail[:-1]
    edge = np.diff(np.concatenate([[0], link.astype(np.int8), [0]]))
    first, last = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)
    if p > 1 and first.size:
        first, last = _exact_runs(first, last, B, St, Cn, M, llc)
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
                 (one > 0) & nv[plain, None])[order],
        masks=stack(M[first], one * sels[plain, None])[order])


def _direct_records(chunks: tuple, llc: LLCConfig, mix: MixConfig) -> bool:
    """Whether ``lane_records`` builds the lane of ``mix`` at ``llc``
    over these NVDLA chunks: the trace has a chunk, the co-runners' 64 B
    lines are no wider than the LLC block (``_split_sparse_corunners``
    leaves them whole), and no co-runner's span is shorter than a chunk
    (a chunk wraps it at most once)."""
    spans = _corunner_spans(llc, mix)
    cc = chunks[2]
    return cc.shape[0] > 0 and (not spans or (
        llc.block_bytes >= 64 and min(s for s, _ in spans) >= cc.max()))


def lane_records(chunks: tuple, llc: LLCConfig, mix: MixConfig,
                 way_mask: int | None = None) -> LaneRecords:
    """``compact_lane`` of the lane ``corunner_meta`` interleaves, record
    for record, built from the NVDLA chunks (``nvdla_chunks``) without
    expanding the lane.  Round k is chunk k and a piece of each
    co-runner's as many lines; every co-runner's cursor before it is the
    chunks' prefix sum, so arithmetic on it finds the rounds where a
    co-runner's chunk wraps its span (two pieces) or ends on its end
    (the next round restarts the span).  The runs ``compact_lane`` folds
    follow from the chunks and those rounds, ``_exact_runs`` filters
    them on their first and last rounds, and only the rounds outside
    every kept run are expanded, into plain segments.  ``way_mask``
    partitions the lane's LLC as ``interference_lane_metrics`` does.

    Takes the lanes ``_direct_records`` admits; ``compact_lane`` of the
    expanded lane is the general fold, and this one's oracle
    (tests/test_compaction.py)."""
    if not _direct_records(chunks, llc, mix):
        raise ValueError("lane_records needs a chunk, co-runner lines no "
                         "wider than the LLC block and spans no shorter "
                         "than a chunk; fold the expanded lane instead")
    cb, cs, cc = chunks
    spans = _corunner_spans(llc, mix)
    p = 1 + len(spans)
    # each member's allocation mask: the NVDLA's, then the co-runners'
    sel = (np.zeros(p, np.int64) if way_mask is None else partition_way_sels(
        np.arange(p) == 0, llc, way_mask).astype(np.int64))
    n_ch = cc.shape[0]
    pre = np.cumsum(cc) - cc           # each co-runner's lines before round k
    wrap = np.zeros(n_ch, bool)        # a co-runner's chunk wraps its span
    ends = np.zeros(n_ch, bool)        # a co-runner's chunk ends on its end
    for span in {s for s, _ in spans}:
        e = pre % span + cc
        wrap |= e > span
        ends |= e == span
    # ``compact_lane``'s links between round k and k + 1
    cont = ((cs[1:] == cs[:-1]) & (cb[1:] == cb[:-1] + cc[:-1] * cs[:-1])
            & ~(wrap[:-1] | wrap[1:] | ends[:-1]))
    eq = cont & (cc[1:] == cc[:-1])
    tail = cont & (cc[1:] < cc[:-1]) & ~np.append(eq[1:], False)
    link = eq | tail
    link[1:] &= ~tail[:-1]
    edge = np.diff(np.concatenate([[0], link.astype(np.int8), [0]]))
    first, last = np.flatnonzero(edge == 1), np.flatnonzero(edge == -1)

    def members(k):
        """Rounds ``k`` (none of them a wrap) as (bases, strides,
        counts, masks), a column per member."""
        B = np.empty((k.shape[0], p), np.int64)
        B[:, 0] = cb[k]
        for w, (span, region) in enumerate(spans):
            B[:, 1 + w] = region + pre[k] % span * 64
        St = np.full((k.shape[0], p), 64, np.int64)
        St[:, 0] = cs[k]
        return (B, St, np.repeat(cc[k][:, None], p, axis=1),
                np.repeat(sel[None, :], k.shape[0], axis=0))

    if p > 1 and first.size:
        k = np.arange(first.size)
        keep, _ = _exact_runs(k, k + first.size,
                              *members(np.concatenate([first, last])), llc)
        first, last = first[keep], last[keep]
    B, St, Cn, M = members(first)
    r_count = np.repeat((pre[last] + cc[last] - pre[first])[:, None], p,
                        axis=1)
    # the rounds outside every run, expanded as ``corunner_meta`` orders
    # their segments: the NVDLA chunk, then each co-runner's pieces
    gap = np.append(0, last + 1)
    n_gap = np.append(first, n_ch) - gap
    k = (np.repeat(gap - (np.cumsum(n_gap) - n_gap), n_gap)
         + np.arange(int(n_gap.sum())))
    # per segment: base, stride, count, round, slot in the round
    cols = [[cb[k]], [cs[k]], [cc[k]], [k], [np.zeros_like(k)]]
    for w, (span, region) in enumerate(spans):
        start = pre[k] % span
        take = np.minimum(cc[k], span - start)
        j = take < cc[k]                          # the wrap's second piece
        for col, v in zip(cols, (
                region + start * 64, np.full_like(k, 64), take, k,
                np.full_like(k, 1 + 2 * w))):
            col.append(v)
        for col, v in zip(cols, (
                np.full_like(k[j], region), np.full_like(k[j], 64),
                (cc[k] - take)[j], k[j], np.full_like(k[j], 2 + 2 * w))):
            col.append(v)
    pb, ps, pc, pr, pslot = (np.concatenate(c) for c in cols)

    def plain(v, fill):
        """Plain segments as one-member records."""
        out = np.full((v.shape[0], p), fill, v.dtype)
        out[:, 0] = v
        return out

    order = np.lexsort((np.append(np.zeros_like(first), pslot),
                        np.append(first, pr)))
    chunk = np.concatenate([Cn if p > 1 else r_count, plain(pc, 0)])[order]
    return LaneRecords(
        bases=np.concatenate([B, plain(pb, 0)])[order],
        strides=np.concatenate([St, plain(ps, 1)])[order],
        counts=np.concatenate([r_count, plain(pc, 0)])[order],
        chunks=chunk,
        offsets=np.cumsum(chunk, axis=1) - chunk,
        periods=chunk.sum(axis=1),
        raw=np.concatenate([(last - first + 1) * p,
                            np.ones(pc.shape[0], np.int64)])[order],
        nv=np.concatenate([np.arange(p)[None, :].repeat(first.size, 0) == 0,
                           plain(pslot == 0, False)])[order],
        masks=np.concatenate([M, plain(np.where(pslot == 0, sel[0],
                                                sel[-1]), 0)])[order])


def _exact_runs(first, last, B, St, Cn, M, llc: LLCConfig):
    """The runs of several members the record engine replays exactly at
    ``llc`` (``compact_lane``); ``M`` holds the members' allocation
    masks."""
    bb, sets, ways = llc.block_bytes, llc.sets, llc.ways
    width = Cn[first] * St[first]                     # bytes per chunk
    lo = B[first] // bb
    hi = (B[last] + (Cn[last] - 1) * St[last]) // bb
    # whole blocks: the device's row reduction (``_record_rows``) lays a
    # member's hits out a chunk at a time
    ok = (width % bb == 0).all(1)
    p = B.shape[1]
    for m in range(p):
        for m2 in range(m + 1, p):
            ok &= (hi[:, m] < lo[:, m2]) | (hi[:, m2] < lo[:, m])
    # a shared boundary block waits out one chunk of every other member
    # of its class, which only those can evict from the class's ways
    arrivals = -(-(-(-width // bb) + 1) // sets)
    shares = (B[first] % bb) != 0
    same, k = _classes(M[first], ways)
    others = (same * arrivals[:, None, :]).sum(2) - arrivals
    ok &= ~(shares & (others >= k)).any(1)
    return first[ok], last[ok]


def _classes(masks, ways: int) -> tuple[np.ndarray, np.ndarray]:
    """Members' allocation masks (..., P) at ``ways`` ways as classes:
    whether two members allocate into the same ways (..., P, P), and
    each member's way count (..., P).  Zero, the unpartitioned
    sentinel, is every way, as the full mask is."""
    full = (1 << min(ways, 62)) - 1
    m = np.asarray(masks, np.int64) & full
    m = np.where(m == full, 0, m)
    return (m[..., :, None] == m[..., None, :],
            np.where(m == 0, ways, np.bitwise_count(m)))


def _classes_apart(chunks: tuple, llc: LLCConfig, mix: MixConfig) -> bool:
    """Whether no LLC block of the NVDLA's chunks lies in a co-runner's
    span, so that the classes of a way partition never touch one
    another's blocks: then each class's lines stay in its own ways, the
    condition under which a partitioned lane runs as records."""
    cb, cs, cc = chunks
    if not cc.shape[0]:
        return True
    bb = llc.block_bytes
    lo = int(cb.min()) // bb
    hi = int((cb + (cc - 1) * cs).max()) // bb
    return all(hi < region // bb or (region + span * 64 - 1) // bb < lo
               for span, region in _corunner_spans(llc, mix))


def _lane_metrics_from_runs(*, n_segments, accesses, hits, runs, bb, nv,
                            dram, t_llc_hit, nv_acc, nv_hits) -> LaneMetrics:
    """The shared lane reduction: exact LLC counts + miss runs
    (``(first_blocks, n_blocks, seg_idx)``, three aligned int64 arrays
    in access order) -> closed-form DRAM row hits -> ``_lane_metrics``.
    The segment path and the farm's lanes end here; the record path
    reduces its lanes to the same counts on the device
    (``_record_rows``) and ends in ``_lane_metrics`` too."""
    from repro.core.dram import segment_row_hits

    fb, nbk, sidx = (np.asarray(a, np.int64) for a in runs)
    row = segment_row_hits((fb * bb, np.full(fb.shape[0], bb, np.int64),
                            nbk), dram)
    run_is_nv = np.asarray(nv, bool)[sidx]
    return _lane_metrics(
        n_segments=n_segments, accesses=accesses, hits=hits,
        row_hits=row.row_hits, dram=dram, t_llc_hit=t_llc_hit,
        nv_acc=nv_acc, nv_hits=nv_hits, nv_miss=int(nbk[run_is_nv].sum()),
        nv_row_hits=int(row.per_segment[run_is_nv].sum()))


def _lane_metrics(*, n_segments, accesses, hits, row_hits, dram, t_llc_hit,
                  nv_acc, nv_hits, nv_miss, nv_row_hits) -> LaneMetrics:
    """A lane's counts -> closed-form latency total -> the typed record,
    in Python ints."""
    misses = accesses - hits
    row_misses = misses - row_hits
    total = (accesses * t_llc_hit + misses * dram.t_cas_cycles
             + row_misses * (dram.t_rp_cycles + dram.t_rcd_cycles))
    return LaneMetrics(
        segments=n_segments,
        accesses=int(accesses),
        llc_hits=int(hits),
        dram_row_hits=int(row_hits),
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
    """One interference lane reduced to the typed ``LaneMetrics`` record
    a campaign point journals (``repro.campaign``):
    ``interference_lane_metrics_batch`` as a batch of one.

    ``mix.corunners=0`` (or ``mix.wss="l1"``) is the solo-NVDLA lane.

    ``way_mask`` turns on LLC way partitioning (``partition_way_sels``):
    victim segments allocate only into ``way_mask``'s ways, co-runners
    into the complement.  The full mask is bit-exactly the
    unpartitioned lane."""
    return interference_lane_metrics_batch(
        nvdla_segs, llcs=[llc], drams=[dram], mixes=[mix],
        chunk_bursts=chunk_bursts, t_llc_hit=t_llc_hit,
        way_masks=None if way_mask is None else [way_mask])[0]


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
    (attributed from the lane's miss runs).  ``corunner_meta`` emits
    exactly one victim segment per ``chunk_bursts``-burst chunk, so the
    victim rows *are* the per-chunk service latencies — returned in
    stream order alongside the lane's ``LaneMetrics``.  The per-chunk
    latencies provably sum to ``metrics.total_cycles`` (the identity's
    linearity; asserted here).

    The lane runs through the way-masked kernel (``_masked_lane_run``),
    which hands back per-segment hits and miss runs; ``way_mask``
    partitions the LLC as in ``interference_lane_metrics``, and None is
    the full mask, bit-exactly the unpartitioned lane."""
    from repro.core.dram import segment_row_hits

    bb = llc.block_bytes
    _check_row_block(llc, dram)
    b, s, c, nv = corunner_meta(nvdla_segs, llc=llc, mix=mix,
                                chunk_bursts=chunk_bursts)
    n_segments = c.shape[0]
    b, s, c, nv = _split_sparse_corunners(b, s, c, nv, llc)
    _check_lane_support_meta([(b, s, c)], [llc])
    way_sels = partition_way_sels(
        nv, llc, (1 << llc.ways) - 1 if way_mask is None else way_mask)
    hits, (fb, nbk, sidx) = _masked_lane_run(b, s, c, llc, way_sels)
    counts = np.asarray(c, np.int64)
    hits = np.asarray(hits[:counts.shape[0]], np.int64)
    _check_missed(int(nbk.sum()), int(counts.sum()), int(hits.sum()))
    row = segment_row_hits((fb * bb, np.full(fb.shape[0], bb, np.int64),
                            nbk), dram)
    seg_row = np.zeros(counts.shape[0], np.int64)
    np.add.at(seg_row, sidx, np.asarray(row.per_segment, np.int64))
    misses = counts - hits
    per_seg = (counts * t_llc_hit + misses * dram.t_cas_cycles
               + (misses - seg_row) * (dram.t_rp_cycles
                                       + dram.t_rcd_cycles))
    metrics = _lane_metrics_from_runs(
        n_segments=n_segments, accesses=int(counts.sum()),
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
    ascending within a segment — the order of first access, up to how a
    segment's missed blocks split into runs, which the closed-form row
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
    (``_lane_miss_runs``) and finishes with the closed-form DRAM/latency
    reduction (``_lane_metrics_from_runs``), so every ``LaneMetrics``
    is bit-identical to the plain per-access reference of the same lane
    (``bench/reference/lane.py``), whatever lanes share its batch.  A
    single lane is a batch of one (``interference_lane_metrics``).

    Each lane is compacted first: ``lane_records`` builds its
    records from the NVDLA chunks without expanding it, where its
    co-runner lines fit the LLC block and its spans hold a chunk
    (``_direct_records``), and ``compact_lane`` folds the expanded lane
    otherwise.  Where the records shorten a bucket's padded scan by
    more than their members cost (``_records_that_pay``), the bucket
    runs as one ``record_lane_scan`` program instead (one per set
    count, where the bucket mixes them), and a second device program
    (``_record_rows``) reduces each lane's hit codes to its missed
    blocks and DRAM row hits, so the host fetches counts, not codes —
    the same metrics, bit for bit.  A lane is expanded only where it
    runs as segments or has no direct records.  The whole frame's lanes
    compact; the Fig. 6 windows, whose NVDLA chunks never continue one
    another, do not.

    ``mesh`` (a 1-D ``jax.sharding.Mesh``, see
    ``repro.launch.mesh.make_sweep_mesh``) shards the lane axis across
    devices; ``mesh=None`` runs the same program on one device.

    Raises ``UnsupportedTraceError`` (a ``ValueError``) if the NVDLA
    trace falls outside a lane's support (a stride outside ``(0,
    block_bytes]`` of that lane's LLC); only the per-access paths
    replay such a trace (``socsim.simulate_dbb_stream``).  Co-runner
    lines past a narrower block run as one-access segments
    (``_split_sparse_corunners``).

    ``way_masks`` is an equal-length sequence of per-lane LLC way
    partitions (``int`` victim masks, or ``None`` for unpartitioned
    lanes) — masked and unmasked lanes mix freely in one compiled
    batch via the kernels' zero-mask sentinel.  A partitioned lane
    compacts like any other where its classes never touch one
    another's blocks (``_classes_apart``): its records carry the
    members' masks into ``record_lane_scan``.  Otherwise, and where its
    bucket's records do not pay, it runs through the masked segment
    program, every segment in the round scan."""
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
    masked = way_masks is not None
    if masked and mesh is not None:
        raise ValueError("way-masked batches do not support mesh "
                         "sharding yet — pass mesh=None")
    fenced = [masked and way_masks[i] is not None for i in range(lanes_n)]
    expanded: list = [None] * lanes_n

    def lane(i):
        """Lane ``i`` expanded, as the lane engines take it, and its
        segment count before ``_split_sparse_corunners``."""
        if expanded[i] is None:
            b, s, c, nv = corunner_meta(nvdla_segs, llc=llcs[i],
                                        mix=mixes[i],
                                        chunk_bursts=chunk_bursts,
                                        _chunks=chunks)
            n_seg = c.shape[0]
            b, s, c, nv = _split_sparse_corunners(b, s, c, nv, llcs[i])
            _check_lane_support_meta([(b, s, c)], [llcs[i]])
            expanded[i] = (b, s, c, nv), n_seg
        return expanded[i]

    def sels(i):
        """Lane ``i``'s allocation mask per expanded segment, or None."""
        return (partition_way_sels(lane(i)[0][3], llcs[i], way_masks[i])
                if fenced[i] else None)

    with tracing.span(tracing.LANE_PLAN):
        chunks = nvdla_chunks(nvdla_segs, chunk_bursts)
        # a record folds repeats of an NVDLA chunk that continues the
        # one before, so a trace with none (the Fig. 6 windows) has
        # nothing to fold; a partitioned lane folds where its classes
        # never share a block
        compact = _chunks_continue(chunks)
        folds = [compact and (not fenced[i] or _classes_apart(
            chunks, llcs[i], mixes[i])) for i in range(lanes_n)]
        direct = [folds[i] and _direct_records(chunks, llcs[i], mixes[i])
                  for i in range(lanes_n)]
        checked: set = set()
        for i, (llc, dram) in enumerate(zip(llcs, drams)):
            _check_row_block(llc, dram)
            if direct[i]:
                _check_records_support(chunks, llc, mixes[i], checked)
            else:
                lane(i)
    records = [None] * lanes_n
    with tracing.span(tracing.COMPACT):
        if compact:
            records = [
                lane_records(chunks, llc, mix,
                             way_masks[i] if fenced[i] else None)
                if direct[i] else compact_lane(*lane(i)[0], llc, 1 + (
                    0 if mix.wss == "l1" else mix.corunners), sels(i))
                if folds[i] else None
                for i, (llc, mix) in enumerate(zip(llcs, mixes))]
            tracing.count(tracing.DIRECT_RECORD_LANES, sum(direct))
    out: list[LaneMetrics | None] = [None] * lanes_n
    for bucket in lane_buckets(llcs):
        with tracing.span(tracing.LANE_BATCH):
            with tracing.span(tracing.LANE_PLAN):
                cfgs_b = [llcs[i] for i in bucket]
                recs = _records_that_pay([records[i] for i in bucket])
                raw = [lane(i)[0][2].shape[0] if records[i] is None
                       else int(records[i].raw.sum()) for i in bucket]
                tracing.count(tracing.LANE_SEGMENTS_RAW, sum(raw))
                tracing.count(tracing.LANE_SEGMENTS, sum(
                    raw if recs is None else [r.raw.shape[0] for r in recs]))
                fenced_b = [i for i in bucket if fenced[i]]
                if recs is not None and (
                        fenced_b or max(r.members for r in recs) > 1):
                    # the row reduction lays out one set count's hit codes
                    parts = [[j for j, c in enumerate(cfgs_b) if c.sets == n]
                             for n in sorted({c.sets for c in cfgs_b},
                                             reverse=True)]
                    runs = [([bucket[j] for j in js], _record_program(
                        [recs[j] for j in js], [cfgs_b[j] for j in js]))
                        for js in parts]
                    tracing.count(tracing.PARTITIONED_RECORD_LANES,
                                  len(fenced_b))
                    tracing.count(tracing.PARTITIONED_LANE_SEGMENTS, sum(
                        records[i].raw.shape[0] for i in fenced_b))
                else:
                    # plain records come from lanes with no partition
                    views = ([lane(i)[0] for i in bucket]
                             if recs is None else
                             # one member each: plain segments
                             [(r.bases[:, 0], r.strides[:, 0],
                               r.counts[:, 0], r.nv[:, 0]) for r in recs])
                    runs = [(bucket, _segment_program(
                        views, cfgs_b, [sels(i) for i in bucket], masked))]
            for part, run in runs:
                got = run([drams[i] for i in part], t_llc_hit, mesh)
                for i, m in zip(part, got):
                    out[i] = dataclasses.replace(m, segments=(
                        int(records[i].raw.sum()) if direct[i]
                        else lane(i)[1]))
    return out


def _chunks_continue(chunks) -> bool:
    """Whether any of the NVDLA's arbiter chunks (``nvdla_chunks``)
    continues the stride run of the chunk before it."""
    b, s, c = chunks
    return bool(np.any((s[1:] == s[:-1])
                       & (b[1:] == b[:-1] + c[:-1] * s[:-1])))


def _records_that_pay(recs: list) -> list | None:
    """A bucket's records where every lane has them and they pay, else
    None.  A record of P members costs up to P times a segment's step in
    the round scan, so compaction must shorten the padded scan, the
    most segments a lane of the bucket has uncompacted (``raw.sum()``),
    by more than that."""
    if any(r is None for r in recs):
        return None
    members = max(r.members for r in recs)
    longest = max(r.raw.shape[0] for r in recs)
    if longest * members >= max(int(r.raw.sum()) for r in recs):
        return None
    return recs


def _segment_program(views, cfgs_b, sels_b, masked: bool):
    """The plan of one bucket of plain-segment lanes, ``(bases,
    strides, counts, nvdla_mask)`` each, as one collecting
    ``segment_lane_scan`` program; returns the function that runs it
    and reduces each lane to its ``LaneMetrics``."""
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(cfgs_b)
    # at least 64 segments: the serving oracle's lanes, a few dozen
    # segments each, then share one compiled program
    s_pad = max(64, max(v[2].shape[0] for v in views))
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
    _check_missed(int(runs[1].sum()), accesses, hits)
    with tracing.span(tracing.DRAM_ROWS):
        return _lane_metrics_from_runs(accesses=accesses, hits=hits,
                                       runs=runs, **kw)


def _check_missed(missed: int, accesses: int, hits: int) -> None:
    """The missed blocks a lane's reduction found must be exactly its
    misses."""
    if missed != accesses - hits:
        raise RuntimeError(
            "lane miss-run reconstruction disagrees with the kernel: "
            f"{missed} missed blocks vs {accesses - hits} misses")


def _record_program(recs, cfgs_b):
    """The plan of one bucket of compacted lanes as one
    ``record_lane_scan`` program; returns the function that runs it and
    reduces each lane to its ``LaneMetrics``.  The lanes share one set
    count.  The hit codes stay on the device: a second program
    (``_record_rows``) reduces them, with the records, to each lane's
    missed blocks and DRAM row hits, and the host fetches those counts
    and the per-record hits alone."""
    sets, ways, blocks, max_sets, max_ways = _geometry_arrays(cfgs_b)
    n_mem = max(r.members for r in recs)
    # members past the bucket's widest record are dead in every record
    recs = [dataclasses.replace(r, **{f: getattr(r, f)[:, :n_mem] for f in (
        "bases", "strides", "counts", "chunks", "offsets", "nv", "masks")})
        for r in recs]
    # a partitioned lane's program takes the members' masks; the others
    # run the unpartitioned program
    masked = any(r.masks.any() for r in recs)
    s_pad = max(r.raw.shape[0] for r in recs)
    shape = (len(recs), s_pad, n_mem)
    arrays = [np.zeros(shape, np.int32), np.ones(shape, np.int32),
              np.zeros(shape, np.int32), np.ones(shape, np.int32),
              np.zeros(shape, np.int32), np.ones(shape[:2], np.int32),
              np.zeros(shape[:2], np.int32)]
    masks = np.zeros(shape, np.int32)
    for row, (r, cfg) in enumerate(zip(recs, cfgs_b)):
        k, p = r.counts.shape
        for a, v in zip(arrays[:5] + [masks], (
                r.bases, r.strides, r.counts, r.chunks, r.offsets, r.masks)):
            a[row, :k, :p] = v
        arrays[5][row, :k] = r.periods
        arrays[6][row, :k] = _record_rounds(r, cfg)
    r_pad = max(1, int(arrays[6].max()))
    rounds = arrays[6].max(axis=0).sum()

    def run(drams_b, t_llc_hit, mesh) -> list[LaneMetrics]:
        with tracing.span(tracing.DISPATCH):
            dev = [jnp.asarray(a) for a in arrays] + [sets, ways, blocks]
            if masked:
                dev.append(jnp.asarray(masks))
            if mesh is not None:
                dev = _mesh_shard_lanes(dev, mesh, zero=(2, 6))
            engine = _record_engine(max_sets, max_ways, r_pad)
            hits_dev, codes_dev = engine(*dev)
        tracing.count(tracing.PROGRAMS, 1)
        tracing.count(tracing.SCAN_ROUNDS, rounds)
        with tracing.span(tracing.FETCH):
            hits = np.asarray(hits_dev, np.int64)
        with tracing.span(tracing.LANE_PLAN):
            shards = 1 if mesh is None else int(np.prod(list(
                mesh.shape.values())))
            plan = _record_rows_plan(recs, cfgs_b, drams_b, hits, r_pad,
                                     max_ways, shards)
        with tracing.span(tracing.DISPATCH):
            tables = plan.arrays
            if mesh is not None:
                tables = _mesh_shard_lanes(tables, mesh)
            counts_dev = _rows_engine(*plan.static)(codes_dev, *tables)
        with tracing.span(tracing.FETCH):
            counts = np.asarray(counts_dev, np.int64)
        tracing.count(tracing.FETCH_BYTES, hits_dev.nbytes + counts_dev.nbytes)
        tracing.count(tracing.DEVICE_REDUCED_LANES, len(recs))
        out = []
        for row, r in enumerate(recs):
            kw = _record_lane_kw(r, hits[row], drams_b[row], t_llc_hit)
            missed, nv_miss, row_hits, nv_row_hits = map(int, counts[row])
            _check_missed(missed, kw["accesses"], kw["hits"])
            out.append(_lane_metrics(row_hits=row_hits, nv_miss=nv_miss,
                                     nv_row_hits=nv_row_hits, **kw))
        return out
    return run


def _record_lane_kw(r: LaneRecords, hits, dram, t_llc_hit: int) -> dict:
    """A compacted lane's counts that the host holds: its segments,
    accesses, and hits from the record program's per-record ``hits``."""
    k, p = r.counts.shape
    h = hits[:k, :p]
    return dict(n_segments=int(r.raw.sum()), accesses=int(r.counts.sum()),
                hits=int(h.sum()), dram=dram, t_llc_hit=t_llc_hit,
                nv_acc=int(r.counts[r.nv].sum()), nv_hits=int(h[r.nv].sum()))


def _padded(n: int, least: int = 256) -> int:
    """``n`` rounded up to one of eight steps per power of two (at least
    ``least``), so that a new seed or campaign of about the same size
    reuses the compiled program."""
    step = max(least, 1 << max(0, n.bit_length() - 3))
    return -(-max(n, 1) // step) * step


@dataclasses.dataclass(frozen=True)
class _RowsPlan:
    """The host's part of ``_record_rows`` for one bucket: per-shard
    arrays (shard axis first) and the static sizes that key its
    compiled program."""
    arrays: tuple
    static: tuple


_SEG = ("unit_start", "plain", "b_off", "stride", "chunk", "count", "blk_off",
        "row0", "nv", "tk0", "tk_step", "nb", "lane", "bb", "bpr", "banks")
_HIT = ("rec", "code", "bmod", "off", "blk_off", "nb", "units", "unit_start",
        "nv", "lane", "bpr")


def _record_rows_plan(recs, cfgs_b, drams_b, hits, r_pad: int,
                      max_ways: int, shards: int = 1) -> _RowsPlan:
    """Plan the device reduction of a bucket's compacted lanes from the
    records and the fetched per-record ``hits``, in O(records x
    members).  Each live record member is a segment of units: a plain
    record's member (the record is one chunk) has one unit per DRAM row
    it touches, a member of a several-member record one unit per chunk.
    A unit's DRAM rows (at most ``T``) are its visits.  The lanes go in
    ``shards`` runs of consecutive lanes (one per device of a mesh),
    each laid out flat, so that no lane pays another's padding.

    Per segment (``_SEG`` columns): where its units start, its stride
    run, the offsets of its first block in its block and its row, where
    its visits go in its lane's trace order (``tk0`` plus ``tk_step`` a
    unit), its lane and the lane's geometry.  Per segment whose blocks
    the round scan hit (``hits`` less every access after a block's
    first), grouped by its unit length in blocks (``_HIT`` columns): its
    record, the code of its arrival 0, its first block's set and where
    its units start in its ordinals.  Per run of whole records that the
    device sorts as one row (``_sort_rows``): its units and its lane.

    The lanes share one set count, and a chunk of a several-member
    record is a whole number of blocks (``compact_lane``), so a hit
    segment's units are all alike.  Raises ``OverflowError`` where a
    lane's sort key would not fit int32: past about 16 whole frames in
    one lane beside four co-runners, at 32 banks.
    """
    sets = cfgs_b[0].sets
    if any(c.sets != sets for c in cfgs_b):
        raise ValueError("a record bucket's lanes share one set count")
    lanes = []
    width = 1                                   # T: rows a unit touches
    for r, llc, dram in zip(recs, cfgs_b, drams_b):
        bb, bpr = llc.block_bytes, dram.row_bytes // llc.block_bytes
        on = r.counts > 0
        if np.any(on & (r.counts * r.strides >= 2 ** 31)):
            raise UnsupportedTraceError("a record member spans 2 GiB or more")
        live = on.sum(axis=1)
        plain = live == 1                       # one member, one chunk
        chunk_blocks = -(-(r.chunks * r.strides) // bb) + 1
        multi = on & ~plain[:, None]
        if multi.any():
            width = max(width, int(((chunk_blocks + bpr - 1) // bpr
                                    + 1)[multi].max()))
        lanes.append((r, dram, bb, bpr, on, live, plain))
    per_shard = -(-len(recs) // shards)
    segs = [[] for _ in range(shards)]
    hit_rows = [{} for _ in range(shards)]
    n_real = [0] * shards
    starts = [[] for _ in range(shards)]
    keyspan = 1
    for lane, (r, dram, bb, bpr, on, live, plain) in enumerate(lanes):
        sh = lane // per_shard
        b_first = r.bases // bb
        b_last = (r.bases + np.maximum(r.counts - 1, 0) * r.strides) // bb
        nb = b_last - b_first + 1
        blk_off = b_first % bpr
        reps = -(-r.counts[:, :1] // np.maximum(r.chunks[:, :1], 1))
        units = np.where(plain[:, None], (blk_off + nb - 1) // bpr + 1, reps)
        cap = np.where(plain, units[:, 0], reps[:, 0] * live * width)
        keyspan = max(keyspan, int(cap.sum()))
        base = np.cumsum(cap) - cap
        m = np.arange(r.counts.shape[1])[None, :]
        tk0 = base[:, None] + np.where(plain[:, None], 0, m * width)
        tk_step = np.where(plain, 1, live * width)[:, None] + 0 * m
        rec, mem = np.nonzero(on)
        u = units[rec, mem]
        cols = dict(
            unit_start=n_real[sh] + np.cumsum(u) - u, plain=plain[rec],
            b_off=(r.bases - b_first * bb)[rec, mem],
            stride=r.strides[rec, mem], chunk=r.chunks[rec, mem],
            count=r.counts[rec, mem], blk_off=blk_off[rec, mem],
            row0=(b_first // bpr)[rec, mem], nv=r.nv[rec, mem],
            tk0=tk0[rec, mem], tk_step=tk_step[rec, mem], nb=nb[rec, mem],
            lane=lane % per_shard, bb=bb, bpr=bpr, banks=dram.banks)
        segs[sh].append(np.stack([np.broadcast_to(cols[c], rec.shape)
                                  for c in _SEG], axis=1))
        per_rec = np.bincount(rec, weights=u, minlength=r.counts.shape[0])
        starts[sh].append((lane % per_shard, n_real[sh],
                           n_real[sh] + np.cumsum(per_rec) - per_rec))
        n_real[sh] += int(u.sum())
        k, p = r.counts.shape
        scanned = (hits[lane, :k, :p] - (r.counts - nb))[rec, mem] > 0
        for i in np.flatnonzero(scanned):
            if cols["plain"][i]:
                unit, off = bpr, -cols["blk_off"][i]
            else:
                unit = cols["chunk"][i] * cols["stride"][i] // bb
                off = int(cols["b_off"][i] >= cols["stride"][i])
            hit_rows[sh].setdefault(int(unit), []).append(
                (rec[i], mem[i] * max_ways + 1, b_first[rec[i], mem[i]] % sets,
                 off, cols["blk_off"][i], cols["nb"][i], u[i],
                 cols["unit_start"][i], cols["nv"][i], lane % per_shard, bpr))
    banks = max(d.banks for d in drams_b)
    # a key is its bank times the span, plus its place in its lane
    keyspan = _padded(keyspan)
    if (banks + 1) * keyspan >= 2 ** 31:
        raise OverflowError(
            f"a lane of {keyspan} DRAM row visits over {banks} banks "
            "overflows the int32 sort key; split multi-frame sweeps into "
            "per-frame lane calls")
    segs = [np.concatenate(a) if a else np.zeros((0, len(_SEG)), np.int64)
            for a in segs]
    n_units = _padded(max(n_real))
    n_seg = _padded(max(len(a) for a in segs), least=8)
    seg = np.zeros((shards, n_seg, len(_SEG)), np.int64)
    seg[:, :, 0] = n_units                      # padding: no units
    for sh, a in enumerate(segs):
        seg[sh, :len(a)] = a
    pieces, row_len = _sort_rows(starts, n_real)
    n_rows = _padded(max(len(a) for a in pieces), least=8)
    rows = np.zeros((shards, n_rows, 3), np.int64)
    rows[:, :, 2] = -1                          # padding: no lane
    for sh, a in enumerate(pieces):
        rows[sh, :len(a)] = a
    arrays = [seg, np.asarray(n_real).reshape(shards, 1), rows]
    groups = []
    length = r_pad * sets                       # ordinals the codes cover
    for unit in sorted({b for g in hit_rows for b in g}):
        n_hit = _padded(max(len(g.get(unit, ())) for g in hit_rows), least=8)
        table = np.zeros((shards, n_hit, len(_HIT)), np.int64)
        table[:, :, 1] = -2 * max_ways * r_pad  # padding: matches no code
        table[:, :, 7] = n_units
        table[:, :, 10] = 1
        for sh, g in enumerate(hit_rows):
            a = np.asarray(g.get(unit, []), np.int64).reshape(-1, len(_HIT))
            table[sh, :len(a)] = a
        arrays.append(table)
        groups.append((unit, -(-length // unit) + 2))
    return _RowsPlan(
        arrays=tuple(a.astype(np.int32) for a in arrays),
        static=(n_units, width, keyspan, banks, per_shard, max_ways,
                row_len, tuple(groups)))


def _sort_rows(starts, n_real, rows: int = 128) -> tuple[list, int]:
    """Cut each shard's units into about ``rows`` runs of whole records
    of one lane, each at most ``row_len`` units: the rows the device
    sorts side by side.  ``starts`` holds, per shard and lane, the lane's
    first unit and its records' first units.  Returns per shard the
    (first unit, end, lane) of each run, and ``row_len``."""
    lanes = [[(lane, np.append(rec_at, per_shard[i + 1][1]
                               if i + 1 < len(per_shard) else total))
              for i, (lane, _, rec_at) in enumerate(per_shard)]
             for per_shard, total in zip(starts, n_real)]
    biggest = max([int(np.diff(b).max(initial=1)) for a in lanes
                   for _, b in a], default=1)
    row_len = _padded(max(biggest, -(-max(n_real) // rows)))
    out = []
    for per_shard in lanes:
        cuts = []
        for lane, bounds in per_shard:
            at, end = int(bounds[0]), int(bounds[-1])
            while at < end:
                stop = bounds[np.searchsorted(bounds, at + row_len,
                                              side="right") - 1]
                cuts.append((at, int(stop), lane))
                at = int(stop)
        out.append(cuts)
    return out, row_len


@functools.lru_cache(maxsize=32)
def _rows_engine(n_units: int, width: int, keyspan: int, banks: int,
                 lanes: int, max_ways: int, row_len: int, groups: tuple):
    body = jax.vmap(functools.partial(
        _record_rows, n_units=n_units, width=width, keyspan=keyspan,
        banks=banks, lanes=lanes, max_ways=max_ways, row_len=row_len,
        groups=groups))

    def run(codes, *plan):
        # the record program's (lanes, records, rounds, sets) hit codes,
        # split into the shards' runs of lanes
        shards = plan[0].shape[0]
        return body(codes.reshape(shards, -1, *codes.shape[1:]),
                    *plan).reshape(-1, 4)
    return jax.jit(run)


def _record_rows(codes, seg, n_real, rows, *hit_tables, n_units: int,
                 width: int, keyspan: int, banks: int, lanes: int,
                 max_ways: int, row_len: int, groups: tuple):
    """One shard of compacted lanes reduced to counts on the device: per
    lane its missed blocks, the NVDLA's missed blocks, DRAM row hits,
    and the row hits of the NVDLA's missed blocks, (lanes, 4) int32.

    Row hits depend only on the sequence of missed blocks: a unit's
    missed blocks in one DRAM row (a visit) are all row hits but the
    first, which hits when its bank's last visit before it, in any
    unit, was to the same row.  Sorting the visits by bank, then trace
    order, puts each next to the one before it; the sort runs on rows
    of whole records side by side, and a table of each row's first and
    last visit per bank joins the rows.  A visit
    whose blocks all hit in the LLC drops out: the round scan's hits
    (``codes``, as ``cache.record_lane_scan`` gives them) are laid out
    by member ordinal, unit by unit, for each segment they hit, and
    each unit's fully hit visits added to the units as a bit mask.
    Fields reach the units as cumulative sums of their changes at
    segment starts, and the hits as windows a segment long: no gather
    or scatter of one element per unit.  Exact as ``_record_miss_runs``
    and ``_lane_metrics_from_runs`` give it (tests/test_device_rows.py).
    """
    i32 = jnp.int32
    # each unit's segment fields: their changes at segment starts, summed
    change = seg - jnp.concatenate([jnp.zeros_like(seg[:1]), seg[:-1]])
    (start, plain, b_off, stride, chunk, count, blk_off, row0, nv, tk0,
     tk_step, nb, lane, bb, bpr, n_banks) = jnp.cumsum(
        jnp.zeros((seg.shape[1], n_units), i32).at[:, seg[:, 0]].add(
            change.T, mode="drop"), axis=1)
    i = jnp.arange(n_units, dtype=i32)
    u = i - start
    plain = plain > 0

    def block(j):                    # the member ordinal of access j
        return (b_off + j * stride) // bb

    j0 = u * chunk
    lo = jnp.where(plain, jnp.maximum(0, u * bpr - blk_off),
                   block(j0) + ((j0 > 0) & (block(j0 - 1) == block(j0))))
    hi = jnp.where(plain, jnp.minimum(nb, (u + 1) * bpr - blk_off),
                   block(jnp.minimum(j0 + chunk, count) - 1) + 1)
    hi = jnp.where(i < n_real[0], hi, lo)
    r_lo = (blk_off + lo) // bpr
    t = jnp.arange(width, dtype=i32)[:, None]       # visits: (T, units)
    rr = r_lo + t
    blocks = jnp.maximum(0, jnp.minimum(hi, (rr + 1) * bpr - blk_off)
                         - jnp.maximum(lo, rr * bpr - blk_off))
    # the round scan's hits: fully hit visits, and how many hits
    mask = jnp.zeros(n_units + max([n for _, n in groups], default=0), i32)
    found = jnp.zeros((lanes, 2), i32)
    for (unit, n_u), table in zip(groups, hit_tables):
        got, n_hit = _hit_units(codes, table, unit, n_u, width, lanes)
        mask = jax.lax.scatter_add(
            mask, table[:, 7:8], got, jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(),
                scatter_dims_to_operand_dims=(0,)))
        found = found + n_hit
    seen = (blocks > 0) & ((mask[None, :n_units] >> t) & 1 == 0)
    is_nv = jnp.broadcast_to(nv > 0, seen.shape)
    # the bank carry.  Each row (``rows``: a run of whole records of one
    # lane, in trace order) is sorted by bank, then trace order, so each
    # visit follows the one before it in its bank; a bank's first visit
    # in a row takes the bank's last one from the rows before
    big = jnp.iinfo(jnp.int32).max
    row = row0 + rr
    key = jnp.where(seen, (row % n_banks) * keyspan + tk0 + u * tk_step + t,
                    big)
    both = jnp.pad(jnp.concatenate([key, row * 2 + is_nv]),
                   ((0, 0), (0, row_len)), constant_values=big)

    def cut(first, end):
        w = jax.lax.dynamic_slice(both, (0, first), (2 * width, row_len))
        live = jnp.arange(row_len, dtype=i32) < end - first
        return (jnp.where(live, w[:width], big).reshape(-1),
                w[width:].reshape(-1))

    key, packed = jax.lax.sort(jax.vmap(cut)(rows[:, 0], rows[:, 1]),
                               num_keys=1)
    same = ((key[:, 1:] < big) & (key[:, 1:] // keyspan
                                  == key[:, :-1] // keyspan)
            & (packed[:, 1:] // 2 == packed[:, :-1] // 2))
    carry = jnp.sum(same, axis=1, dtype=i32)
    carry_nv = jnp.sum(same & (packed[:, 1:] % 2 == 1), axis=1, dtype=i32)
    edges = jax.vmap(lambda k: jnp.searchsorted(
        k, jnp.arange(banks + 1, dtype=i32) * keyspan))(key)
    there = edges[:, 1:] > edges[:, :-1]                 # (rows, banks)
    first = jnp.take_along_axis(packed, edges[:, :-1], axis=1)
    last = jnp.take_along_axis(packed, jnp.maximum(edges[:, 1:] - 1, 0),
                               axis=1)
    def join(state, at):
        held, prev_lane = state
        lane_k, there_k, first_k, last_k = at
        held = jnp.where(lane_k == prev_lane, held, -1)
        same = there_k & (held >= 0) & (first_k // 2 == held // 2)
        return ((jnp.where(there_k, last_k, held), lane_k),
                (jnp.sum(same, dtype=i32),
                 jnp.sum(same & (first_k % 2 == 1), dtype=i32)))

    _, (extra, extra_nv) = jax.lax.scan(
        join, (jnp.full((banks,), -1, i32), jnp.int32(-1)),
        (rows[:, 2], there, first, last))
    carry = carry + extra
    carry_nv = carry_nv + extra_nv

    def per_lane(x, at):
        return jnp.stack([jnp.sum(jnp.where(at == k, x, 0), dtype=i32)
                          for k in range(lanes)])

    lanes_of = jnp.broadcast_to(lane, seen.shape)
    total = per_lane(blocks, lanes_of) - found[:, 0]
    nv_total = per_lane(jnp.where(is_nv, blocks, 0), lanes_of) - found[:, 1]
    return jnp.stack([
        total, nv_total,
        total - per_lane(seen, lanes_of) + per_lane(carry, rows[:, 2]),
        nv_total - per_lane(seen & is_nv, lanes_of)
        + per_lane(carry_nv, rows[:, 2])], axis=1)


def _hit_units(codes, table, unit: int, n_u: int, width: int, lanes: int):
    """The round scan's hits in one group of segments, each laid out by
    its ordinals, ``unit`` blocks to a unit, ``n_u`` units from its
    first: per segment and unit the bit mask of its fully hit visits,
    (segments, n_u); and per lane the hits, all and the NVDLA's,
    (lanes, 2)."""
    i32 = jnp.int32
    code, bmod = table[:, 1, None, None], table[:, 2, None, None]
    r_pad, sets = codes.shape[2], codes.shape[3]
    c = codes[table[:, 9], table[:, 0]].astype(i32)     # (S, r_pad, sets)
    arrival = code + jnp.arange(r_pad, dtype=i32)[None, :, None]
    flags = functools.reduce(jnp.logical_or, [
        c[:, k:k + 1, :] == arrival for k in range(r_pad)])
    # ordinal order: arrival q in set s is ordinal q*sets + (s - bmod) %
    # sets, so the sets below bmod follow the arrival after
    s = jnp.arange(sets, dtype=i32)[None, None, :]
    zero = jnp.zeros_like(flags[:, :1])
    line = jnp.where(s >= bmod, jnp.concatenate([flags, zero], axis=1),
                     jnp.concatenate([zero, flags], axis=1))
    line = line.reshape(line.shape[0], -1)                # ordinal + bmod
    line = jnp.pad(line, ((0, 0), (unit, n_u * unit + 1)))
    grid = jax.vmap(lambda row, a: jax.lax.dynamic_slice(
        row, (a,), (n_u * unit,)))(line, unit + table[:, 2] + table[:, 3])
    first = jax.vmap(lambda row, a: row[a])(line, unit + table[:, 2])
    off, blk_off, nb, units, nv, bpr = (table[:, c, None]
                                        for c in (3, 4, 5, 6, 8, 10))
    pos = jnp.arange(n_u * unit, dtype=i32)[None, :]
    g = pos // unit
    o = pos + off
    real = (o >= 0) & (o < nb) & (g < units)
    hit = grid & real
    # a chunk starting mid-block takes its first block in with unit 0
    extra = (off[:, 0] > 0) & (nb[:, 0] > 0) & (units[:, 0] > 0)
    lo = jnp.where(g == 0, 0, jnp.maximum(0, g * unit + off))
    t_o = (blk_off + o) // bpr - (blk_off + lo) // bpr
    lead = (jnp.arange(n_u)[None, :] == 0) & extra[:, None]
    mask = jnp.zeros((grid.shape[0], n_u), i32)
    for t in range(width):
        here = real & (t_o == t)
        some = jnp.any(here.reshape(-1, n_u, unit), axis=-1)
        full = ~jnp.any((here & ~hit).reshape(-1, n_u, unit), axis=-1)
        if t == 0:
            some = some | lead
            full = full & (~lead | first[:, None])
        mask = mask + ((some & full).astype(i32) << t)
    n_hit = jnp.sum(hit, axis=1, dtype=i32) + (extra & first).astype(i32)
    at = table[:, 9]
    return mask, jnp.stack([
        jnp.stack([jnp.sum(jnp.where(at == k, n_hit, 0), dtype=i32),
                   jnp.sum(jnp.where((at == k) & (nv[:, 0] > 0), n_hit, 0),
                           dtype=i32)])
        for k in range(lanes)])


def _record_rounds(r: LaneRecords, llc: LLCConfig) -> np.ndarray:
    """Round-scan rounds per record: each class's first ``k`` arrivals
    per set (``k`` the class's ways; unpartitioned, one class of
    ``ways``), bounded by the arrivals the class's members bring to any
    one set."""
    bb, sets = llc.block_bytes, llc.sets
    live = r.counts > 0
    last = r.bases + np.maximum(r.counts - 1, 0) * r.strides
    nb = np.where(live, last // bb - r.bases // bb + 1, 0)
    same, k = _classes(r.masks, llc.ways)
    per_class = (same * (-(-nb // sets))[:, None, :]).sum(axis=2)
    # count each class once, at its first live member
    first = live & ~(np.tril(same, -1) & live[:, None, :]).any(axis=2)
    return np.where(first, np.minimum(k, per_class), 0).sum(axis=1)


def _record_miss_runs(r: LaneRecords, llc: LLCConfig, codes: np.ndarray,
                      max_ways: int) -> tuple:
    """The host oracle of ``_record_rows``: a compacted lane's
    missed-block runs in the order of its uncompacted trace: every
    chunk's newly touched blocks (a block its previous chunk already
    touched is a hit), less the round scan's hits
    (``cache.record_lane_scan``'s codes).  Returns ``(first_blocks,
    n_blocks, member)`` int64 arrays, ``member`` the flat (record,
    member) index, for ``_lane_metrics_from_runs``.  The record path
    itself never calls it (tests/test_device_rows.py)."""
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
    """The vmapped record program; called with the members' allocation
    masks as its last operand, it traces the partitioned scan."""
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

"""Token-level SoC memory pipeline — the paper's Figure 2, executable.

Composes the exact LLC simulator and the DRAM row/bank model as FAME-1
components behind the NVDLA DBB: each *target* cycle one DBB burst
address flows  DBB -> LLC (hit/miss classification, LRU update) ->
DRAM (row hit/miss service latency for LLC misses).  Host stalls may gate
any component on any host cycle (FireSim's situation when the host FPGA's
DRAM is slow) — the per-access latencies and every cache/bank state are
bit-identical regardless (tests/test_socsim.py, with Hypothesis).

This is the mechanism layer under ``repro.core.accelerator``'s closed-form
timing: where the closed form aggregates streams statistically, this
pipeline replays an actual burst trace cycle by cycle.  Used for (a)
validating the closed form on real layer traces and (b) demonstrating
FAME-1 semantics on the paper's own topology.

Performance: replay rides the chunked early-exit FAME-1 scheduler (the
host-cycle scan stops as soon as the sink drains the trace, and all-stall
host cycles are pre-compacted away — see ``repro.core.fame1``); for
hit-rate-only questions the segment-lane engine
(``repro.core.sweep``) replays the compressed trace of
``repro.core.traces`` without per-access replay, and for latency
*totals* ``simulate_dbb_segments`` runs it as a solo lane with the
closed-form DRAM row model (``repro.core.dram.segment_row_hits``) so the
whole pipeline result comes out of segment-level arithmetic — bit
-identical to the per-access pipeline.  Address arrays go through
``repro.utils.env`` so 64-bit DBB addresses can never be silently
truncated when x64 is disabled.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp

from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.fame1 import Component, FAME1Pipeline
from repro.utils.env import address_dtype, as_address_array


def llc_component(cfg: LLCConfig) -> Component:
    sets, ways = cfg.sets, cfg.ways
    adt = address_dtype()

    def step(state, addr):
        tags, age = state
        block = addr // cfg.block_bytes
        s = (block % sets).astype(jnp.int32)
        t = block // sets
        row_tags = tags[s]
        row_age = age[s]
        match = row_tags == t
        hit = jnp.any(match)
        way = jnp.where(hit, jnp.argmax(match), jnp.argmax(row_age))
        tags = tags.at[s, way].set(t)
        age = age.at[s].set(jnp.where(jnp.arange(ways) == way, 0,
                                      row_age + 1))
        return (tags, age), {"addr": addr, "hit": hit}

    init = (jnp.full((sets, ways), -1, adt),
            jnp.zeros((sets, ways), jnp.int32))
    return Component("llc", step, init,
                     {"addr": jnp.zeros((), adt), "hit": jnp.bool_(False)})


def dram_component(llc_cfg: LLCConfig, dram_cfg: DRAMConfig,
                   t_llc_hit: int = 20) -> Component:
    banks = dram_cfg.banks

    def step(open_rows, tok):
        addr, hit = tok["addr"], tok["hit"]
        row = addr // dram_cfg.row_bytes
        bank = (row % banks).astype(jnp.int32)
        row_of_bank = row // banks
        row_hit = open_rows[bank] == row_of_bank
        dram_lat = jnp.where(
            row_hit, dram_cfg.t_cas_cycles,
            dram_cfg.t_rp_cycles + dram_cfg.t_rcd_cycles
            + dram_cfg.t_cas_cycles)
        # a miss pays the LLC lookup AND the DRAM access
        lat = jnp.where(hit, t_llc_hit, t_llc_hit + dram_lat).astype(jnp.int32)
        # only LLC misses touch DRAM state (no row activation on a hit)
        open_rows = jnp.where(
            hit, open_rows, open_rows.at[bank].set(row_of_bank))
        return open_rows, lat

    return Component("dram", step, jnp.full((banks,), -1, address_dtype()),
                     jnp.int32(0))


@dataclasses.dataclass
class MemPipelineResult:
    latencies: jax.Array     # (T,) per-access service latency
    total_cycles: jax.Array  # sum
    host_cycles: int | None = None   # host cycles the scheduler spent


@functools.lru_cache(maxsize=32)
def _mem_pipeline(llc_cfg: LLCConfig, dram_cfg: DRAMConfig,
                  x64: bool) -> FAME1Pipeline:
    """One pipeline (and so one jit cache) per memory configuration —
    repeated replays reuse the compiled host program.  The x64 key
    rebuilds the pipeline if the precision mode flips mid-process."""
    return FAME1Pipeline([llc_component(llc_cfg),
                          dram_component(llc_cfg, dram_cfg)])


def _positional_config_warning(fn_name: str) -> str:
    return (f"positional configs to {fn_name}() are deprecated; pass "
            "llc=/dram= keyword-only (the shared convention across the "
            "sweep/pipeline APIs)")


def _legacy_configs(fn_name: str, legacy: tuple, llc, dram):
    """One-release escape hatch: positional (llc, dram) still works but
    warns.  The ``DeprecationWarning`` itself is emitted by the *public*
    function (``warnings.warn(..., stacklevel=2)``, the repo-wide
    convention — every deprecation attributes to the caller's line, and
    tests/test_deprecations.py asserts the attribution for every site).
    This helper used to warn on the public function's behalf, which
    forced a one-off ``stacklevel=3`` to skip its own frame.  Returns
    the resolved (llc, dram); raises ``TypeError`` on a config passed
    both ways or a missing ``llc``."""
    if legacy:
        if len(legacy) > 2:
            raise TypeError(f"{fn_name}() takes at most 2 positional "
                            f"configs, got {len(legacy)}")
        if llc is not None or (dram is not None and len(legacy) > 1):
            raise TypeError(f"{fn_name}() got a config both positionally "
                            "and by keyword")
        llc = legacy[0]
        if len(legacy) > 1:
            dram = legacy[1]
    if llc is None:
        raise TypeError(f"{fn_name}() missing required keyword argument "
                        "'llc'")
    return llc, dram


def simulate_dbb_stream(byte_addrs, *legacy, llc: LLCConfig | None = None,
                        dram: DRAMConfig | None = None,
                        host_stalls=None,
                        early_exit: bool = True) -> MemPipelineResult:
    """Replay a DBB burst-address trace through the LLC -> DRAM pipeline.

    Configs are keyword-only (``llc=``, ``dram=``) — the shared
    convention across the sweep/pipeline APIs; positional configs still
    work for one release but emit ``DeprecationWarning``.
    ``early_exit=False`` forces the seed's fixed-length host schedule
    (used by benchmarks as the before/after baseline); results are
    bit-identical either way.
    """
    from repro.utils.env import x64_enabled

    if legacy:
        warnings.warn(_positional_config_warning("simulate_dbb_stream"),
                      DeprecationWarning, stacklevel=2)
    llc, dram = _legacy_configs("simulate_dbb_stream", legacy, llc, dram)
    dram = dram or DRAMConfig()
    addrs = as_address_array(byte_addrs, what="DBB byte address")
    pipe = _mem_pipeline(llc, dram, x64_enabled())
    _, lats, n = pipe.run(addrs, host_stalls=host_stalls,
                          max_host_cycles=(host_stalls.shape[0]
                                           if host_stalls is not None else None),
                          early_exit=early_exit)
    t = addrs.shape[0]
    return MemPipelineResult(latencies=lats[:t],
                             total_cycles=jnp.sum(lats[:t]),
                             host_cycles=pipe.last_host_cycles)


# --------------------------------------------------------------------------
# segment-native totals: no per-access replay at all
# --------------------------------------------------------------------------
class PipelineInvariantError(ValueError):
    """A memory-pipeline result violates a closed-form invariant — the
    numbers cannot have come from a correct simulation (a poisoned
    worker, a corrupted record, an injected fault)."""


def check_segment_totals(*, accesses: int, llc_hits: int,
                         dram_row_hits: int, total_cycles: int,
                         dram: DRAMConfig, t_llc_hit: int = 20) -> None:
    """Validate a (accesses, hits, row hits, total) quadruple against
    the closed-form latency identity of ``simulate_dbb_segments``:

        total = T*t_llc_hit + misses*tCAS + row_misses*(tRP + tRCD)

    plus the counting invariants 0 <= hits <= accesses and
    0 <= row_hits <= misses.  Raises ``PipelineInvariantError`` with the
    failing relation spelled out; used both on fresh results and when a
    resumed campaign re-validates journaled records
    (``repro.campaign.executor``)."""
    vals = (accesses, llc_hits, dram_row_hits, total_cycles)
    if not all(isinstance(v, int) for v in vals):
        raise PipelineInvariantError(
            f"pipeline counters must be ints, got {vals!r}")
    if accesses < 0 or llc_hits < 0 or dram_row_hits < 0:
        raise PipelineInvariantError(
            f"negative pipeline counter: accesses={accesses} "
            f"llc_hits={llc_hits} dram_row_hits={dram_row_hits}")
    if llc_hits > accesses:
        raise PipelineInvariantError(
            f"llc_hits {llc_hits} exceeds accesses {accesses}")
    misses = accesses - llc_hits
    if dram_row_hits > misses:
        raise PipelineInvariantError(
            f"dram_row_hits {dram_row_hits} exceeds LLC misses {misses}")
    expect = (accesses * t_llc_hit + misses * dram.t_cas_cycles
              + (misses - dram_row_hits)
              * (dram.t_rp_cycles + dram.t_rcd_cycles))
    if total_cycles != expect:
        raise PipelineInvariantError(
            f"total_cycles {total_cycles} != closed form {expect} "
            f"(accesses={accesses} misses={misses} "
            f"row_hits={dram_row_hits})")


def check_segment_totals_batch(*, accesses, llc_hits, dram_row_hits,
                               total_cycles, drams,
                               t_llc_hit: int = 20) -> None:
    """Vectorized ``check_segment_totals`` over a point batch — the
    executor's fast pre-validation of an unstacked mesh batch before
    the per-point guardrails run.  All four counter arguments are
    equal-length sequences of ints, ``drams`` the per-point DRAM
    configs.  Raises ``PipelineInvariantError`` naming every failing
    batch index (one bad point must not mask another — the caller
    quarantines per point)."""
    import numpy as np

    acc = np.asarray(accesses, np.int64)
    hits = np.asarray(llc_hits, np.int64)
    row = np.asarray(dram_row_hits, np.int64)
    tot = np.asarray(total_cycles, np.int64)
    n = len(acc)
    if not (len(hits) == len(row) == len(tot) == len(drams) == n):
        raise PipelineInvariantError(
            "batch counter sequences have mismatched lengths")
    misses = acc - hits
    t_cas = np.asarray([d.t_cas_cycles for d in drams], np.int64)
    t_act = np.asarray([d.t_rp_cycles + d.t_rcd_cycles for d in drams],
                       np.int64)
    expect = acc * t_llc_hit + misses * t_cas + (misses - row) * t_act
    bad = ((acc < 0) | (hits < 0) | (row < 0) | (hits > acc)
           | (row > misses) | (tot != expect))
    if bad.any():
        idxs = np.nonzero(bad)[0]
        details = ", ".join(
            f"[{i}] accesses={acc[i]} llc_hits={hits[i]} "
            f"row_hits={row[i]} total={tot[i]} expect={expect[i]}"
            for i in idxs[:8])
        raise PipelineInvariantError(
            f"{idxs.size}/{n} batch points violate the pipeline "
            f"invariants: {details}")


@dataclasses.dataclass
class SegmentPipelineResult:
    total_cycles: int            # == simulate_dbb_stream(...).total_cycles
    accesses: int
    llc_hits: int
    dram_row_hits: int           # row hits among the LLC misses

    @property
    def llc_hit_rate(self) -> float:
        return self.llc_hits / max(1, self.accesses)

    @property
    def mean_latency(self) -> float:
        return self.total_cycles / max(1, self.accesses)

    def check_invariants(self, dram: DRAMConfig,
                         t_llc_hit: int = 20) -> "SegmentPipelineResult":
        """Raise ``PipelineInvariantError`` unless the counters satisfy
        the closed-form identities; returns self for chaining."""
        check_segment_totals(
            accesses=self.accesses, llc_hits=self.llc_hits,
            dram_row_hits=self.dram_row_hits,
            total_cycles=self.total_cycles,
            dram=dram, t_llc_hit=t_llc_hit)
        return self


def simulate_dbb_segments(segments, *legacy, llc: LLCConfig | None = None,
                          dram: DRAMConfig | None = None,
                          t_llc_hit: int = 20) -> SegmentPipelineResult:
    """Latency totals of the LLC -> DRAM pipeline over a *compressed*
    DBB trace, with no per-access replay on either side.

    The trace runs as a solo interference lane (no co-runners) through
    ``sweep.interference_lane_metrics_batch``: the segment LLC engine
    classifies hits and emits the exact miss stream as runs of
    consecutive blocks, and the closed-form DRAM row model counts row
    hits over those runs with per-bank open-row carry.  Since every
    per-access latency is determined by (llc hit?, dram row hit?), the
    totals are bit-identical to ``simulate_dbb_stream`` on the expanded
    trace (tests/test_dram_segments.py):

        total = T*t_llc_hit + misses*tCAS + row_misses*(tRP + tRCD)

    Requires ``dram.row_bytes % llc.block_bytes == 0`` (every standard
    geometry) so a missed block's row is independent of which burst in
    the block missed, and every stride in ``(0, block_bytes]``
    (``sweep.UnsupportedTraceError`` otherwise: ``simulate_dbb_stream``
    replays such a trace).  Configs are keyword-only (``llc=``,
    ``dram=``); positional use warns for one release.
    """
    from repro.core.sweep import MixConfig, interference_lane_metrics_batch

    if legacy:
        warnings.warn(_positional_config_warning("simulate_dbb_segments"),
                      DeprecationWarning, stacklevel=2)
    llc, dram = _legacy_configs("simulate_dbb_segments", legacy, llc, dram)
    dram = dram or DRAMConfig()
    bb = llc.block_bytes
    if dram.row_bytes % bb:
        raise ValueError(
            f"row_bytes {dram.row_bytes} not a multiple of block_bytes "
            f"{bb}: a block could straddle rows; use simulate_dbb_stream")
    (lane,) = interference_lane_metrics_batch(
        segments, llcs=[llc], drams=[dram], mixes=[MixConfig()],
        t_llc_hit=t_llc_hit)
    return SegmentPipelineResult(total_cycles=lane.total_cycles,
                                 accesses=lane.accesses,
                                 llc_hits=lane.llc_hits,
                                 dram_row_hits=lane.dram_row_hits)

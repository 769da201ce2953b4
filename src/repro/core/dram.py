"""DRAM timing model: banks, open rows, FR-FCFS-style row-hit priority.

Matches the FireSim memory-model knobs the paper uses (DDR3, 4 ranks x 8
banks, FR-FCFS): per access the latency is

    row hit   -> tCAS
    row miss  -> tRP + tRCD + tCAS        (precharge + activate + CAS)

simulated exactly with a ``lax.scan`` carrying the open row per bank —
or, for stride-run segment streams (the compressed DBB traces of
``repro.core.traces`` and the LLC miss runs the segment engine emits),
computed in closed form by ``segment_row_hits``: rows touched per
segment, per-bank open-row carry across segment boundaries, bit
-identical to the per-access scan with O(bank visits) work, min(rows,
banks) per segment.
FR-FCFS's *scheduling* effect (row hits served first under load) and
inter-master contention are modeled at the queue level in
``repro.core.interference`` — this module is the deterministic service
-time component.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DRAMConfig:
    banks: int = 32                  # 4 ranks x 8 banks
    row_bytes: int = 2048
    t_cas_cycles: int = 14           # DDR3-1600-ish, in memory-clock cycles
    t_rcd_cycles: int = 14
    t_rp_cycles: int = 14
    clock_hz: float = 800e6          # memory controller clock
    bus_bytes_per_cycle: int = 16    # 64-bit DDR -> 16 B / controller cycle

    @property
    def peak_bw(self) -> float:
        return self.clock_hz * self.bus_bytes_per_cycle


@functools.partial(jax.jit, static_argnames=("banks",))
def access_latencies(byte_addrs: jax.Array, *, banks: int, row_bytes: int,
                     t_cas: int, t_rcd: int, t_rp: int):
    """byte_addrs (T,) -> per-access latency in memory cycles (exact
    open-row bookkeeping; no queueing)."""
    row = byte_addrs // row_bytes
    bank = row % banks
    row_of_bank = row // banks

    def step(open_rows, inp):
        b, r = inp
        hit = open_rows[b] == r
        lat = jnp.where(hit, t_cas, t_rp + t_rcd + t_cas)
        return open_rows.at[b].set(r), lat

    init = jnp.full((banks,), -1, jnp.int64)
    _, lats = jax.lax.scan(step, init,
                           (bank.astype(jnp.int32), row_of_bank))
    return lats


def row_hit_rate(byte_addrs, cfg: DRAMConfig) -> float:
    lats = access_latencies(
        jnp.asarray(byte_addrs, jnp.int64), banks=cfg.banks,
        row_bytes=cfg.row_bytes, t_cas=cfg.t_cas_cycles,
        t_rcd=cfg.t_rcd_cycles, t_rp=cfg.t_rp_cycles)
    return float(jnp.mean((lats == cfg.t_cas_cycles).astype(jnp.float32)))


# --------------------------------------------------------------------------
# closed-form row model for stride-run segments
# --------------------------------------------------------------------------
@dataclasses.dataclass
class RowHitResult:
    row_hits: int                # accesses served from an open row
    accesses: int
    open_rows: np.ndarray        # final per-bank open row ids (-1 closed)
    per_segment: np.ndarray      # (n_segments,) int64 row hits

    @property
    def row_hit_rate(self) -> float:
        return self.row_hits / max(1, self.accesses)


def _bank_first_last_rows(r0: int, r1: int, banks: int):
    """For the contiguous row run [r0, r1]: each bank's first and last
    visited row (full row ids), and which banks are visited at all."""
    b = np.arange(banks, dtype=np.int64)
    first = r0 + ((b - r0) % banks)
    last = r1 - ((r1 - b) % banks)
    visited = first <= r1
    return first, last, visited


def _row_hits_bulk(base: np.ndarray, stride: np.ndarray, count: np.ndarray,
                   banks: int, rb: int, rows_state: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized carry chain for stride <= row_bytes segments.  A
    segment sweeps the row run [r0, r1]; it visits each bank at most
    once per ``banks`` rows, so its carry-in hits are decided by its
    *bank visits* — per bank its first and last row — alone: the first
    row hits when the bank's previous visit, by an earlier segment,
    left that row open.  Sorting the visits by bank (stably, so each
    bank keeps segment order) puts every visit next to the one before
    it, so the whole serial loop is O(visits) numpy with no Python per
    segment, and a visit count of min(rows, banks) per segment.
    Returns (per_segment row hits, final open rows) — bit-identical to
    the scalar loop."""
    n = base.shape[0]
    if n == 0:
        return np.zeros(0, np.int64), rows_state[:banks].copy()
    live = count > 0
    r0 = base // rb
    r1 = (base + np.maximum(count - 1, 0) * stride) // rb
    visits = np.where(live, np.minimum(r1 - r0 + 1, banks), 0)
    seg = np.repeat(np.arange(n, dtype=np.int64), visits)
    first = (r0[seg] + np.arange(seg.shape[0], dtype=np.int64)
             - np.repeat(np.cumsum(visits) - visits, visits))
    last = r1[seg] - ((r1[seg] - first) % banks)
    bank = first % banks
    order = np.argsort(bank.astype(np.int16 if banks < 2 ** 15
                                   else np.int64), kind="stable")
    b, f, lst = bank[order], first[order], last[order]
    opens = np.empty_like(b, dtype=bool)
    opens[:1] = True
    opens[1:] = b[1:] != b[:-1]         # the bank's first visit
    prev = np.empty_like(lst)
    prev[1:] = lst[:-1]
    prev[opens] = rows_state[b[opens]]
    carry = np.bincount(seg[order[prev == f]], minlength=n)
    per_seg = np.where(live, count - (r1 - r0 + 1) + carry, 0)
    final = rows_state[:banks].copy()
    closes = np.append(b[1:] != b[:-1], True)[:b.shape[0]]
    final[b[closes]] = lst[closes]
    return per_seg.astype(np.int64), final.astype(np.int64)


def segment_row_hits(segments, cfg: DRAMConfig,
                     open_rows: np.ndarray | None = None) -> RowHitResult:
    """Row-hit count of a compressed stride-run trace, closed form.

    Bit-identical to replaying the expanded trace through
    ``access_latencies`` (tests/test_dram_segments.py, with Hypothesis),
    with work O(bank visits), min(rows, banks) per segment, instead of
    O(accesses):

    * a segment with stride <= row_bytes sweeps the contiguous row run
      [base//row_bytes, last//row_bytes]; every row is visited once,
      contiguously, so all accesses beyond each row's first hit that
      open row, and a row's *first* access can only hit via the open-row
      state carried in from earlier segments — possible only for each
      bank's first visited row (later visits to a bank always follow an
      intra-segment activation of a different row of that bank);
    * a segment with stride > row_bytes touches a strictly increasing,
      gappy row sequence — rare (never produced by DBB streams or LLC
      miss runs), replayed per access with the same open-row carry.

    ``open_rows`` continues from a prior result's state (full row ids,
    -1 = closed); segments may be ``Segment`` objects or
    ``(base, stride, count)`` tuples, base/stride in bytes.
    """
    from repro.core.traces import segment_tuple

    banks, rb = cfg.banks, cfg.row_bytes
    rows_state = (np.full(banks, -1, np.int64) if open_rows is None
                  else np.array(open_rows, np.int64, copy=True))
    if isinstance(segments, tuple) and len(segments) == 3 \
            and isinstance(segments[0], np.ndarray):
        base_a, stride_a, count_a = (np.asarray(a, np.int64)
                                     for a in segments)
    else:
        seg_list = [segment_tuple(s) for s in segments]
        base_a = np.asarray([m[0] for m in seg_list], np.int64)
        stride_a = np.asarray([m[1] for m in seg_list], np.int64)
        count_a = np.asarray([m[2] for m in seg_list], np.int64)
    live_a = count_a > 0
    if np.any(live_a & (stride_a <= 0)):
        bad = int(stride_a[live_a & (stride_a <= 0)][0])
        raise ValueError(f"segment stride must be positive: {bad}")
    if not np.any(live_a & (stride_a > rb)):
        per_seg, rows_state = _row_hits_bulk(
            base_a, stride_a, count_a, banks, rb, rows_state)
        return RowHitResult(row_hits=int(per_seg.sum()),
                            accesses=int(count_a[live_a].sum()),
                            open_rows=rows_state, per_segment=per_seg)
    seg_list = list(zip(base_a.tolist(), stride_a.tolist(),
                        count_a.tolist()))
    per_seg = np.zeros(len(seg_list), np.int64)
    accesses = 0
    for i, (base, stride, count) in enumerate(seg_list):
        if count <= 0:
            continue
        if stride <= 0:
            raise ValueError(f"segment stride must be positive: {stride}")
        accesses += count
        if stride > rb:
            # gappy rows: every access opens (or re-hits) its own row
            rows = (base + np.arange(count, dtype=np.int64) * stride) // rb
            hits = 0
            for r in rows:
                b = int(r % banks)
                hits += rows_state[b] == r
                rows_state[b] = r
            per_seg[i] = hits
            continue
        r0 = base // rb
        r1 = (base + (count - 1) * stride) // rb
        first, last, visited = _bank_first_last_rows(r0, r1, banks)
        carry_hits = int((visited & (rows_state[:banks] == first)).sum())
        per_seg[i] = count - (r1 - r0 + 1) + carry_hits
        rows_state = np.where(visited, last, rows_state)
    return RowHitResult(row_hits=int(per_seg.sum()), accesses=accesses,
                        open_rows=rows_state, per_segment=per_seg)

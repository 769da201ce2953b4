"""Set-associative LLC simulator — exact, vectorized, runtime-configurable.

The FireSim LLC model is runtime-configurable in sets/ways/block size
without an FPGA rebuild; this is the same knob set, as pure JAX.  State
is (tags, age) of shape (sets, ways); each access updates one set with
true LRU.  Three execution paths, all bit-identical in final state and
hit counts (tests/test_traces.py proves parity):

* **exact per-access scan** (``simulate_trace``): one ``lax.scan`` step
  per access — the reference semantics, used on unit-test traces and as
  the parity oracle;
* **compressed segment engine** (``simulate_segments``): a DBB stream is
  run-length-compressed into ``(base, stride, count)`` segments
  (``repro.core.traces``).  A sequential segment is analytically
  predictable under LRU, so it is retired either

  - in **O(1) serial steps** (closed form): when the segment sweeps every
    set at least ``ways`` times and none of its blocks are already
    resident, every first touch misses, victims cycle through the ways in
    prior-LRU order, and the final (tags, age) state and hit count are
    written directly with no scan at all; or
  - by the **per-set round scan**: one scan step retires one block *per
    set* (``sets`` blocks at once, each with all its intra-block burst
    repeats folded in), so serial depth drops from O(accesses) to
    O(blocks / sets) — exact for warm/overlapping/partial segments where
    the closed form does not apply.

  The exact per-access scan remains the fallback at segment boundaries
  that compression cannot express (stride > block size).
* **batched multi-geometry scan** (``repro.core.sweep``): (tags, age)
  padded to the largest geometry in a sweep and ``jax.vmap``-ed over
  (sets, ways, block_bytes) so a whole Fig. 5 grid compiles once and
  runs as a single device program (``segment_lane_scan``); an
  interference lane compacted into records of the arbiter's repeating
  pattern runs one record per step (``record_lane_scan``).

Used two ways: exactly, on sampled windows of the NVDLA DBB stream (the
per-stream hit rates feed the accelerator timing model); and as the
reference that validates the closed-form stream-locality model in
``repro.core.accelerator`` (sequential-burst hit rate = 1 - 32/B).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LLCConfig:
    size_bytes: int = 2 * 1024 * 1024
    ways: int = 8
    block_bytes: int = 64

    @property
    def sets(self) -> int:
        return max(1, self.size_bytes // (self.ways * self.block_bytes))


def block_address(byte_addr, block_bytes: int):
    return byte_addr // block_bytes


def cold_state(sets: int, ways: int) -> tuple[jax.Array, jax.Array]:
    """The (tags, age) state of an empty cache."""
    return (jnp.full((sets, ways), -1, jnp.int32),
            jnp.zeros((sets, ways), jnp.int32))


@functools.partial(jax.jit, static_argnames=("sets", "ways"))
def _scan_trace(state, block_addrs, *, sets: int, ways: int):
    """Exact per-access scan from an arbitrary (tags, age) state."""
    set_idx = (block_addrs % sets).astype(jnp.int32)
    tag = (block_addrs // sets).astype(jnp.int32)

    def step(carry, inp):
        tags, age = carry                   # (sets, ways) each
        s, t = inp
        row_tags = tags[s]
        row_age = age[s]
        match = row_tags == t
        hit = jnp.any(match)
        way = jnp.where(hit, jnp.argmax(match), jnp.argmax(row_age))
        row_tags = row_tags.at[way].set(t)
        # true LRU: touched way -> age 0, everything else in the set +1
        row_age = jnp.where(jnp.arange(ways) == way, 0, row_age + 1)
        tags = tags.at[s].set(row_tags)
        age = age.at[s].set(row_age)
        return (tags, age), hit

    state, hits = jax.lax.scan(step, state, (set_idx, tag))
    return state, hits


def simulate_trace(block_addrs: jax.Array, *, sets: int, ways: int):
    """block_addrs (T,) int32 -> hits (T,) bool. True-LRU, allocate-on-miss
    (writes allocate too — NVDLA's DBB read/write bursts both fill)."""
    _, hits = _scan_trace(cold_state(sets, ways),
                          jnp.asarray(block_addrs), sets=sets, ways=ways)
    return hits


def hit_rate(block_addrs, cfg: LLCConfig) -> float:
    hits = simulate_trace(jnp.asarray(block_addrs, jnp.int32),
                          sets=cfg.sets, ways=cfg.ways)
    return float(jnp.mean(hits.astype(jnp.float32)))


def sequential_burst_trace(n_bursts: int, burst_bytes: int,
                           block_bytes: int, base: int = 0) -> jnp.ndarray:
    """Byte-sequential stream of `burst_bytes` bursts -> block addresses
    (the NVDLA weight/ifmap streaming pattern)."""
    byte_addrs = base + jnp.arange(n_bursts) * burst_bytes
    return block_address(byte_addrs, block_bytes).astype(jnp.int32)


# --------------------------------------------------------------------------
# compressed segment engine
# --------------------------------------------------------------------------
def _first_access(blocks, base, stride, block_bytes):
    """Index (within the segment) of the first access landing in each of
    `blocks` (accesses are base + j*stride, j in [0, count))."""
    lo = blocks * block_bytes - base
    return jnp.where(lo <= 0, 0, (lo + stride - 1) // stride)


def _last_access(blocks, base, stride, count, block_bytes):
    """Index of the last segment access landing in each of `blocks`."""
    lo = blocks * block_bytes - base
    return jnp.minimum(count - 1, (lo + block_bytes - 1) // stride)


def _block_counts(blocks, base, stride, count, block_bytes):
    """Exact number of segment accesses landing in each block of `blocks`
    (accesses are base + j*stride for j in [0, count))."""
    return (_last_access(blocks, base, stride, count, block_bytes)
            - _first_access(blocks, base, stride, block_bytes)
            + 1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("sets", "ways", "m_pad", "collect"))
def _segment_rounds_grouped(state, b_firsts, n_blockss, bases, strides,
                            counts, block_bytes,
                            *, sets: int, ways: int, m_pad: int,
                            collect: bool = False):
    """Per-set round scan over a *group* of segments (one device program
    per group, no per-segment dispatch).  Within a segment, round k
    retires, for every set at once, that set's k-th arriving block, with
    all its intra-block burst repeats folded into one LRU update
    (touched way -> age 0, other ways += accesses).  Sets are
    independent under LRU, so this is bit-identical to the per-access
    scan while cutting serial depth from O(count) to
    O(segments * n_blocks / sets).  Padding segments have count == 0 and
    update nothing.

    Returns per-segment hit counts; with ``collect`` it also returns the
    per-(segment, round, set) miss bits, from which the caller
    reconstructs the exact missed-block runs the DRAM model consumes."""
    s_idx = jnp.arange(sets)

    def per_segment(carry, meta):
        b_first, n_blocks, base, stride, count = meta
        off = (s_idx - b_first) % sets   # ordinal of a set's first block

        def round_k(inner, k):
            tags, age, hits = inner
            i = off + k * sets           # block ordinal within segment
            valid = i < n_blocks
            blocks = b_first + i
            t = (blocks // sets).astype(jnp.int32)
            a = _block_counts(blocks, base, stride, count, block_bytes)
            a = jnp.where(valid, a, 0)
            match = tags == t[:, None]
            hit = jnp.any(match, axis=1)
            way = jnp.where(hit, jnp.argmax(match, axis=1),
                            jnp.argmax(age, axis=1))
            touched = jnp.arange(ways)[None, :] == way[:, None]
            upd = valid[:, None]
            tags = jnp.where(upd & touched, t[:, None], tags)
            age = jnp.where(upd,
                            jnp.where(touched, 0, age + a[:, None]), age)
            hits = hits + jnp.sum(jnp.where(valid, a - 1 + hit, 0),
                                  dtype=jnp.int32)
            miss = (valid & ~hit) if collect else None
            return (tags, age, hits), miss

        tags, age = carry
        (tags, age, hits), miss = jax.lax.scan(
            round_k, (tags, age, jnp.int32(0)), jnp.arange(m_pad))
        return (tags, age), (hits, miss)

    state, (hits, miss) = jax.lax.scan(
        per_segment, state,
        (b_firsts, n_blockss, bases, strides, counts))
    return state, hits, miss


class _TouchedBlocks:
    """Host-side conservative residency tracker: the union of block
    intervals any earlier segment touched.  A segment disjoint from
    every touched interval provably has no resident blocks, so its
    disjointness can be decided without a device sync (the price of
    conservatism: a revisit of a long-evicted range still takes the
    round-scan path — exact either way)."""

    def __init__(self):
        self._iv: list[tuple[int, int]] = []   # merged, sorted

    def overlaps(self, lo: int, hi: int) -> bool:
        return any(a <= hi and lo <= b for a, b in self._iv)

    def add(self, lo: int, hi: int) -> None:
        merged = [(lo, hi)]
        for a, b in self._iv:
            if a <= merged[0][1] + 1 and merged[0][0] <= b + 1:
                merged[0] = (min(a, merged[0][0]), max(b, merged[0][1]))
            else:
                merged.append((a, b))
        self._iv = sorted(merged)


@functools.partial(jax.jit, static_argnames=("sets", "ways"))
def _segment_closed_form(state, b_first, n_blocks, a_interior, a_last,
                         *, sets: int, ways: int):
    """O(1)-serial state update for a full-sweep disjoint segment.

    Preconditions (checked by the caller): every set receives >= ways
    arrivals (n_blocks >= ways * sets), no segment block is resident
    beforehand, and interior block access counts are uniform (stride
    divides block size).  Then every first touch misses, so victims
    cycle through the ways in prior-LRU order: arrival j of a set lands
    on way rho[(j-1) % ways] where rho orders ways by descending prior
    age (stable — matching argmax's first-index tie-break).  The final
    occupants are each set's last `ways` arrivals and their ages are the
    access counts of the arrivals after them.
    """
    tags, age = state
    s_idx = jnp.arange(sets)
    off = (s_idx - b_first) % sets
    m_s = (n_blocks - off + sets - 1) // sets        # arrivals per set
    rho = jnp.argsort(-age, axis=1, stable=True)     # (S, W) victim order
    q = jnp.arange(ways)[None, :]
    jstar = m_s[:, None] - ((m_s[:, None] - 1 - q) % ways)   # 1-indexed
    i_star = off[:, None] + (jstar - 1) * sets
    new_tag = ((b_first + i_star) // sets).astype(jnp.int32)
    # age of the way holding arrival j* = accesses of arrivals after it;
    # all interior blocks count a_interior, except the segment's very
    # last block (partial) — in its set's suffix unless it *is* j*.
    s_last = (b_first + n_blocks - 1) % sets
    in_suffix_last = (s_idx[:, None] == s_last) & (jstar < m_s[:, None])
    new_age = ((m_s[:, None] - jstar) * a_interior
               + jnp.where(in_suffix_last, a_last - a_interior, 0)
               ).astype(jnp.int32)
    # scatter rank-ordered results back to way positions
    tags = jnp.zeros_like(tags).at[s_idx[:, None], rho].set(new_tag)
    age = jnp.zeros_like(age).at[s_idx[:, None], rho].set(new_age)
    return (tags, age)


# --------------------------------------------------------------------------
# segment-lane engine: geometry as *traced* operands
# --------------------------------------------------------------------------
def _shift_down(x, r, n: int):
    """``y[i] = x[i + r]`` (False past the end of ``x``) for ``i < n``,
    with a traced ``0 <= r < 2**k`` where ``len(x) <= 2**k``.  A barrel
    shifter of static slices and selects: the direct form, a per-lane
    gather, crashed the TPU v5e compiler where it fused into the round
    loop's scatter."""
    bits = max(1, (x.shape[0] - 1).bit_length())
    x = jnp.pad(x, (0, n + (1 << bits) - 1 - x.shape[0]))
    for j in reversed(range(bits)):
        step = 1 << j
        keep = n + step - 1          # what the lower bits can still reach
        x = jnp.where((r >> j) & 1, x[step:step + keep], x[:keep])
    return x


def _shift_up(x, u, n: int):
    """``y[i] = x[i - u]`` for ``u <= i < n``, else False; ``x`` holds
    ``n`` entries and ``u >= 0`` is traced."""
    u = jnp.minimum(u, n)
    for j in range(n.bit_length()):
        step = 1 << j
        moved = (jnp.pad(x[:n - step], (step, 0)) if step < n
                 else jnp.zeros_like(x))
        x = jnp.where((u >> j) & 1, moved, x)
    return x


def _by_ordinal(miss, b_first, sets, width: int):
    """A round's per-set bits ``miss`` (False at and past ``sets``) by
    block ordinal: ``y[i] = miss[(b_first + i) % sets]`` for
    ``i < min(width, sets)``, else False."""
    r = b_first % sets
    head = _shift_down(miss, r, width)              # r + i < sets
    wrap = _shift_up(miss[:width], sets - r, width)  # r + i >= sets
    return (head | wrap) & (jnp.arange(width) < sets)


def segment_lane_scan(bases, strides, counts, r_needed, cold,
                      sets, ways, block_bytes, way_sels=None,
                      *, max_sets: int, max_ways: int, r_pad: int,
                      collect: bool = False, collect_width: int | None = None,
                      suffix: str = "full", return_state: bool = False):
    """One sweep lane's exact segment replay with *runtime* geometry.

    ``bases/strides/counts`` are (S,) int32 segment streams (count == 0
    entries are padding and update nothing); ``sets/ways/block_bytes``
    are traced scalars bounded by the static ``max_sets``/``max_ways``
    paddings, so ``jax.vmap`` over lanes turns a whole geometry grid
    into one compiled program (``repro.core.sweep.segment_lane_hit_counts``).
    ``r_needed``/``cold`` are host-side execution plans: the number of
    round-scan rounds this segment needs (an upper bound across the
    vmapped lanes — extra rounds are masked no-ops, missing rounds would
    be wrong) and whether the segment's byte range is provably disjoint
    from everything replayed before it.

    Per segment the update is the same exact decomposition the
    single-geometry engine uses, expressed uniformly so every lane runs
    the same program:

    * a per-set round scan retires the first min(n_blocks, ways*sets)
      blocks (one block per set per round, all intra-block burst repeats
      folded into one LRU touch) in ``r_needed`` dynamic rounds — zero
      for a ``cold`` segment, whose arrivals provably all miss;
    * the rest of the segment finishes with a closed-form suffix: after
      `ways` arrivals in every set the cache provably holds exactly
      those arrivals — whatever was resident before — so every suffix
      block misses and victims cycle through the ways oldest-first (for
      a ``cold`` segment the "suffix" is the whole segment, with any
      per-set arrival count).  The final occupants and their last-touch
      timestamps are written directly.

    LRU is tracked as a global last-touch timestamp (recency order, and
    so every victim choice including first-index tie-breaks, is
    identical to the per-set age counters of the reference simulator).
    State is laid out (ways, sets) — way-reductions run over the small
    leading axis with sets contiguous, which is what XLA:CPU vectorizes
    well.  Requires stride <= block_bytes for every (segment, lane)
    pair — the caller checks; DBB traces are 32 B-stride so every
    standard geometry qualifies.  Returns per-segment hit counts (S,)
    int32; hit counts are bit-identical to expanding the trace and
    running the exact per-access scan at that geometry.

    ``collect=True`` (static) additionally returns the round-scan miss
    bits by block ordinal, (S, r_pad, W) bool with ``W = collect_width``
    (static, default ``max_sets``): entry (j, k, i) is True iff the
    block at ordinal ``k*sets + i`` of segment j missed in the round
    scan; its set is ``(b_first + i) % sets``, and entries with
    ``i >= sets`` are False.  Round k retires ordinals ``k*sets`` up to
    ``min(n_pre, (k+1)*sets)``, so a width of the largest
    ``min(n_pre, sets)`` of any segment loses no bit; the caller rounds
    it up to a multiple of 128, the TPU's lane width (at most
    ``max_sets``: then the layout is the set-indexed one rotated by
    ``b_first``).  The bits are narrowed inside each round, so no
    (S, r_pad, max_sets) buffer is ever made.  Together with the
    analytically-known suffix (every block past the round-scanned prefix
    misses), the caller can reconstruct each segment's exact
    missed-block runs — the compressed currency of the DRAM row model —
    without per-access expansion
    (``repro.core.sweep.interference_lane_metrics_batch``).

    ``suffix`` (static) specializes the closed-form suffix from the
    host plan:

    * ``"full"`` — the general oldest-first rank insert, any suffix
      depth;
    * ``"one"`` — every (segment, lane) suffix leaves at most one block
      per set (n_blocks - n_pre <= sets): the insert is a plain
      oldest-way eviction, O(ways) per set instead of the O(ways^2)
      rank computation, which otherwise dominates the whole scan;
    * ``"none"`` — every segment retires entirely in the round scan
      (no cold segments and n_blocks <= ways*sets everywhere, so
      n_suf == 0): the suffix block is dropped from the program.

    ``way_sels`` (optional, (S,) int32) adds LLC **way-masking
    partitioning** (Intel CAT semantics, FireSim's LLC model knob): a
    per-segment bitmask of the ways the segment's master may *allocate*
    into on a miss.  Hits are unrestricted — a line is served from
    whichever way holds it, and the touch updates that way's recency —
    only victim selection is confined to the mask, so disjoint masks
    give each master a private partition of every set.  A zero mask
    means "unpartitioned" (the full-mask behavior, bit-exactly — the
    sentinel lets one vmapped batch mix masked and unmasked lanes).
    Masked segments (mask != 0) retire entirely in the round scan —
    the closed-form suffix assumes unrestricted LRU victim cycling —
    so the caller's plan must give them ``ceil(n_blocks / sets)``
    rounds and their ``cold`` flag is ignored.  Callers guarantee
    ``mask & ((1 << ways) - 1) != 0`` (an empty partition cannot
    allocate anywhere).

    ``return_state`` (static) additionally returns the final
    ``(tags, ts)`` state, (max_ways, max_sets) each — the partition
    invariant tests decode it to prove masked ways never hold the
    victim's lines.
    """
    s_idx = jnp.arange(max_sets, dtype=jnp.int32)
    q_idx = jnp.arange(max_ways, dtype=jnp.int32)
    width = max_sets if collect_width is None else collect_width
    set_mask = s_idx < sets
    way_mask = q_idx < ways
    imax = jnp.iinfo(jnp.int32).max
    bb = block_bytes

    masked = way_sels is not None

    def per_segment(carry, meta):
        tags, ts, counter = carry          # (max_ways, max_sets) x2, scalar
        if masked:
            base, stride, count, rounds, is_cold, wsel = meta
            # allocation mask: mask bits limited to real ways; the zero
            # sentinel means unpartitioned (alloc anywhere real)
            alloc = way_mask & ((wsel == 0) | (((wsel >> q_idx) & 1) != 0))
        else:
            base, stride, count, rounds, is_cold = meta
            wsel = jnp.int32(0)
            alloc = way_mask
        live = count > 0
        b_first = base // bb
        b_last = (base + (count - 1) * stride) // bb
        n_blocks = jnp.where(live, b_last - b_first + 1, 0)
        full = ways * sets
        n_pre = jnp.where(is_cold, 0, jnp.minimum(n_blocks, full))
        if masked:
            # a partitioned segment cannot use the suffix closed form
            # (victims cycle within its mask, not all ways): the whole
            # segment goes through the round scan
            n_pre = jnp.where(wsel != 0, n_blocks, n_pre)
        off = jnp.where(set_mask, (s_idx - b_first) % sets, 0)

        def round_k(k, inner):
            tags, ts, hits, miss_buf = inner
            i = off + jnp.int32(k) * sets  # block ordinal within segment
            v = set_mask & (i < n_pre) & live
            blocks = b_first + i
            t = (blocks // sets).astype(jnp.int32)
            j_lo = _first_access(blocks, base, stride, bb)
            j_hi = _last_access(blocks, base, stride, count, bb)
            a = (j_hi - j_lo + 1).astype(jnp.int32)
            # one fused reduction picks the touched way: a matching tag
            # wins outright (key -1, unique per set), else the oldest
            # real way (padded ways pinned to int32 max; the cumsum
            # first-min mask reproduces argmin's first-index tie-break
            # without a gather — XLA:CPU gathers cost ~100ns/element,
            # elementwise ops ~1ns)
            key = jnp.where(tags == t[None, :], -1,
                            jnp.where(alloc[:, None], ts, imax))
            kmin = jnp.min(key, axis=0)
            hit = kmin == -1
            is_min = key == kmin[None, :]
            first_min = (jnp.cumsum(is_min, axis=0) == 1) & is_min
            touched = first_min & v[None, :]
            tags = jnp.where(touched, t[None, :], tags)
            ts = jnp.where(touched,
                           (counter + j_hi[None, :] + 1).astype(jnp.int32),
                           ts)
            hits = hits + jnp.sum(jnp.where(v, a - 1 + hit, 0),
                                  dtype=jnp.int32)
            if collect:
                miss = _by_ordinal(v & ~hit, b_first, sets, width)
                miss_buf = miss_buf.at[k].set(miss)
            return (tags, ts, hits, miss_buf)

        miss_init = jnp.zeros((r_pad, width) if collect else (0, 0),
                              jnp.bool_)
        tags, ts, hits, miss_buf = jax.lax.fori_loop(
            0, jnp.minimum(rounds, r_pad), round_k,
            (tags, ts, jnp.int32(0), miss_init))

        if suffix == "none":
            counter = counter + jnp.where(live, count, 0)
            return (tags, ts, counter), (hits, miss_buf)

        # closed-form suffix: everything past the round-scanned prefix
        # (the whole segment when cold)
        sb_first = b_first + n_pre
        n_suf = jnp.maximum(n_blocks - n_pre, 0)
        has_suf = n_suf > 0
        off_suf = jnp.where(set_mask, (s_idx - sb_first) % sets, 0)
        victim_ts = jnp.where(way_mask[:, None], ts, imax)
        if suffix == "one":
            # at most one suffix block per set: it evicts the oldest
            # way (min ts, first-index tie-break via the same cumsum
            # first-min mask as the round scan)
            ins = set_mask & live & (off_suf < n_suf)
            vmin = jnp.min(victim_ts, axis=0)
            is_old = victim_ts == vmin[None, :]
            oldest = (jnp.cumsum(is_old, axis=0) == 1) & is_old
            blk1 = sb_first + off_suf
            t1 = (blk1 // sets).astype(jnp.int32)
            ts1 = counter + _last_access(blk1, base, stride, count, bb) + 1
            wr = oldest & ins[None, :]
            tags = jnp.where(wr, t1[None, :], tags)
            ts = jnp.where(wr, ts1[None, :].astype(jnp.int32), ts)
        else:
            m_s = jnp.where(off_suf < n_suf,
                            (n_suf - off_suf + sets - 1) // sets, 0)
            # each way's rank in oldest-first recency order (stable:
            # ties break on way index) via an O(ways^2) comparison
            # count — the scatter/argsort formulation this replaces
            # dominated the whole scan on CPU (batched scatters
            # serialize per element)
            older = ((victim_ts[None, :, :] < victim_ts[:, None, :])
                     | ((victim_ts[None, :, :] == victim_ts[:, None, :])
                        & (q_idx[None, :, None] < q_idx[:, None, None])))
            rank = jnp.sum(older, axis=1).astype(jnp.int32)
            jstar = m_s[None, :] - ((m_s[None, :] - 1 - rank) % ways)
            valid_q = (way_mask[:, None] & (jstar >= 1)
                       & set_mask[None, :] & live)
            blk = sb_first + off_suf[None, :] + (jstar - 1) * sets
            t_star = (blk // sets).astype(jnp.int32)
            ts_star = (counter
                       + _last_access(blk, base, stride, count, bb) + 1)
            tags = jnp.where(valid_q, t_star, tags)
            ts = jnp.where(valid_q, ts_star.astype(jnp.int32), ts)
        # every suffix access beyond a block's first touch hits
        j_split = jnp.where(has_suf,
                            _first_access(sb_first, base, stride, bb),
                            count)
        hits = hits + jnp.where(has_suf, (count - j_split) - n_suf, 0)
        counter = counter + jnp.where(live, count, 0)
        return (tags, ts, counter), (hits, miss_buf)

    init = (jnp.full((max_ways, max_sets), -1, jnp.int32),
            jnp.zeros((max_ways, max_sets), jnp.int32),
            jnp.int32(0))
    xs = [bases, strides, counts, r_needed,
          jnp.asarray(cold).astype(jnp.bool_)]
    if masked:
        xs.append(jnp.asarray(way_sels).astype(jnp.int32))
    (tags_f, ts_f, _), (per_seg_hits, miss_bits) = jax.lax.scan(
        per_segment, init, tuple(xs))
    out = (per_seg_hits,)
    if collect:
        out += (miss_bits,)
    if return_state:
        out += ((tags_f, ts_f),)
    return out if len(out) > 1 else out[0]


def record_lane_scan(bases, strides, counts, chunks, offsets, periods,
                     r_needed, sets, ways, block_bytes,
                     *, max_sets: int, max_ways: int, r_pad: int):
    """One sweep lane of *compacted records*, exact, with runtime geometry.

    A record is ``R`` repeats of the arbiter's pattern: in every repeat
    each member ``m`` issues its next ``chunks[m]`` accesses, members in
    order.  Member ``m`` is one dense stride run over the whole record,
    ``counts[m]`` accesses from ``bases[m]`` by ``strides[m]``, so its
    access ``j`` falls at the record-local time

        (j // chunks[m]) * periods + offsets[m] + j % chunks[m]

    (``periods`` = the sum of the chunks, ``offsets[m]`` = the chunks of
    the members before ``m``; the last repeat may be short).  All inputs
    are (S, P) int32 per record and member, except ``periods`` and
    ``r_needed``, (S,); members with ``counts == 0`` are padding, and a
    record whose member 0 alone is live is a plain segment.

    Per set, a record's block arrivals are the members' arrival
    sequences merged by the time of each block's first access.  The
    caller (``repro.core.sweep``) forms records only where that merge is
    exact for LRU: the members' block ranges are disjoint, and a block
    whose accesses straddle two repeats sees fewer than ``ways`` other
    arrivals in its set between its first and last access, so it stays
    resident and each block is one LRU touch stamped with its last
    access.  Then, as for one segment (``segment_lane_scan``):

    * the first ``min(ways, arrivals)`` arrivals of every set go through
      a round scan, one arrival per set per round, ``r_needed`` rounds
      (at most ``ways``); each round picks, per set, the member whose
      next arrival comes first;
    * after ``ways`` arrivals a set holds only blocks of the record, so
      every later arrival misses, and the set ends holding the ``ways``
      blocks of the record touched last: the closed-form suffix picks
      them from the members' last ``ways`` arrivals.

    A way's position never decides anything unmasked lanes can see
    (stamps are distinct, and never-filled ways are alike), so the
    suffix fills ways in stamp order.

    Returns the hits per record and member, (S, P) int32, and the
    round scan's hits: (S, r_pad, max_sets) codes (int8 where
    ``P * max_ways`` allows, else int16), where code
    ``m * max_ways + q + 1`` at (record, round, set ``s``) says member
    ``m``'s arrival number ``q`` in set ``s`` hit, that is the block at
    ordinal ``(s - bases[m] // block_bytes) % sets + q * sets`` of the
    member; 0 is a miss or no arrival.  Every block the codes do not
    name missed on its first access, and every later access to a block
    hits — from which ``repro.core.sweep`` rebuilds the exact miss runs
    in the order of the uncompacted trace.
    """
    n_mem = bases.shape[-1]
    code_type = (jnp.int8 if n_mem * max_ways < np.iinfo(np.int8).max
                 else jnp.int16)
    s_idx = jnp.arange(max_sets, dtype=jnp.int32)
    m_idx = jnp.arange(n_mem, dtype=jnp.int32)[:, None]
    set_mask = (s_idx < sets)[None, :]
    way_idx = jnp.arange(max_ways, dtype=jnp.int32)
    way_mask = way_idx < ways
    imax = jnp.iinfo(jnp.int32).max
    bb = block_bytes

    def col(x):                            # (P,) -> (P, 1), against sets
        return x[:, None]

    def per_record(carry, meta):
        tags, ts, counter = carry          # (max_ways, max_sets) x2, scalar
        base, stride, count, chunk, offset, period, rounds = meta
        live = count > 0
        chunk = jnp.maximum(chunk, 1)
        b_first = base // bb
        b_last = (base + (count - 1) * stride) // bb
        n_blocks = jnp.where(live, b_last - b_first + 1, 0)
        off = jnp.where(set_mask, (s_idx[None, :] - col(b_first)) % sets, 0)
        # each member's arrivals in each set
        per = jnp.where(set_mask & (off < col(n_blocks)),
                        (col(n_blocks) - off + sets - 1) // sets, 0)
        total = jnp.sum(per, axis=0)

        def vtime(j):
            return (j // col(chunk)) * period + col(offset) + j % col(chunk)

        def block_of(q):                   # member ordinal q's block
            return col(b_first) + off + q * sets

        def round_k(k, inner):
            tags, ts, hits, ptr, code = inner
            blk = block_of(ptr)
            valid = set_mask & (ptr < per)
            t_lo = jnp.where(valid, vtime(_first_access(
                blk, col(base), col(stride), bb)), imax)
            first = jnp.min(t_lo, axis=0)
            arrive = first < imax
            pick = valid & (t_lo == first[None, :])   # one member per set
            t = jnp.sum(jnp.where(pick, blk // sets, 0),
                        axis=0).astype(jnp.int32)
            t_hi = jnp.sum(jnp.where(pick, vtime(_last_access(
                blk, col(base), col(stride), col(count), bb)), 0), axis=0,
                dtype=jnp.int32)
            key = jnp.where(tags == t[None, :], -1,
                            jnp.where(way_mask[:, None], ts, imax))
            kmin = jnp.min(key, axis=0)
            hit = (kmin == -1) & arrive
            is_min = key == kmin[None, :]
            touched = (jnp.cumsum(is_min, axis=0) == 1) & is_min & arrive
            tags = jnp.where(touched, t[None, :], tags)
            ts = jnp.where(touched, (counter + t_hi + 1)[None, :], ts)
            scored = pick & hit[None, :]
            hits = hits + jnp.sum(scored, axis=1, dtype=jnp.int32)
            mark = jnp.sum(jnp.where(scored, m_idx * max_ways + ptr + 1, 0),
                           axis=0)
            code = code.at[k].set(mark.astype(code_type))
            return tags, ts, hits, ptr + pick, code

        tags, ts, hits, _, code = jax.lax.fori_loop(
            0, jnp.minimum(rounds, r_pad), round_k,
            (tags, ts, jnp.zeros(n_mem, jnp.int32),
             jnp.zeros((n_mem, max_sets), jnp.int32),
             jnp.zeros((r_pad, max_sets), code_type)))

        # closed-form suffix: a set past `ways` arrivals ends holding the
        # record's `ways` blocks touched last, each member's latest first
        full = set_mask[0] & (total > ways)

        def newest(w, inner):
            tags, ts, taken = inner
            q = per - 1 - taken
            valid = set_mask & (q >= 0)
            blk = block_of(q)
            t_hi = jnp.where(valid, vtime(_last_access(
                blk, col(base), col(stride), col(count), bb)), -1)
            last = jnp.max(t_hi, axis=0)
            pick = valid & (t_hi == last[None, :])
            t = jnp.sum(jnp.where(pick, blk // sets, 0), axis=0)
            write = (way_idx == w)[:, None] & full[None, :]
            tags = jnp.where(write, t.astype(jnp.int32)[None, :], tags)
            ts = jnp.where(write, (counter + last + 1)[None, :], ts)
            return tags, ts, taken + pick

        tags, ts, _ = jax.lax.fori_loop(
            0, ways, newest,
            (tags, ts, jnp.zeros((n_mem, max_sets), jnp.int32)))
        # every access after a block's first is a hit
        hits = hits + jnp.where(live, count - n_blocks, 0)
        repeats = jnp.max(jnp.where(live, (count + chunk - 1) // chunk, 0))
        return (tags, ts, counter + repeats * period), (hits, code)

    init = (jnp.full((max_ways, max_sets), -1, jnp.int32),
            jnp.zeros((max_ways, max_sets), jnp.int32),
            jnp.int32(0))
    _, (hits, codes) = jax.lax.scan(
        per_record, init, (bases, strides, counts, chunks, offsets,
                           periods, r_needed))
    return hits, codes


@dataclasses.dataclass
class SegmentSimResult:
    hits: int
    accesses: int
    state: tuple                 # final (tags, age)
    closed_form_segments: int    # retired with the O(1) analytic update
    round_scanned_segments: int  # retired with the per-set round scan
    expanded_segments: int       # fell back to the exact per-access scan
    per_segment_hits: np.ndarray | None = None   # (n_segments,) int64
    miss_runs: list | None = None  # [(first_block, n_blocks, seg_idx)]

    @property
    def hit_rate(self) -> float:
        return self.hits / max(1, self.accesses)


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _append_block_runs(runs: list, blocks: np.ndarray, idx: int) -> None:
    """Compress a sorted array of distinct block indices into maximal
    consecutive (first_block, n_blocks, segment_idx) runs."""
    if blocks.size == 0:
        return
    cut = np.nonzero(np.diff(blocks) != 1)[0]
    starts = np.concatenate([[0], cut + 1])
    ends = np.concatenate([cut, [blocks.size - 1]])
    for a, b in zip(starts, ends):
        runs.append((int(blocks[a]), int(b - a + 1), idx))


def simulate_segments(segments, cfg: LLCConfig, state=None, *,
                      per_segment: bool = False,
                      collect_miss_runs: bool = False) -> SegmentSimResult:
    """Replay a compressed DBB trace (iterable of objects/tuples with
    ``base, stride, count`` in bytes/bursts, stride > 0) through the
    LLC, optionally continuing from a prior (tags, age) ``state``.

    Dispatches each segment to the cheapest exact path: closed form when
    it fully sweeps a provably non-resident region, per-set round scan
    otherwise, exact per-access scan only when compression cannot
    express the segment (stride > block size).  Consecutive round-scan
    segments with the same round budget are fused into one device
    program, and hit counters stay on device until the end, so the hot
    loop performs no per-segment synchronization.  Hit counts and final
    state are bit-identical to expanding the segments and running
    ``simulate_trace`` on the concatenation.

    ``per_segment`` additionally attributes hits to each input segment
    (``result.per_segment_hits``, aligned with the input order — the
    sim-driven accelerator model sums these by stream).
    ``collect_miss_runs`` reconstructs the exact LLC-miss stream as
    maximal runs of consecutive missed blocks in access order
    (``result.miss_runs``) — the compressed currency of the closed-form
    DRAM row model in ``repro.core.dram.segment_row_hits``.
    """
    sets, ways, bb = cfg.sets, cfg.ways, cfg.block_bytes
    collect = collect_miss_runs
    touched = _TouchedBlocks()
    if state is None:
        state = cold_state(sets, ways)
    else:
        # arbitrary warm state: anything may be resident, so no segment
        # is provably disjoint (long ones still split fast — the
        # prefix/suffix proof is dynamic and needs no tracker)
        touched.add(-(1 << 62), 1 << 62)
    accesses = 0
    n_cf = n_rs = n_ex = 0
    n_input = 0
    # replay log, resolved to host values once at the end (device arrays
    # are only synced after the whole trace is dispatched):
    #   ("group", idxs, metas, hits_dev, miss_dev)
    #   ("cf",    idx, first_block, n_blocks, hits_int)
    #   ("ex",    idx, hit_bits_dev, blocks_dev)
    order_log: list[tuple] = []
    pending: list[tuple] = []  # (idx, (b_first, n_blocks, base, stride, cnt))
    pending_m = 0

    def flush():
        nonlocal state, pending, pending_m
        if not pending:
            return
        idxs = [i for i, _ in pending]
        metas = [m for _, m in pending]
        k_pad = _next_pow2(len(metas))
        metas_p = metas + [(0, 0, 0, 1, 0)] * (k_pad - len(metas))
        cols = list(np.asarray(metas_p, np.int32).T)
        state, h, miss = _segment_rounds_grouped(
            state, *cols, bb, sets=sets, ways=ways, m_pad=pending_m,
            collect=collect)
        order_log.append(("group", idxs, metas, h, miss))
        pending, pending_m = [], 0

    from repro.core.traces import segment_tuple

    for idx, seg in enumerate(segments):
        n_input = idx + 1
        base, stride, count = segment_tuple(seg)
        if count <= 0:
            continue
        if stride <= 0:
            raise ValueError(
                f"segment stride must be positive, got {stride} "
                "(a repeated single address is not a compressible "
                "sequential burst stream)")
        accesses += count
        if stride > bb:
            # blocks are non-contiguous: expand and scan exactly
            flush()
            addrs = (base + jnp.arange(count) * stride) // bb
            blocks_dev = addrs.astype(jnp.int32)
            state, h = _scan_trace(state, blocks_dev,
                                   sets=sets, ways=ways)
            order_log.append(("ex", idx, h, blocks_dev))
            touched.add(base // bb, (base + (count - 1) * stride) // bb)
            n_ex += 1
            continue
        b_first = base // bb
        b_last = (base + (count - 1) * stride) // bb
        n_blocks = b_last - b_first + 1
        uniform = bb % stride == 0
        disjoint = not touched.overlaps(b_first, b_last)
        if uniform and not disjoint and n_blocks >= 2 * (ways + 1) * sets:
            # long warm segment: once every set has seen >= ways arrivals
            # the cache holds exactly those arrivals (LRU always evicts a
            # pre-segment resident before any arrival), so everything
            # past a (ways+1)*sets-block prefix is provably non-resident
            # no matter what was cached before.  Round-scan the prefix,
            # closed-form the suffix.
            split_block = b_first + (ways + 1) * sets
            j_split = -(-(split_block * bb - base) // stride)
            m = _next_pow2(ways + 1)
            if pending and m != pending_m:
                flush()
            pending.append((idx, (b_first, split_block - b_first, base,
                                  stride, j_split)))
            pending_m = m
            flush()
            n_rs += 1
            suf_base = base + j_split * stride
            suf_count = count - j_split
            n_blocks_suf = b_last - split_block + 1
            lo = b_last * bb - suf_base
            a_last = suf_count - (0 if lo <= 0 else -(-lo // stride))
            state = _segment_closed_form(
                state, split_block, n_blocks_suf, bb // stride, a_last,
                sets=sets, ways=ways)
            order_log.append(("cf", idx, split_block, n_blocks_suf,
                              suf_count - n_blocks_suf))
            n_cf += 1
            touched.add(b_first, b_last)
            continue
        if n_blocks >= ways * sets and uniform and disjoint:
            flush()
            a_int = bb // stride
            lo = b_last * bb - base
            j_lo = 0 if lo <= 0 else -(-lo // stride)
            a_last = count - j_lo
            state = _segment_closed_form(
                state, b_first, n_blocks, a_int, a_last,
                sets=sets, ways=ways)
            order_log.append(("cf", idx, b_first, n_blocks,
                              count - n_blocks))
            n_cf += 1
        else:
            m = _next_pow2(-(-n_blocks // sets))
            if pending and m != pending_m:
                flush()
            pending.append((idx, (b_first, n_blocks, base, stride, count)))
            pending_m = m
            n_rs += 1
        touched.add(b_first, b_last)
    flush()

    # resolve the log: total hits, optional per-segment attribution and
    # miss-run reconstruction — device arrays sync here, once
    hits = 0
    per_seg = np.zeros(n_input, np.int64) if per_segment else None
    miss_runs: list | None = [] if collect else None
    for entry in order_log:
        if entry[0] == "group":
            _, idxs, metas, h_dev, miss_dev = entry
            h = np.asarray(h_dev)
            hits += int(h[:len(idxs)].sum())
            if per_seg is not None:
                for j, i in enumerate(idxs):
                    per_seg[i] += int(h[j])
            if collect:
                mb = np.asarray(miss_dev)        # (k_pad, m_pad, sets)
                for j, (b_first, n_blocks, _b, _s, _c) in enumerate(metas):
                    k_idx, s_np = np.nonzero(mb[j])
                    if k_idx.size == 0:
                        continue
                    off = (s_np - b_first) % sets
                    blocks = b_first + np.sort(off + k_idx * sets)
                    _append_block_runs(miss_runs, blocks, idxs[j])
        elif entry[0] == "cf":
            _, i, first_block, n_blocks, h_int = entry
            hits += h_int
            if per_seg is not None:
                per_seg[i] += h_int
            if collect:
                miss_runs.append((first_block, n_blocks, i))
        else:                                    # "ex"
            _, i, h_dev, blocks_dev = entry
            h = np.asarray(h_dev)
            hits += int(h.sum())
            if per_seg is not None:
                per_seg[i] += int(h.sum())
            if collect:
                _append_block_runs(miss_runs,
                                   np.asarray(blocks_dev)[~h], i)
    return SegmentSimResult(hits=hits, accesses=accesses, state=state,
                            closed_form_segments=n_cf,
                            round_scanned_segments=n_rs,
                            expanded_segments=n_ex,
                            per_segment_hits=per_seg,
                            miss_runs=miss_runs)


def hit_rate_segments(segments, cfg: LLCConfig) -> float:
    """LLC hit rate of a compressed trace (exact, never expands unless a
    segment's stride exceeds the block size)."""
    return simulate_segments(segments, cfg).hit_rate

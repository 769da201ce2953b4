"""Set-associative LLC simulator — exact, vectorized, runtime-configurable.

The FireSim LLC model is runtime-configurable in sets/ways/block size
without an FPGA rebuild; this is the same knob set, as pure JAX.  Every
path is true LRU, allocate-on-miss, and all three give bit-identical
hit counts (tests/test_traces.py, tests/test_sweep.py):

* **exact per-access scan** (``simulate_trace``): one ``lax.scan`` step
  per access on (tags, age) state of shape (sets, ways) — the reference
  semantics and the test oracle.  It alone replays a trace of any
  stride;
* **segment lane** (``segment_lane_scan``): a DBB stream
  run-length-compressed into ``(base, stride, count)`` segments
  (``repro.core.traces``) replays one segment per step, a per-set round
  scan (one block per set per round, intra-block repeats folded into
  one LRU touch) plus a closed-form suffix for the blocks past
  ``ways`` arrivals per set, so serial depth is O(segments * ways), not
  O(accesses);
* **record lane** (``record_lane_scan``): an interference lane
  compacted into records of the arbiter's repeating pattern replays
  one record per step.

The lane kernels take the geometry as traced scalars on state padded to
the largest geometry, so ``repro.core.sweep`` ``jax.vmap``-s them over
a whole grid of (sets, ways, block_bytes) lanes as one compiled device
program; a single lane is a batch of one.  They need every stride in
``(0, block_bytes]`` (every DBB trace is 32 B-stride).

Used two ways: exactly, on the NVDLA DBB stream (the per-stream hit
rates feed the accelerator timing model); and as the reference that
validates the closed-form stream-locality model in
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
# lane engines: compressed segments, geometry as *traced* operands
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

    Per segment the update is an exact decomposition, expressed
    uniformly so every lane runs the same program:

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
                     r_needed, sets, ways, block_bytes, masks=None,
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

    ``masks`` (optional, (S, P) int32) adds LLC way partitioning with
    ``segment_lane_scan``'s semantics: member ``m`` allocates only into
    the ways of ``masks[m]`` (zero, the sentinel, allocates anywhere)
    and hits in any way.  Members with the same mask are one class; the
    caller (``repro.core.sweep``) gives masks only where the classes'
    ways are disjoint and no block is touched by two classes, so each
    set is one LRU stack per class over the class's ways, and the
    record's exactness holds per class, against the class's way count
    ``k``.  The round scan serves each class its first ``min(k,
    arrivals)`` arrivals in a set, and the suffix fills each full
    class's ways with its newest blocks.  Without ``masks`` the program
    is the unpartitioned one.
    """
    masked = masks is not None
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
        base, stride, count, chunk, offset, period, rounds = meta[:7]
        if masked:
            # each member's allocation ways, (P, max_ways); one class per
            # mask, with its way count
            mask = meta[7][:, None]
            alloc = way_mask[None, :] & (
                (mask == 0) | (((mask >> way_idx[None, :]) & 1) != 0))
            same = jnp.all(alloc[:, None, :] == alloc[None, :, :], axis=-1)
            k_ways = jnp.sum(alloc, axis=1, dtype=jnp.int32)[:, None]

            def of_class(x):               # (P, sets): summed over the class
                return jnp.sum(jnp.where(same[:, :, None], x[None], 0),
                               axis=1)
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
            if masked:                     # a class's first k arrivals only
                valid = valid & (of_class(ptr) < k_ways)
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
            match = tags == t[None, :]
            victims = way_mask[:, None]
            if masked:                     # the picked member's ways
                victims = jnp.any(pick[:, None, :] & alloc[:, :, None],
                                  axis=0)
            key = jnp.where(match, -1, jnp.where(victims, ts, imax))
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
        # (partitioned: per class, in the class's ways)
        full = set_mask[0] & (total > ways)
        if masked:
            full_m = set_mask & (of_class(per) > k_ways)

        def newest(w, inner):
            tags, ts, taken = inner
            q = per - 1 - taken
            valid = set_mask & (q >= 0)
            filled = full
            if masked:                     # way w's class
                mine = jnp.any(alloc & (way_idx == w)[None, :], axis=1)
                valid = valid & mine[:, None]
                filled = jnp.any(mine[:, None] & full_m, axis=0)
            blk = block_of(q)
            t_hi = jnp.where(valid, vtime(_last_access(
                blk, col(base), col(stride), col(count), bb)), -1)
            last = jnp.max(t_hi, axis=0)
            pick = valid & (t_hi == last[None, :])
            t = jnp.sum(jnp.where(pick, blk // sets, 0), axis=0)
            write = (way_idx == w)[:, None] & filled[None, :]
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
    xs = (bases, strides, counts, chunks, offsets, periods, r_needed)
    if masked:
        xs += (masks,)
    _, (hits, codes) = jax.lax.scan(per_record, init, xs)
    return hits, codes

"""The record path's device reduction (``sweep._record_rows``): each
compacted lane reduced to its missed blocks and DRAM row hits on the
device gives every ``LaneMetrics`` field bit for bit as the host oracle
gives it from the same hit codes (``sweep._record_miss_runs`` then
``sweep._lane_metrics_from_runs``): on ``test_compaction``'s lanes, on
random lanes, and sharded over a two-device mesh."""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap
from unittest import mock

import numpy as np
import pytest

from repro.core import sweep, traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import MixConfig
from repro.utils import tracing

FRAME = traces.network_trace(max_ops=2)
DRAM = DRAMConfig()
WIDE = LLCConfig(size_bytes=32768, ways=8, block_bytes=64)
NARROW = LLCConfig(size_bytes=16384, ways=4, block_bytes=64)
LANES = ([(WIDE, MixConfig(k, wss)) for wss in ("l1", "llc", "dram")
          for k in range(5)] + [(NARROW, MixConfig(4, "dram"))])


def _records(segs, llc: LLCConfig, mix: MixConfig) -> sweep.LaneRecords:
    b, s, c, nv = sweep.corunner_meta(segs, llc=llc, mix=mix)
    return sweep.compact_lane(b, s, c, nv, llc,
                              1 + (0 if mix.wss == "l1" else mix.corunners))


def _device_and_oracle(recs, llcs, drams) -> tuple[list, list]:
    """The lanes as one record program, reduced on the device; and the
    host oracle's metrics from the hit codes that program scanned."""
    real, got = sweep._record_engine, {}

    def engine(*static):
        program = real(*static)

        def call(*args):
            got["out"], got["max_ways"] = program(*args), static[1]
            return got["out"]
        return call

    before = tracing.counters()
    with mock.patch.object(sweep, "_record_engine", engine):
        device = sweep._record_program(recs, llcs)(drams, 20, None)
    after = tracing.counters()
    assert (after.get(tracing.DEVICE_REDUCED_LANES, 0)
            - before.get(tracing.DEVICE_REDUCED_LANES, 0)
            == len(recs))
    hits, codes = (np.asarray(a) for a in got["out"])
    oracle = []
    for row, (r, llc, dram) in enumerate(zip(recs, llcs, drams)):
        k, p = r.counts.shape
        # the program keeps the bucket's widest record's members only
        h = np.zeros((k, p), np.int64)
        h[:, :hits.shape[2]] = hits[row, :k, :p]
        runs = sweep._record_miss_runs(r, llc, codes[row, :k],
                                       got["max_ways"])
        oracle.append(sweep._lane_metrics_checked(
            runs, n_segments=int(r.raw.sum()), accesses=int(r.counts.sum()),
            hits=int(h.sum()), bb=llc.block_bytes, nv=r.nv.reshape(-1),
            dram=dram, t_llc_hit=20, nv_acc=int(r.counts[r.nv].sum()),
            nv_hits=int(h[r.nv].sum())))
    return device, oracle


@pytest.fixture(scope="module")
def compacted():
    recs = [_records(FRAME, llc, m) for llc, m in LANES]
    return _device_and_oracle(recs, [llc for llc, _ in LANES],
                              [DRAM] * len(LANES))


@pytest.mark.parametrize("i", range(len(LANES)), ids=[
    f"{'ways4-' if llc is NARROW else ''}{m.wss}-x{m.corunners}"
    for llc, m in LANES])
def test_device_reduction_matches_the_host_oracle(compacted, i):
    device, oracle = compacted
    assert dataclasses.asdict(device[i]) == dataclasses.asdict(oracle[i])


def _random_lane(draw):
    from hypothesis import strategies as st

    # the co-runners' 64 B lines fit a block (the lane engine's support)
    bb = draw(st.sampled_from([64, 128]))
    sets = draw(st.sampled_from([1, 2, 4, 16, 64]))
    # 64 ways beside two co-runners overflow int8 hit codes
    ways = draw(st.sampled_from([1, 2, 4, 8, 64]))
    llc = LLCConfig(size_bytes=sets * ways * bb, ways=ways, block_bytes=bb)
    dram = DRAMConfig(banks=draw(st.sampled_from([1, 2, 4, 32])),
                      row_bytes=bb * draw(st.sampled_from([1, 2, 4, 32])))
    segs = []
    for i in range(draw(st.integers(1, 6))):
        # few regions, so later segments re-read blocks still cached:
        # LLC hits that split a chunk's blocks, inside one row or all of it
        base = (draw(st.integers(0, 2)) << 16) + draw(st.integers(0, 4096))
        segs.append(traces.Segment(base, draw(st.sampled_from([16, 32, bb])),
                                   draw(st.integers(1, 700)), f"s{i}"))
    mix = MixConfig(draw(st.integers(0, 3)),
                    draw(st.sampled_from(["llc", "dram"])))
    return segs, llc, dram, mix


def test_device_reduction_matches_the_oracle_on_random_lanes():
    """Hypothesis: random traces (runs starting mid-row and mid-block,
    re-read regions, solo lanes whose plain records sweep more rows
    than there are banks, int16 codes) beside random co-runners."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=14, deadline=None, derandomize=True)
    @given(st.data())
    def prop(data):
        segs, llc, dram, mix = _random_lane(data.draw)
        lanes = [mix, MixConfig(0, "l1")]
        recs = [_records(segs, llc, m) for m in lanes]
        device, oracle = _device_and_oracle(recs, [llc] * 2, [dram] * 2)
        assert ([dataclasses.asdict(m) for m in device]
                == [dataclasses.asdict(m) for m in oracle])

    prop()


def _reduced(run) -> tuple:
    """What ``run()`` returns, and the lanes it reduced on the device."""
    before = tracing.counters().get(tracing.DEVICE_REDUCED_LANES, 0)
    out = run()
    return out, tracing.counters()[tracing.DEVICE_REDUCED_LANES] - before


def test_mixed_set_counts_take_the_host_oracle():
    """A bucket of two set counts (a 32 KiB and a 16 KiB LLC of 8 ways)
    has no one ordinal layout for its hit codes: it runs as one record
    program per set count, each reduced on the device, and each lane
    gives what the host oracle gives it alone."""
    small = LLCConfig(size_bytes=16384, ways=8, block_bytes=64)
    lanes = [(WIDE, MixConfig(2, "dram")), (small, MixConfig(2, "dram"))]
    assert len(sweep.lane_buckets([llc for llc, _ in lanes])) == 1
    with pytest.raises(ValueError, match="one set count"):
        sweep._record_program([_records(FRAME, llc, m) for llc, m in lanes],
                              [llc for llc, _ in lanes])(
            [DRAM] * 2, 20, None)
    got, reduced = _reduced(lambda: sweep.interference_lane_metrics_batch(
        FRAME, llcs=[llc for llc, _ in lanes], drams=[DRAM] * 2,
        mixes=[m for _, m in lanes]))
    assert reduced == 2
    for m, (llc, mix) in zip(got, lanes):
        _, oracle = _device_and_oracle([_records(FRAME, llc, mix)], [llc],
                                       [DRAM])
        assert dataclasses.asdict(m) == dataclasses.asdict(oracle[0])


def test_more_lanes_than_the_key_held_reduce_on_the_device():
    """Twenty frame lanes (its first op) in one bucket, over 2048 banks:
    the sort key holds one lane's visits, so the lane count does not
    bound it (a key over twenty lanes would pass int32), and every lane
    reduces on the device as the host oracle decodes it."""
    frame = traces.network_trace(max_ops=1)
    dram = DRAMConfig(banks=2048)
    mixes = [MixConfig(k % 5, "dram") for k in range(20)]
    got, reduced = _reduced(lambda: sweep.interference_lane_metrics_batch(
        frame, llcs=[WIDE] * 20, drams=[dram] * 20, mixes=mixes))
    assert reduced == 20
    _, oracle = _device_and_oracle([_records(frame, WIDE, m)
                                    for m in mixes[:5]], [WIDE] * 5,
                                   [dram] * 5)
    assert [dataclasses.asdict(m) for m in got] == [
        dataclasses.asdict(m) for m in oracle] * 4


def _moved(by: int) -> list:
    return traces.network_trace(max_ops=1, regions=tuple(
        r + by for r in traces.REGIONS))


@pytest.mark.parametrize("change", ["moved-map", "fewer-records"])
def test_slightly_different_campaigns_share_one_program(change):
    """The frame's first op beside 0, 2 and 4 co-runners, then a
    slightly different campaign: its address map moved by 640 B (the
    same records, a few more row visits), or its last segment 3200
    bursts shorter (273 records at two co-runners, not 279).  Both
    reduce with the one ``_rows_engine`` entry, since every size that
    keys it is rounded up; the moved map compiles nothing new.  Fewer
    records change the codes' record axis, which follows the record
    program's own shapes."""
    frame = _moved(0)
    if change == "moved-map":
        other = _moved(640)
    else:
        other = frame[:-1] + [dataclasses.replace(
            frame[-1], count=frame[-1].count - 3200)]
    mixes = [MixConfig(k, "dram") for k in (0, 2, 4)]
    records = [_records(segs, WIDE, mixes[1]).raw.shape[0]
               for segs in (frame, other)]
    assert (records[0] == records[1]) == (change == "moved-map")
    real, programs, compiled = sweep._rows_engine, [], []

    def engine(*static):
        programs.append(real(*static))
        return programs[-1]

    with mock.patch.object(sweep, "_rows_engine", engine):
        for segs in (frame, other):
            sweep.interference_lane_metrics_batch(
                segs, llcs=[WIDE] * 3, drams=[DRAM] * 3, mixes=mixes)
            compiled.append(programs[-1]._cache_size())
    assert programs[0] is programs[1]
    if change == "moved-map":
        assert compiled[1] == compiled[0]


@pytest.mark.parametrize("chunk_bursts", [3, 6])
def test_part_block_chunks_stay_uncompacted(chunk_bursts):
    """Chunks of 3 bursts of 32 B end mid-block, so no run of them
    folds into records; chunks of 6 are three whole blocks and fold.
    Either way the batch gives what the uncompacted lanes give."""
    frame = traces.network_trace(max_ops=1)
    mixes = [MixConfig(0, "l1"), MixConfig(2, "dram")]
    b, s, c, nv = sweep.corunner_meta(frame, llc=WIDE, mix=mixes[1],
                                      chunk_bursts=chunk_bursts)
    r = sweep.compact_lane(b, s, c, nv, WIDE, 3)
    multi = (r.counts > 0).sum(axis=1) > 1
    assert multi.any() == (chunk_bursts == 6)
    assert (r.chunks[multi] * r.strides[multi] % 64 == 0).all()

    def batch():
        return sweep.interference_lane_metrics_batch(
            frame, llcs=[WIDE] * 2, drams=[DRAM] * 2, mixes=mixes,
            chunk_bursts=chunk_bursts)

    got, reduced = _reduced(batch)
    assert reduced == 2 * (chunk_bursts == 6)
    with mock.patch.object(sweep, "_records_that_pay", lambda recs: None):
        assert batch() == got


MESH_CHILD = textwrap.dedent("""
    import dataclasses, jax
    from repro.core import sweep, traces
    from repro.core.cache import LLCConfig
    from repro.core.dram import DRAMConfig
    from repro.launch.mesh import make_sweep_mesh
    from repro.utils import tracing

    assert len(jax.devices()) == 2
    frame = traces.network_trace(max_ops=1)
    mixes = [sweep.MixConfig(k, "dram") for k in (0, 2, 4)]
    kw = dict(llcs=[LLCConfig(size_bytes=32768, ways=8, block_bytes=64)] * 3,
              drams=[DRAMConfig()] * 3, mixes=mixes)
    one = sweep.interference_lane_metrics_batch(frame, **kw)
    before = tracing.counters()[tracing.DEVICE_REDUCED_LANES]
    two = sweep.interference_lane_metrics_batch(
        frame, mesh=make_sweep_mesh(jax.devices()), **kw)
    assert tracing.counters()[tracing.DEVICE_REDUCED_LANES] - before == 3
    assert [dataclasses.asdict(m) for m in one] == [
        dataclasses.asdict(m) for m in two]
    print("identical")
""")


def test_two_device_mesh_matches_one_device():
    """Three frame lanes sharded over two virtual CPU devices (one
    padding lane) reduce to the counts one device gives."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", MESH_CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().endswith("identical")

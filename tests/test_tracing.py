"""Spans and counters of the host sweep layer (``repro.utils.tracing``):
where the spans land in a profile, what the counters count, and that
neither moves a simulated statistic."""
from __future__ import annotations

import glob
import hashlib
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro.campaign import example_spec, run_campaign
from repro.core import sweep, traces
from repro.core.runtime import compile_network
from repro.core.soc import llc_config_for
from repro.utils import tracing

FIG5_GRID = [(s, b) for s in (0.5, 64, 1024, 4096) for b in (32, 64, 128)]
# sha256 of the manifest of example_spec(points=4, window_bursts=256),
# written by the simulator before it had spans or counters
UNTRACED_MANIFEST = ("611fe65bf8f30bda381c29416531d341"
                     "d88a8ffeddfd8e837af0ddaac5984c36")


def _delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def _program_spans(trace_dir: str) -> list[tuple]:
    """Every ``repro.*`` host event of a profile as (thread, name,
    start_ns, end_ns), in start order."""
    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                out += [(line.name, ev.name, int(ev.start_ns),
                         int(ev.end_ns)) for ev in line.events
                        if ev.name.startswith("repro.")]
    return sorted(out, key=lambda r: (r[2], -r[3]))


def _within(inner, outer) -> bool:
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_counters_add_and_snapshot():
    before = tracing.counters()
    tracing.count("test.units", 3)
    tracing.count("test.units", np.int64(4))
    snap = tracing.counters()
    assert snap["test.units"] - before.get("test.units", 0) == 7
    snap["test.units"] = -1                 # a copy: the counter is kept
    assert tracing.counters()["test.units"] >= 7


def test_span_is_a_profiler_annotation():
    with tracing.span(tracing.LANE_PLAN) as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
    assert set(tracing.LEAVES).isdisjoint({tracing.CAMPAIGN,
                                           tracing.LANE_BATCH})
    assert all(n.startswith("repro.") for n in
               tracing.LEAVES + (tracing.CAMPAIGN, tracing.LANE_BATCH))


def test_campaign_spans_nest_in_table_order(tmp_path):
    spec = example_spec(points=4, window_bursts=256)
    run_campaign(spec, str(tmp_path / "warm"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    run_campaign(spec, str(tmp_path / "run"))
    jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path / "trace"))
    (run,) = [s for s in spans if s[1] == tracing.CAMPAIGN]
    assert {s[1] for s in spans} == {tracing.CAMPAIGN, tracing.LANE_BATCH,
                                     *tracing.LEAVES}
    assert all(_within(s, run) for s in spans)
    # leaves hold no program span
    for leaf in (s for s in spans if s[1] in tracing.LEAVES):
        assert not any(o is not leaf and _within(o, leaf) for o in spans)
    (batch,) = [s for s in spans if s[1] == tracing.LANE_BATCH]
    inside = [s[1] for s in spans if _within(s, batch) and s is not batch]
    lanes = len(spec.expand())
    assert inside == ([tracing.LANE_PLAN, tracing.DISPATCH, tracing.FETCH]
                      + [tracing.MISS_RUNS, tracing.DRAM_ROWS] * lanes)
    # the lanes are planned before the batch; its results recorded after
    before = [s[1] for s in spans if s[3] <= batch[2] and s is not run]
    after = {s[1] for s in spans if s[2] >= batch[3]}
    assert tracing.LANE_PLAN in before and after == {tracing.RECORD}
    # one guardrail check and one journal append per point, the batch
    # check, and the spec and done records with the manifest
    assert [s[1] for s in spans].count(tracing.RECORD) == 2 * lanes + 3


def _recording_engine(monkeypatch) -> list:
    """Make every lane program record, per dispatch, the serial rounds
    its plan asks for and the arrays it returns."""
    made, dispatched = sweep._lane_engine, []

    def engine(max_sets, max_ways, r_pad, *args, **kw):
        program = made(max_sets, max_ways, r_pad, *args, **kw)

        def run(*arrays):
            out = program(*arrays)
            r = np.minimum(np.asarray(arrays[3]), r_pad)
            dispatched.append((int(r.reshape(-1, r.shape[-1]).max(0).sum()),
                               jax.tree.leaves(out)))
            return out
        return run
    monkeypatch.setattr(sweep, "_lane_engine", engine)
    return dispatched


def _fetched(dispatched) -> int:
    return sum(a.nbytes for _, outs in dispatched for a in outs)


def test_frame_grid_counters_match_the_shapes(monkeypatch):
    dispatched = _recording_engine(monkeypatch)
    per_op = traces.network_op_segments(compile_network(), 4)
    flat = [s for segs in per_op for s in segs]
    cfgs = [llc_config_for(s, b) for s, b in FIG5_GRID]
    before = tracing.counters()
    hits = sweep.segment_lane_hit_counts(flat, cfgs)
    got = _delta(before, tracing.counters())
    # one program per bucket
    assert (got[tracing.PROGRAMS] == len(dispatched)
            == len(sweep.lane_buckets(cfgs)) == 6)
    # one int32 count per lane and segment
    assert got[tracing.FETCH_BYTES] == _fetched(dispatched) == hits.size * 4
    assert hits.size * 4 == 576
    # the frame-grid cell's leading four ops, pinned
    assert got[tracing.SCAN_ROUNDS] == sum(r for r, _ in dispatched) == 319


def test_campaign_counters_match_the_shapes(monkeypatch, tmp_path):
    dispatched = _recording_engine(monkeypatch)
    spec = example_spec(points=4, window_bursts=256)
    before = tracing.counters()
    res = run_campaign(spec, str(tmp_path))
    got = _delta(before, tracing.counters())
    assert res.completed == 4
    ((rounds, (hits, miss)),) = dispatched
    sets = max(p.geometry.llc().sets for p in spec.expand())
    lane_segments = [len(sweep.corunner_meta(
        p.model.trace(), llc=p.geometry.llc(), mix=p.mix.mix())[2])
        for p in spec.expand()]
    # the window's NVDLA chunks never continue one another: nothing
    # compacts, so the lanes scan their uncompacted segments
    assert got == {tracing.PROGRAMS: 1, tracing.SCAN_ROUNDS: rounds,
                   tracing.FETCH_BYTES: _fetched(dispatched),
                   tracing.MISS_WIDTH: sets,
                   tracing.LANE_SEGMENTS: sum(lane_segments),
                   tracing.LANE_SEGMENTS_RAW: sum(lane_segments)}
    # hits (lanes, segments) int32, miss bits (lanes, segments, rounds,
    # ordinals) bool, as wide as the 64 sets: below the 128-lane width
    segments = max(lane_segments)
    assert hits.shape == (4, segments) and hits.dtype == np.int32
    assert miss.shape[:2] == (4, segments) and miss.shape[3] == sets == 64
    assert miss.dtype == bool
    assert segments <= rounds <= segments * miss.shape[2]


def test_campaign_miss_bits_are_narrowed_past_128_sets(monkeypatch,
                                                       tmp_path):
    """On a 512-set LLC a 16-burst chunk spans at most 16 blocks, so each
    collecting program hands back 128 ordinals a round, not 512 sets."""
    from repro.campaign import CampaignSpec, GeometrySpec, MixSpec, ModelSpec

    dispatched = _recording_engine(monkeypatch)
    spec = CampaignSpec(
        name="wide", models=(ModelSpec(window_bursts=256),),
        geometries=tuple(GeometrySpec(size_kib=512 * w * 64 / 1024,
                                      block=64, ways=w) for w in (1, 2)),
        mixes=(MixSpec(0, "l1"), MixSpec(2, "llc")))
    before = tracing.counters()
    res = run_campaign(spec, str(tmp_path))
    got = _delta(before, tracing.counters())
    assert res.completed == 4
    ((_, (hits, miss)),) = dispatched
    lanes, segments = hits.shape
    r_pad = miss.shape[2]
    assert miss.shape == (lanes, segments, r_pad, 128) and lanes == 4
    assert got[tracing.FETCH_BYTES] == lanes * segments * (4 + r_pad * 128)
    assert got[tracing.MISS_WIDTH] == 128 * got[tracing.PROGRAMS] == 128


@pytest.mark.parametrize("profiled", [False, True])
def test_tracing_moves_no_statistic(tmp_path, profiled):
    """The manifest is the one the simulator wrote before it had spans
    or counters, with a profile running or not."""
    before = tracing.counters()
    if profiled:
        jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        run_campaign(example_spec(points=4, window_bursts=256),
                     str(tmp_path / "run"))
    finally:
        if profiled:
            jax.profiler.stop_trace()
    assert _delta(before, tracing.counters())[tracing.PROGRAMS] == 1
    manifest = (tmp_path / "run" / "manifest.json").read_bytes()
    assert hashlib.sha256(manifest).hexdigest() == UNTRACED_MANIFEST


def test_counts_from_many_threads_are_not_lost():
    threads, per_thread = 16, 2000
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = tracing.counters().get("test.threads", 0)

        def work():
            for _ in range(per_thread):
                tracing.count("test.threads", 1)
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(switch)
    assert (tracing.counters()["test.threads"] - before
            == threads * per_thread)


@pytest.mark.parametrize("frame", [True, False], ids=["frame", "window"])
def test_record_path_reduces_lanes_on_the_device(monkeypatch, tmp_path,
                                                 frame):
    """A whole-frame campaign (cut to the frame's first op) runs its
    lanes compacted, and the device reduces each to counts: the host
    fetches the per-record hits and four counts a lane, and opens no
    miss-run or DRAM-row span.  The windows never reach the record
    path."""
    from repro.campaign import CampaignSpec, GeometrySpec, MixSpec, ModelSpec
    from repro.campaign import spec as spec_module

    full = traces.network_trace
    monkeypatch.setattr(traces, "network_trace",
                        lambda regions=traces.REGIONS:
                        full(max_ops=1, regions=regions))
    spec_module._model_trace.cache_clear()
    fetched, opened = [], []
    for name in ("_record_engine", "_rows_engine"):
        made = getattr(sweep, name)

        def engine(*static, made=made):
            program = made(*static)

            def run(*arrays):
                out = program(*arrays)
                fetched.append(jax.tree.leaves(out))
                return out
            return run
        monkeypatch.setattr(sweep, name, engine)
    span = tracing.span

    def recorded(name):
        opened.append(name)
        return span(name)
    monkeypatch.setattr(tracing, "span", recorded)
    if frame:
        spec = CampaignSpec(
            name="frame", models=(ModelSpec(window_bursts=None),),
            geometries=(GeometrySpec(size_kib=32, block=64, ways=8),),
            mixes=tuple(MixSpec(k, "dram") for k in (0, 2, 4)))
    else:
        spec = example_spec(points=4, window_bursts=256)
    before = tracing.counters()
    try:
        res = run_campaign(spec, str(tmp_path))
    finally:
        spec_module._model_trace.cache_clear()
    got = _delta(before, tracing.counters())
    assert res.completed == len(spec.expand())
    if not frame:
        assert tracing.DEVICE_REDUCED_LANES not in got and not fetched
        assert tracing.MISS_RUNS in opened
        return
    (hits, _), (counts,) = fetched       # the codes stay on the device
    assert got[tracing.DEVICE_REDUCED_LANES] == 3
    assert hits.shape[0] == counts.shape[0] == 3 and counts.shape[1] == 4
    assert got[tracing.FETCH_BYTES] == hits.nbytes + counts.nbytes
    assert tracing.MISS_RUNS not in opened
    assert tracing.DRAM_ROWS not in opened
    assert opened.count(tracing.FETCH) == 2

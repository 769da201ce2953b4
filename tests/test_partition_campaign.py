"""Campaigns on a way-partitioned LLC: the NVDLA's capacity bitmask is a
point parameter (``CampaignSpec.way_masks``), hashed only when set, and
``run_campaign`` runs a fenced whole-frame campaign as partitioned
records, journaled, through crashes and resumes."""
from __future__ import annotations

import json

import pytest

from repro.campaign import (CampaignSpec, FaultInjector, GeometrySpec,
                            InjectedCrash, MixSpec, ModelSpec, RetryPolicy,
                            example_spec, plan_from_indices, run_campaign)
from repro.campaign import executor
from repro.campaign import spec as spec_module
from repro.campaign.spec import CampaignPoint, DRAMSpec, canonical_json
from repro.core import traces
from repro.core.cache import LLCConfig
from repro.core.dram import DRAMConfig
from repro.core.sweep import MixConfig, interference_lane_metrics_batch
from repro.utils import tracing

FRAME_SPEC = dict(
    name="frame", models=(ModelSpec(window_bursts=None),),
    geometries=(GeometrySpec(size_kib=2048, block=64, ways=8),),
    mixes=tuple(MixSpec(k, "dram") for k in range(5)))


def test_unpartitioned_ids_and_hashes_are_unchanged():
    """Specs without a partition hash, point for point, as they did
    before the axis existed."""
    spec = example_spec(points=4, window_bursts=256)
    assert spec.spec_hash == "99d26874ebd97c86"
    assert [p.point_id for p in spec.expand()] == [
        "e3dcaed7ab885d8f", "17de3637b2bc281e", "34bfd558bc057054",
        "9c331ae1ac8911a0"]
    frame = CampaignSpec(**FRAME_SPEC)
    assert frame.spec_hash == "8042e7506242640f"
    assert [p.point_id for p in frame.expand()] == [
        "453cbba7cbfdacc0", "4f5447126aa028e2", "5c751b1a7101eb97",
        "6260daaae02089da", "7762fd72e61ef708"]
    assert "way_masks" not in frame.to_dict()
    assert all("way_mask" not in p.params() for p in frame.expand())


def test_partition_round_trips_json_and_hashes_when_set(tmp_path):
    spec = CampaignSpec(**FRAME_SPEC, way_masks=(None, 0x0F))
    path = str(tmp_path / "spec.json")
    spec.save(path)
    back = CampaignSpec.load(path)
    assert back == spec and back.spec_hash == spec.spec_hash
    with open(path) as f:
        assert json.load(f)["way_masks"] == [None, 15]
    points = spec.expand()
    assert len(points) == 10
    assert [p.way_mask for p in points[:2]] == [None, 0x0F]
    plain = CampaignSpec(**FRAME_SPEC).expand()
    assert [p.point_id for p in points[::2]] == [p.point_id for p in plain]
    assert points[1].params()["way_mask"] == 0x0F
    assert len({p.point_id for p in points}) == 10


@pytest.mark.parametrize("mask", [0, -1, True, 0x100, 1.5])
def test_partition_is_validated(mask):
    """A mask must hold a way and no way past the geometry's."""
    with pytest.raises(ValueError):
        CampaignSpec(**FRAME_SPEC, way_masks=(mask,))


def test_run_batch_passes_the_masks():
    """The executor hands the points' masks to the lane batch, and none
    where no point is partitioned."""
    seen = []
    real = interference_lane_metrics_batch

    def spy(*args, **kw):
        seen.append(kw["way_masks"])
        return real(*args, **kw)

    spec = CampaignSpec(
        name="w", models=(ModelSpec(window_bursts=128),),
        geometries=(GeometrySpec(size_kib=4, block=64, ways=8),),
        mixes=(MixSpec(2, "llc"),), way_masks=(None, 0x03))
    segs = spec.models[0].trace()
    with pytest.MonkeyPatch.context() as mp:
        from repro.core import sweep

        mp.setattr(sweep, "interference_lane_metrics_batch", spy)
        executor.run_batch(spec.expand(), segs)
        executor.run_batch(spec.expand()[:1], segs)
    assert seen == [[None, 0x03], None]


REGIONS = (2048 * 7, traces.FMAP_REGION_A + 2048 * 3,
           traces.FMAP_REGION_B + 2048 * 40)


@pytest.fixture
def frame_of_one_op(monkeypatch):
    """The whole frame, as campaigns build it, cut to its first op."""
    full = traces.network_trace
    monkeypatch.setattr(traces, "network_trace",
                        lambda regions=traces.REGIONS:
                        full(max_ops=1, regions=regions))
    spec_module._model_trace.cache_clear()
    yield full(max_ops=1, regions=REGIONS)
    spec_module._model_trace.cache_clear()


def _run_until_done(spec, out_dir, plan, **kw):
    """Rerun with resume after every simulated process death."""
    for runs in range(1, 12):
        try:
            return run_campaign(spec, out_dir, resume=runs > 1,
                                hooks=FaultInjector(plan, out_dir),
                                policy=RetryPolicy(max_retries=1,
                                                   backoff_s=0.0), **kw)
        except InjectedCrash:
            continue
    raise AssertionError("campaign did not converge")


def test_fenced_frame_campaign_journals_crashes_and_resumes(tmp_path,
                                                           frame_of_one_op):
    """A whole-frame campaign (its first op, at moved regions) with the
    NVDLA fenced into half the ways: every lane runs as partitioned
    records, each point as the lane batch gives it, and a run killed
    twice and resumed ends on the uninterrupted run's manifest."""
    spec = CampaignSpec(
        name="fenced", models=(ModelSpec(window_bursts=None,
                                         regions=REGIONS),),
        geometries=(GeometrySpec(size_kib=32, block=64, ways=8),),
        mixes=tuple(MixSpec(k, "dram") for k in (0, 2, 4)),
        way_masks=(0x0F,))
    before = tracing.counters()
    clean = run_campaign(spec, str(tmp_path / "clean"), batch_points=3)
    after = tracing.counters()
    assert clean.manifest["counts"]["completed"] == 3
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert grew[tracing.PARTITIONED_RECORD_LANES] == 3
    want = interference_lane_metrics_batch(
        frame_of_one_op, llcs=[LLCConfig(32768, 8, 64)] * 3,
        drams=[DRAMConfig()] * 3,
        mixes=[MixConfig(k, "dram") for k in (0, 2, 4)], way_masks=[0x0F] * 3)
    assert [p["result"] for p in clean.manifest["points"]] == [
        m.to_record() for m in want]
    assert all(p["params"]["way_mask"] == 0x0F
               for p in clean.manifest["points"])
    plan = plan_from_indices(spec, [{"point": 1, "kind": "crash"},
                                    {"point": 2, "kind": "torn"}])
    res = _run_until_done(spec, str(tmp_path / "faulted"), plan,
                          batch_points=3)
    assert not res.failed
    assert canonical_json(res.manifest) == canonical_json(clean.manifest)


# a solo lane that re-reads 4 KiB: 8 ways of 8 sets keep it, a fence of
# one way does not
REREAD = [traces.Segment(0, 32, 128), traces.Segment(0, 32, 128)]


def _old_key(point):
    """The guardrail's family key before it knew of partitions."""
    if point.mix.corunners and point.mix.wss != "l1":
        return None
    llc = point.geometry.llc()
    return (canonical_json(point.model.to_dict()),
            canonical_json(point.dram.to_dict()),
            llc.sets, llc.block_bytes)


@pytest.mark.parametrize("key", ["fenced", "old"])
def test_monotone_guardrail_keeps_fenced_points_apart(tmp_path, monkeypatch,
                                                      key):
    """A fenced solo point at 8 ways hits less than its unfenced 8-way
    sibling, rightly: the family key tells them apart.  Keyed as before,
    the guardrail trips on the fenced point and quarantines it."""
    monkeypatch.setattr(spec_module, "_model_trace",
                        lambda *args: REREAD)
    if key == "old":
        monkeypatch.setattr(executor, "_monotone_family_key", _old_key)
    spec = CampaignSpec(
        name="family", models=(ModelSpec(window_bursts=None),),
        geometries=(GeometrySpec(size_kib=4, block=64, ways=8),),
        way_masks=(None, 0x01))
    unfenced, fenced = spec.expand()
    assert (executor._monotone_family_key(unfenced)
            != executor._monotone_family_key(fenced)) == (key == "fenced")
    res = run_campaign(spec, str(tmp_path), batch_points=2,
                       policy=RetryPolicy(max_retries=1, backoff_s=0.0))
    if key == "fenced":
        assert not res.failed and res.completed == 2
        hits = {p["point_id"]: p["result"]["llc_hits"]
                for p in res.manifest["points"]}
        assert hits[fenced.point_id] < hits[unfenced.point_id]
    else:
        assert set(res.failed) == {fenced.point_id}
        assert "LRU inclusion" in res.failed[fenced.point_id]["error"]


def test_points_of_one_partition_share_a_family():
    """The mask joins the key; the points of one mask stay comparable."""
    m, x, d = ModelSpec(window_bursts=64), MixSpec(), DRAMSpec()
    a = CampaignPoint(m, GeometrySpec(8, ways=4), x, d, 0x03)
    b = CampaignPoint(m, GeometrySpec(16, ways=8), x, d, 0x03)
    assert executor._monotone_family_key(a) == executor._monotone_family_key(b)
    assert executor._monotone_family_key(a)[-1] == 0x03

"""Campaign orchestrator: spec hashing, journaling, resume, guardrails,
and fault-injection equivalence (crash / hang / NaN / torn write)."""
from __future__ import annotations

import json
import os
import tempfile

import pytest

from repro.campaign import (
    CampaignSpec,
    FaultInjector,
    GeometrySpec,
    InjectedCrash,
    Journal,
    JournalError,
    MixSpec,
    ModelSpec,
    RetryPolicy,
    example_spec,
    plan_from_indices,
    run_campaign,
)
from repro.campaign.manifest import record_crc


def tiny_spec(points: int = 4) -> CampaignSpec:
    return example_spec(points=points, window_bursts=256)


def canon(manifest: dict) -> str:
    return json.dumps(manifest, sort_keys=True)


# --------------------------------------------------------------------------
# spec expansion and content hashing
# --------------------------------------------------------------------------
def test_expand_is_deterministic():
    spec = tiny_spec()
    a = [p.point_id for p in spec.expand()]
    b = [p.point_id for p in tiny_spec().expand()]
    assert a == b
    assert len(set(a)) == len(a)


def test_point_id_tracks_physics():
    g1, g2 = GeometrySpec(8, ways=2), GeometrySpec(16, ways=2)
    m, x = ModelSpec(window_bursts=64), MixSpec()
    from repro.campaign.spec import CampaignPoint, DRAMSpec

    p1 = CampaignPoint(m, g1, x, DRAMSpec())
    p2 = CampaignPoint(m, g2, x, DRAMSpec())
    assert p1.point_id != p2.point_id
    assert p1.point_id == CampaignPoint(m, g1, x, DRAMSpec()).point_id


def test_spec_round_trips_json(tmp_path):
    spec = tiny_spec()
    path = str(tmp_path / "spec.json")
    spec.save(path)
    again = CampaignSpec.load(path)
    assert again == spec
    assert again.spec_hash == spec.spec_hash


def test_spec_validation():
    with pytest.raises(ValueError, match="wss"):
        MixSpec(1, "l2")
    with pytest.raises(ValueError, match="model"):
        ModelSpec(name="resnet")
    with pytest.raises(ValueError, match="row_bytes"):
        CampaignSpec(name="bad", geometries=(GeometrySpec(8, block=96),))


# --------------------------------------------------------------------------
# clean run + resume
# --------------------------------------------------------------------------
def test_clean_run_writes_manifest(tmp_path):
    spec = tiny_spec()
    res = run_campaign(spec, str(tmp_path))
    assert res.completed == 4 and not res.failed
    m = json.load(open(res.manifest_path))
    assert m["spec_hash"] == spec.spec_hash
    assert [p["point_id"] for p in m["points"]] == \
        [p.point_id for p in spec.expand()]
    for p in m["points"]:
        r = p["result"]
        assert 0 <= r["llc_hits"] <= r["accesses"]
        assert r["dram_row_hits"] <= r["accesses"] - r["llc_hits"]


def test_resume_is_noop_after_success(tmp_path):
    spec = tiny_spec()
    first = run_campaign(spec, str(tmp_path))
    second = run_campaign(spec, str(tmp_path), resume=True)
    assert second.executed == 0 and second.resumed == 4
    assert canon(first.manifest) == canon(second.manifest)


def test_existing_journal_requires_resume_or_overwrite(tmp_path):
    spec = tiny_spec()
    run_campaign(spec, str(tmp_path))
    with pytest.raises(JournalError, match="resume"):
        run_campaign(spec, str(tmp_path))
    res = run_campaign(spec, str(tmp_path), overwrite=True)
    assert res.executed == 4


def test_resume_refuses_other_campaign(tmp_path):
    run_campaign(tiny_spec(), str(tmp_path))
    other = example_spec(points=2, window_bursts=128)
    with pytest.raises(JournalError, match="different campaign"):
        run_campaign(other, str(tmp_path), resume=True)


def test_torn_journal_tail_reruns_point(tmp_path):
    spec = tiny_spec()
    first = run_campaign(spec, str(tmp_path))
    journal = os.path.join(str(tmp_path), "journal.jsonl")
    lines = open(journal).read().splitlines(keepends=True)
    # tear into the final point record (drop the trailing "done" record
    # and half of the last point line) — the classic crash-mid-append
    with open(journal, "w") as f:
        f.writelines(lines[:-2])
        f.write(lines[-2][: len(lines[-2]) // 2])
    res = run_campaign(spec, str(tmp_path), resume=True)
    assert res.dropped_records == 1
    assert res.executed == 1 and res.resumed == 3
    assert canon(first.manifest) == canon(res.manifest)


def test_journal_crc_rejects_bitflips(tmp_path):
    spec = tiny_spec()
    run_campaign(spec, str(tmp_path))
    journal = Journal(os.path.join(str(tmp_path), "journal.jsonl"))
    records, dropped = journal.replay()
    assert dropped == 0
    # flip a digit inside a committed record's result
    text = open(journal.path).read()
    bad = text.replace('"accesses":256', '"accesses":999', 1)
    assert bad != text
    open(journal.path, "w").write(bad)
    _, dropped = journal.replay()
    assert dropped == 1


def test_record_crc_excludes_itself():
    rec = {"kind": "done", "completed": 1, "failed": 0}
    crc = record_crc(rec)
    assert record_crc({**rec, "crc": crc}) == crc


# --------------------------------------------------------------------------
# faults: retry, quarantine, equivalence
# --------------------------------------------------------------------------
def _run_until_done(spec, out_dir, plan, policy, **kw):
    """Drive a faulted campaign the way an operator would: rerun with
    --resume after every simulated process death."""
    runs = 0
    while True:
        runs += 1
        assert runs < 12, "campaign did not converge"
        hooks = FaultInjector(plan, out_dir)
        try:
            return run_campaign(spec, out_dir, resume=runs > 1,
                                policy=policy, hooks=hooks, **kw), runs
        except InjectedCrash:
            continue


def test_fault_equivalence_all_kinds(tmp_path):
    """A campaign surviving one crash, one hang, one NaN, and one torn
    write ends bit-identical to an uninterrupted campaign."""
    spec = tiny_spec()
    clean = run_campaign(spec, str(tmp_path / "clean"))
    # every retry runs the sequential single-lane program of its point:
    # compile them all first, so a timed attempt never waits on a compile
    run_campaign(spec, str(tmp_path / "sequential"), batch_points=1)
    plan = plan_from_indices(spec, [
        {"point": 0, "kind": "nan"},
        {"point": 1, "kind": "crash"},
        {"point": 2, "kind": "hang", "hang_s": 4.0},
        {"point": 3, "kind": "torn"},
    ])
    policy = RetryPolicy(max_retries=2, timeout_s=1.0, backoff_s=0.01)
    res, runs = _run_until_done(spec, str(tmp_path / "faulted"),
                                plan, policy)
    assert runs >= 3            # crash and torn each cost one process
    assert not res.failed
    assert canon(res.manifest) == canon(clean.manifest)


def test_nan_quarantined_without_retries(tmp_path):
    spec = tiny_spec()
    plan = plan_from_indices(spec, [{"point": 0, "kind": "nan"}])
    res = run_campaign(spec, str(tmp_path),
                       policy=RetryPolicy(max_retries=0, backoff_s=0),
                       hooks=FaultInjector(plan, str(tmp_path)))
    assert res.manifest["counts"] == {"total": 4, "completed": 3,
                                      "failed": 1}
    (info,) = res.failed.values()
    assert "finite" in info["error"]
    # resume keeps the quarantine; --retry-failed clears it
    keep = run_campaign(spec, str(tmp_path), resume=True,
                        hooks=FaultInjector(plan, str(tmp_path)))
    assert keep.executed == 0 and keep.manifest["counts"]["failed"] == 1
    heal = run_campaign(spec, str(tmp_path), resume=True, retry_failed=True,
                        hooks=FaultInjector(plan, str(tmp_path)))
    assert heal.completed == 4 and not heal.failed


def test_monotone_ways_guardrail_catches_consistent_corruption(tmp_path):
    # point 1 is the solo-mix ways=2 lane; deflating it is internally
    # consistent, so only LRU inclusion vs the ways=1 sibling trips
    spec = tiny_spec()
    plan = plan_from_indices(spec, [{"point": 1, "kind": "corrupt"}])
    res = run_campaign(spec, str(tmp_path),
                       policy=RetryPolicy(max_retries=0, backoff_s=0),
                       hooks=FaultInjector(plan, str(tmp_path)))
    (info,) = res.failed.values()
    assert "monotone" in info["error"]


def test_hang_times_out_and_recovers(tmp_path):
    spec = tiny_spec()
    plan = plan_from_indices(spec, [{"point": 0, "kind": "hang",
                                     "hang_s": 0.6}])
    res = run_campaign(spec, str(tmp_path),
                       policy=RetryPolicy(max_retries=1, timeout_s=0.15,
                                          backoff_s=0.01),
                       hooks=FaultInjector(plan, str(tmp_path)))
    assert res.completed == 4 and not res.failed


def test_fault_plan_validation():
    spec = tiny_spec()
    with pytest.raises(ValueError, match="outside"):
        plan_from_indices(spec, [{"point": 99, "kind": "crash"}])
    with pytest.raises(ValueError, match="kind"):
        plan_from_indices(spec, [{"point": 0, "kind": "gremlin"}])


# --------------------------------------------------------------------------
# mesh-sharded batched execution
# --------------------------------------------------------------------------
def _all_device_mesh():
    """A sweep mesh over every visible device — one on a plain CPU
    host, four under CI's XLA_FLAGS=--xla_force_host_platform_
    device_count=4 (which also exercises lane padding)."""
    import jax

    from repro.launch.mesh import make_sweep_mesh
    return make_sweep_mesh(jax.devices())


def test_mesh_and_batched_manifests_identical_to_sequential(tmp_path):
    """Tentpole acceptance: strictly sequential (batch_points=1),
    vmapped-batched, and mesh-sharded executions of the same spec write
    byte-identical manifests."""
    spec = tiny_spec(6)
    seq = run_campaign(spec, str(tmp_path / "seq"), batch_points=1)
    bat = run_campaign(spec, str(tmp_path / "bat"))
    msh = run_campaign(spec, str(tmp_path / "mesh"),
                       mesh=_all_device_mesh())
    assert seq.completed == bat.completed == msh.completed == 6
    assert canon(seq.manifest) == canon(bat.manifest) == canon(msh.manifest)


def test_quarantine_mid_batch_stays_per_point(tmp_path):
    """A NaN-poisoned point inside a batched lane program is
    quarantined alone; its batchmates complete from the same batch."""
    spec = tiny_spec(6)
    plan = plan_from_indices(spec, [{"point": 2, "kind": "nan"}])
    res = run_campaign(spec, str(tmp_path), mesh=_all_device_mesh(),
                       policy=RetryPolicy(max_retries=0, backoff_s=0),
                       hooks=FaultInjector(plan, str(tmp_path)))
    assert res.manifest["counts"] == {"total": 6, "completed": 5,
                                      "failed": 1}
    (info,) = res.failed.values()
    assert "finite" in info["error"]


@pytest.mark.parametrize("error,falls_back", [
    ("unsupported", True),
    ("device", False),
    ("value", False),
], ids=["unsupported_trace", "device_error", "other_value_error"])
def test_batch_falls_back_only_on_unsupported_trace(tmp_path, monkeypatch,
                                                    error, falls_back):
    """Only a trace the lane engine cannot replay sends a batch down the
    sequential path; a device failure, or any other error from the batch
    engine, fails the campaign instead of hiding as a slow run."""
    from repro.campaign import executor
    from repro.core.sweep import UnsupportedTraceError

    exc = {"unsupported": UnsupportedTraceError("segment stride 96"),
           "device": RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
           "value": ValueError("miss-run reconstruction disagrees")}[error]

    def broken(*a, **kw):
        raise exc

    monkeypatch.setattr(executor, "run_batch", broken)
    spec = tiny_spec(4)
    notes: list[str] = []
    if not falls_back:
        with pytest.raises(type(exc), match=str(exc)):
            run_campaign(spec, str(tmp_path / "bat"), progress=notes.append)
        assert not any("fell back" in n for n in notes)
        return
    res = run_campaign(spec, str(tmp_path / "bat"), progress=notes.append)
    assert sum("fell back to sequential" in n for n in notes) == 1
    seq = run_campaign(spec, str(tmp_path / "seq"), batch_points=1)
    assert canon(res.manifest) == canon(seq.manifest)


def test_crash_mid_batch_resume_bit_identical_property():
    """Hypothesis: killing the process at a random point inside a
    mesh-sharded batch, then resuming, lands on the sequential run's
    exact manifest — for several batch sizes."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    spec = example_spec(points=4, window_bursts=128)
    mesh = _all_device_mesh()
    with tempfile.TemporaryDirectory() as clean_dir:
        clean = run_campaign(spec, clean_dir, batch_points=1)
        baseline = canon(clean.manifest)

        @settings(max_examples=6, deadline=None)
        @given(kill_at=st.integers(0, 3), batch=st.sampled_from([2, 4]))
        def prop(kill_at, batch):
            with tempfile.TemporaryDirectory() as d:
                plan = plan_from_indices(spec, [
                    {"point": kill_at, "kind": "crash"}])
                res, _ = _run_until_done(
                    spec, d, plan,
                    RetryPolicy(max_retries=1, backoff_s=0),
                    mesh=mesh, batch_points=batch)
                assert not res.failed
                assert canon(res.manifest) == baseline

        prop()


# --------------------------------------------------------------------------
# crash-resume property: random kill prefix == uninterrupted run
# --------------------------------------------------------------------------
def test_crash_resume_bit_identical_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    spec = example_spec(points=3, window_bursts=128)
    with tempfile.TemporaryDirectory() as clean_dir:
        clean = run_campaign(spec, clean_dir)
        baseline = canon(clean.manifest)

        @settings(max_examples=8, deadline=None)
        @given(kill_at=st.integers(0, 2), second_kill=st.integers(0, 2))
        def prop(kill_at, second_kill):
            with tempfile.TemporaryDirectory() as d:
                plan = plan_from_indices(spec, [
                    {"point": kill_at, "kind": "crash"},
                    {"point": second_kill, "kind": "torn"},
                ])
                res, _ = _run_until_done(spec, d, plan, RetryPolicy(
                    max_retries=1, backoff_s=0))
                assert not res.failed
                assert canon(res.manifest) == baseline

        prop()


# --------------------------------------------------------------------------
# cross-backend campaigns: NVDLA + NPU points in one spec
# --------------------------------------------------------------------------
def test_backend_axis_preserves_pre_backend_hashes():
    """Adding the backend axis must not invalidate existing journals:
    an NVDLA ModelSpec's dict (and therefore every point_id) is exactly
    what it was before backend/npu_rows/npu_cols existed."""
    d = ModelSpec(window_bursts=256).to_dict()
    assert d == {"name": "yolov3", "window_bursts": 256,
                 "chunk_bursts": 16, "layer_index": 40}
    assert ModelSpec(**d) == ModelSpec(window_bursts=256)
    # the axis fields do carry physics for NPU points
    nv = ModelSpec(window_bursts=256)
    np8 = ModelSpec(window_bursts=256, backend="npu", npu_rows=8,
                    npu_cols=8)
    np16 = ModelSpec(window_bursts=256, backend="npu")
    from repro.campaign.spec import CampaignPoint, DRAMSpec

    ids = {CampaignPoint(m, GeometrySpec(8, ways=2), MixSpec(),
                         DRAMSpec()).point_id for m in (nv, np8, np16)}
    assert len(ids) == 3


def test_backend_axis_validation():
    with pytest.raises(ValueError, match="backend"):
        ModelSpec(backend="tpu")
    with pytest.raises(ValueError, match="trace sources"):
        ModelSpec(name="transformer_decode")          # nvdla can't GEMM
    with pytest.raises(ValueError, match="layer_index"):
        ModelSpec(backend="npu", layer_index=7)       # dropped from hash
    with pytest.raises(ValueError, match="npu_rows"):
        ModelSpec(npu_rows=8)                         # dropped from hash
    from repro.campaign.spec import mixed_backend_spec

    with pytest.raises(ValueError, match="even"):
        mixed_backend_spec(points=3)


def test_npu_points_trace_through_executor(tmp_path):
    """A pure-NPU campaign runs the unchanged executor + guardrails and
    its journaled counters replay the NPU window exactly."""
    from repro.campaign.spec import mixed_backend_spec
    from repro.core import npu
    from repro.core.cache import simulate_segments

    spec = mixed_backend_spec(4, window_bursts=128)
    res = run_campaign(spec, str(tmp_path))
    assert res.completed == 4 and not res.failed
    npu_points = [p for p in res.manifest["points"]
                  if p["params"]["model"].get("backend") == "npu"]
    assert len(npu_points) == 2
    window = npu.npu_chunks(npu.workload("yolov3"),
                            npu.NPUConfig(rows=8, cols=8),
                            chunk_bursts=16, max_bursts=128)
    for p in npu_points:
        geo = p["params"]["geometry"]
        llc = GeometrySpec(**geo).llc()
        ref = simulate_segments(window, llc)
        assert p["result"]["nvdla_accesses"] == ref.accesses
        assert p["result"]["nvdla_hits"] == ref.hits


def test_mixed_backend_campaign_crash_resume_bit_identical(tmp_path):
    """The satellite acceptance case: an 8-point NVDLA+NPU campaign
    journals, crashes mid-run on each backend's half, and resumes to a
    manifest bit-identical to an uninterrupted run."""
    from repro.campaign.spec import mixed_backend_spec

    spec = mixed_backend_spec(8, window_bursts=256)
    backends = [p.model.backend for p in spec.expand()]
    assert sorted(set(backends)) == ["npu", "nvdla"]
    clean = run_campaign(spec, str(tmp_path / "clean"))
    assert clean.completed == 8 and not clean.failed
    plan = plan_from_indices(spec, [
        {"point": backends.index("nvdla"), "kind": "crash"},
        {"point": backends.index("npu") + 1, "kind": "crash"},
    ])
    res, runs = _run_until_done(spec, str(tmp_path / "faulted"), plan,
                                RetryPolicy(max_retries=1, backoff_s=0))
    assert runs >= 3 and not res.failed
    assert canon(res.manifest) == canon(clean.manifest)


def test_mixed_backend_batched_matches_sequential(tmp_path):
    """Batched (vmapped-lane) execution shards NVDLA and NPU points
    into separate lane programs but must journal identical numbers."""
    from repro.campaign.spec import mixed_backend_spec

    spec = mixed_backend_spec(4, window_bursts=128)
    seq = run_campaign(spec, str(tmp_path / "seq"))
    bat = run_campaign(spec, str(tmp_path / "bat"), batch_points=4)
    assert canon(seq.manifest) == canon(bat.manifest)

"""The whole-frame campaign cell (``fig6-frame-dram``) at a test size: a
planted fault in its answers fails its check, and its control reads
above 0."""
from __future__ import annotations

import copy

import jax
import pytest
from bench_helpers import mini_root, run_cell, small_config, small_traffic

FRAME = "fig6-frame-dram"


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return mini_root(tmp_path_factory.mktemp("bench"))


def _misses_one_hit(manifest):
    manifest = copy.deepcopy(manifest)
    r = manifest["points"][-1]["result"]
    r["nvdla_hits"] -= 1
    r["llc_hits"] -= 1
    return manifest


@pytest.mark.parametrize("fault", ["one_hit_lost", "last_lane_dropped"])
def test_fault_in_the_frame_cell_makes_the_run_incorrect(small, monkeypatch,
                                                         fault):
    from bench import generator

    build = generator.build

    def broken(*args, **kwargs):
        cell = build(*args, **kwargs)
        call = cell.call

        def wrong():
            manifest = call()
            if fault == "one_hit_lost":
                return _misses_one_hit(manifest)
            manifest = copy.deepcopy(manifest)
            manifest["points"] = manifest["points"][:-1]
            return manifest
        cell.call = wrong
        return cell

    monkeypatch.setattr(generator, "build", broken)
    rc, result, err = run_cell(small, FRAME)
    assert rc == 0, err
    assert result["correct"] is False, err
    assert result["failed"] == result["attempted"]


def test_frame_cell_compacts_and_its_control_reads_above_zero():
    """One call at test size: every lane matches the reference (0), the
    control with DRAM rows per master does not, and the lanes ran as
    far fewer records than segments."""
    from bench import generator
    from repro.utils import tracing

    config = small_config("nvdla-soc-yolov3-frame")
    traffic = small_traffic(FRAME)
    cell = generator.build(config, traffic, 2147483659, jax.devices()[:1])
    try:
        before = tracing.counters()
        out = cell.call()
        after = tracing.counters()
        program = cell.check([out])
        control = cell.check([out], control=True)
        notes = cell.notes(out)
    finally:
        cell.close()
    assert program.correct and set(v for v, _ in program.numbers.values()) \
        == {0}
    assert not control.correct
    assert control.numbers["dram_field_mismatches"][0] > 0
    grew = {k: after[k] - before.get(k, 0) for k in after}
    assert (10 * grew["sweep.lane_segments"]
            < grew["sweep.lane_segments_raw"])
    assert len([n for n in notes if n.startswith("frame dram x")]) == 5
    assert out["counts"]["completed"] == 5


"""The traffic generator: inputs are a function of the seed, every seed
keeps the sizes (and so the compiled programs) of seed 0, and seed 0 is
the input the simulator's chip bring-up used."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest
from bench_helpers import ROOT, load, small_config, small_traffic

from bench import generator
from bench.entries import campaign as campaign_entry
from bench.entries import frame_grid
from bench.reference import dbb as ref_dbb
from repro.core import traces
from repro.core.runtime import compile_network

BIG_SEED = 2**31 + 12345
CFG = load(ROOT / "bench/configs/nvdla-soc-yolov3.json")


def _cell(traffic: str, seed: int, small: bool = True):
    if small:
        cfg, tr = small_config("nvdla-soc-yolov3"), small_traffic(traffic)
    else:
        cfg, tr = CFG, load(ROOT / "bench" / "traffic" / f"{traffic}.json")
    return generator.build(cfg, tr, seed, None)


def _inputs(cell) -> str:
    if hasattr(cell, "flat"):
        return json.dumps([traces.segment_tuple(s) for s in cell.flat])
    return json.dumps(cell.spec.to_dict())


TRAFFIC = ["frame-grid", "fig6-grid"]


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_inputs_are_a_function_of_the_seed(traffic):
    a = _inputs(_cell(traffic, BIG_SEED))
    b = _inputs(_cell(traffic, BIG_SEED))
    c = _inputs(_cell(traffic, 7))
    assert a == b
    assert a != c


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_every_seed_keeps_the_sizes(traffic):
    a, b = _cell(traffic, 0), _cell(traffic, BIG_SEED)
    assert a.bursts_per_call == b.bursts_per_call
    if hasattr(a, "flat"):
        assert [s.count for s in a.flat] == [s.count for s in b.flat]
    else:
        va, vb = (traces.default_dbb_window(
            max_bursts=c.config["window_bursts"], chunk_bursts=16,
            layer_index=c.layer) for c in (a, b))
        assert [s.count for s in va] == [s.count for s in vb]


def test_seeded_regions_stay_apart_and_in_range():
    for seed in (1, 2, 3, BIG_SEED):
        bases = frame_grid.seeded_regions(CFG, seed, 32768)
        assert len({(b // 2048) % 32 for b in bases}) == 3
        assert bases[0] + 64 * 2**20 < bases[1] < bases[2] < 2**31 - 2**25


def test_seed_zero_is_the_default_frame():
    cell = _cell("frame-grid", 0, small=False)
    want = [s for segs in traces.network_op_segments() for s in segs]
    assert cell.flat == want
    assert sum(s.count for s in want) * 12 == cell.bursts_per_call


def test_the_acceptance_grid_gives_the_bring_up_manifest():
    """The campaign entry, given the 64-point acceptance grid (16 sets,
    ways 1-4, blocks 128-1024 B, four mixes) at seed 0, runs the
    16384-burst campaign whose manifest the chip bring-up hashed."""
    grid = {"entry": "campaign", "name": "bench-64pt", "batch_points": 64,
            "mesh": False,
            "geometries": [[16 * w * b / 1024, b, w]
                           for b in (128, 256, 512, 1024) for w in (1, 2, 3, 4)],
            "mixes": [[0, "l1"], [1, "llc"], [2, "llc"], [2, "dram"]]}
    cell = generator.build(CFG, grid, 0, None)
    try:
        cell.call()
        with open(cell.out_dir + "/manifest.json", "rb") as f:
            sha = hashlib.sha256(f.read()).hexdigest()
    finally:
        cell.close()
    assert sha == ("f45a3ff85dec714ce2eb78557bd6723a"
                   "09d710920e9495fe3a649630f3a45936")
    assert cell.layer == 40
    assert cell.bursts_per_call == 2_359_296


def test_fig6_grid_is_the_papers_node():
    cell = _cell("fig6-grid", 0, small=False)
    (g,) = cell.spec.geometries
    assert (g.llc().size_bytes, g.llc().ways, g.llc().block_bytes) == (
        2 * 2**20, 8, 64)
    assert {(m.corunners, m.wss) for m in cell.spec.mixes} == {
        (n, w) for w in ("llc", "dram") for n in (1, 2, 3, 4)} | {
        (n, "l1") for n in range(5)}
    assert cell.bursts_per_call == 16384 * (5 + 2 * (2 + 3 + 4 + 5))


def test_seeded_layers_are_distinct_windows_of_whole_chunks():
    layers = campaign_entry.distinct_layers(CFG)
    assert 40 in layers and len(layers) >= 10
    wins = set()
    for seed in range(len(layers)):
        layer = campaign_entry.seeded_layer(CFG, seed)
        win = traces.default_dbb_window(max_bursts=16384, layer_index=layer)
        assert np.all([s.count == 16 for s in win])
        wins.add(tuple(traces.segment_tuple(s) for s in win))
    assert len(wins) == len(layers)


def test_the_op_table_is_the_networks_traffic():
    """The configuration's per-op table is what the simulator's network
    compiler schedules, so the reference's own frame and windows are the
    program's."""
    ops = compile_network(conv_buf_bytes=CFG["accelerator"]["conv_buf_bytes"]
                          ).accel_ops
    assert CFG["dbb_ops"] == [[o.weight_traffic, o.ifmap_traffic,
                               o.ofmap_traffic, o.weight_passes] for o in ops]
    d = CFG["dbb"]
    segments, op_of = ref_dbb.frame(CFG["dbb_ops"], d["weight_region"],
                                    d["fmap_region_a"], d["fmap_region_b"],
                                    burst=32)
    want = traces.network_op_segments()
    assert list(zip(*(a.tolist() for a in segments))) == [
        traces.segment_tuple(s) for segs in want for s in segs]
    assert op_of.tolist() == [i for i, segs in enumerate(want) for _ in segs]
    for layer in (0, 4, 40, 97):
        got = campaign_entry.victim_window(CFG, layer)
        assert list(zip(*(a.tolist() for a in got))) == [
            traces.segment_tuple(s) for s in traces.default_dbb_window(
                max_bursts=16384, chunk_bursts=16, layer_index=layer)]

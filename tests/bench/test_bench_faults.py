"""A run whose timed path is broken underneath reports ``correct`` false.

Each fault is planted where the simulator entry a cell drives hands
back its answers, and the whole run goes through the harness (its
look for a chip skipped) at a test size:

* ``state_unchanged``: the LLC keeps nothing from one access to the
  next, so nothing hits;
* ``half_batch``: the second half of the lanes (geometries or points)
  is left out and filled with the first half's answers;
* ``exchange_left_out``: only the first of four lane shards comes back
  from the mesh, the others read as empty;
* ``answer_altered``: one answer is off by one.
"""
from __future__ import annotations

import copy
import json

import numpy as np
import pytest
from bench_helpers import MESH_CELL, mini_root, run_cell


def _halves(items: list) -> list:
    half = len(items) // 2
    return items[:half] + items[:len(items) - half]


def _fig5(fault):
    def wrap(counts):
        counts = np.array(counts)
        if fault == "state_unchanged":
            return np.zeros_like(counts)
        if fault == "half_batch":
            return np.asarray(_halves(list(counts)))
        counts[0, 0] += 1
        return counts
    return wrap


def _campaign(fault):
    def empty(result):
        return {**result, "llc_hits": 0, "nvdla_hits": 0, "hit_rate": 0.0,
                "nvdla_hit_rate": 0.0}

    def wrap(manifest):
        manifest = copy.deepcopy(manifest)
        points = manifest["points"]
        results = [p["result"] for p in points]
        if fault == "state_unchanged":
            results = [empty(r) for r in results]
        elif fault == "half_batch":
            results = _halves(results)
        elif fault == "exchange_left_out":
            shard = len(results) // 4
            results = results[:shard] + [empty(r) for r in results[shard:]]
        else:
            results[0] = {**results[0], "llc_hits": results[0]["llc_hits"] + 1}
        for p, r in zip(points, results):
            p["result"] = r
        return manifest
    return wrap


CELLS = {
    "fig5-frame-grid": (_fig5, ("state_unchanged", "half_batch",
                                "answer_altered")),
    "fig6-campaign": (_campaign, ("state_unchanged", "half_batch",
                                  "answer_altered")),
    MESH_CELL["name"]: (_campaign, ("state_unchanged", "half_batch",
                                    "exchange_left_out", "answer_altered")),
}
CASES = [(w, f) for w, (_, faults) in CELLS.items() for f in faults]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return mini_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(small, monkeypatch, workload, fault):
    from bench import generator

    make, _ = CELLS[workload]
    build, wrap = generator.build, make(fault)

    def broken(*args, **kwargs):
        cell = build(*args, **kwargs)
        call = cell.call
        cell.call = lambda: wrap(call())
        return cell

    monkeypatch.setattr(generator, "build", broken)
    rc, result, err = run_cell(small, workload)
    assert rc == 0, err
    assert json.loads(err.splitlines()[0])["workload"] == workload
    assert result["correct"] is False, err
    assert result["failed"] == result["attempted"]

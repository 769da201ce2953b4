"""The split of device idle time by the program span that held the
device back (``bench/idle_split.py``), on hand-made rows with known
answers, and the program-counter readers on cells at a test size."""
from __future__ import annotations

import argparse
import sys

import pytest
from bench_helpers import mini_root, run_cell

from bench import idle_split, trace

H, D0, D1 = trace.HOST_PLANE, "/device:TPU:0", "/device:TPU:1"
OPS = trace.OPS_LINE
MS = 1_000_000
RUN, BATCH = "repro.campaign.run", "repro.sweep.lane_batch"
PLAN, DISPATCH, FETCH = ("repro.sweep.lane_plan", "repro.sweep.dispatch",
                         "repro.sweep.fetch")
RECORD = "repro.campaign.record"


def _ms(**spans):
    return {k: pytest.approx(v / 1e3) for k, v in spans.items()}


def _window(*rows):
    return [(H, "main", trace.WINDOW_SPAN, 0, 100 * MS), *rows]


def test_idle_goes_to_the_innermost_span_of_any_thread():
    split = idle_split.idle_by_span(_window(
        (D0, OPS, "fusion", 0, 10 * MS),
        (H, "main", RUN, 0, 100 * MS),
        (H, "main", FETCH, 10 * MS, 40 * MS),
        # started last, on another thread: it owns 30-60 ms
        (H, "worker", RECORD, 30 * MS, 60 * MS),
    ), 1)
    assert split.leaf_s == _ms(**{FETCH: 20, RECORD: 30})
    assert split.parent_s == _ms(**{RUN: 40})
    assert split.untraced_s == pytest.approx(0.040)
    assert split.idle_s == pytest.approx(0.090)


def test_a_parent_owns_only_what_its_children_leave():
    split = idle_split.idle_by_span(_window(
        (H, "main", "bench.call", 0, 100 * MS),
        (H, "main", BATCH, 0, 90 * MS),
        # a child that starts with its parent is still the inner span
        (H, "main", PLAN, 0, 20 * MS),
        (H, "main", DISPATCH, 20 * MS, 30 * MS),
        (D0, OPS, "while", 30 * MS, 50 * MS),
    ), 1)
    assert split.leaf_s == _ms(**{PLAN: 20, DISPATCH: 10})
    assert split.parent_s == _ms(**{BATCH: 40})
    # 50-90 ms under the parent alone, 90-100 ms under no program span
    assert split.untraced_s == pytest.approx(0.050)
    assert split.spans == 3


@pytest.mark.parametrize("n_devices", [1, 2])
def test_leaves_and_untraced_add_up_to_the_idle_share(n_devices):
    rows = _window(
        (H, "main", RUN, 5 * MS, 95 * MS),
        (H, "main", PLAN, 5 * MS, 12 * MS),
        (H, "main", BATCH, 12 * MS, 70 * MS),
        (H, "main", DISPATCH, 12 * MS, 15 * MS),
        (H, "main", FETCH, 15 * MS, 52 * MS),
        (H, "main", RECORD, 71 * MS, 94 * MS),
        (H, "io", RECORD, 80 * MS, 99 * MS),
        (D0, OPS, "fusion", 14 * MS, 30 * MS),
        (D0, OPS, "while", 25 * MS, 50 * MS),
        (D0, OPS, "fusion", 96 * MS, 140 * MS),
        (D1, OPS, "while", 0, 20 * MS),
        (D1, OPS, "all-reduce", 60 * MS, 75 * MS),
    )
    split = idle_split.idle_by_span(rows, n_devices)
    summary = trace.reduce_rows(rows, n_devices)
    idle_share = 100.0 * (1.0 - summary.busy_s / summary.window_s)
    shares = split.shares()
    assert sum(shares.values()) == pytest.approx(idle_share, abs=1e-9)
    assert split.devices == summary.devices == n_devices
    assert 100.0 * split.idle_s / split.window_s == pytest.approx(idle_share)
    # the parents' idle time is part of the untraced
    assert split.untraced_s > sum(split.parent_s.values()) > 0


def test_nothing_to_read_gives_none():
    rows = _window((D0, OPS, "fusion", 0, 10 * MS),
                   (H, "main", FETCH, 0, 50 * MS))
    assert idle_split.idle_by_span(rows[1:], 1) is None
    assert idle_split.idle_by_span([r for r in rows if r[0] == H], 1) is None
    assert idle_split.idle_by_span(rows, 1) is not None


def test_run_split_hands_the_harness_the_summary_it_would_read(monkeypatch):
    from bench import harness

    rows = _window((D0, OPS, "fusion", 0, 10 * MS),
                   (H, "main", FETCH, 0, 50 * MS))
    seen = []
    monkeypatch.setattr(trace, "read_rows", lambda d: rows)
    monkeypatch.setattr(harness, "run", lambda args, t, **kw: seen.append(
        trace.summarize("unused", 1)) or 0)
    plain = trace.summarize
    rc, split = idle_split.run_split(argparse.Namespace(), 0.0)
    assert rc == 0 and trace.summarize is plain
    assert seen == [trace.reduce_rows(rows, 1)]
    assert split.leaf_s == _ms(**{FETCH: 40})
    assert split.untraced_s == pytest.approx(0.050)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return mini_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload, fetch_bytes, rounds", [
    # 12 geometries x 12 segments of int32 counts; the pinned plan of
    # the frame's leading four ops (tests/test_tracing.py)
    ("fig5-frame-grid", 12 * 12 * 4, 319),
    # 4 lanes x 96 segments (32 chunks of the window, each beside at
    # most two co-runner chunks) of an int32 hit count and one round of
    # miss bits over 64 sets; one round per segment
    ("fig6-campaign", 4 * 96 * (4 + 64), 96),
])
def test_counter_readers_read_the_programs_counters(small, monkeypatch,
                                                    workload, fetch_bytes,
                                                    rounds):
    from repro.utils import tracing

    monkeypatch.setattr(tracing, "_counts", {})
    rc, result, err = run_cell(small, workload, trace=1)
    assert rc == 0 and result["correct"], err
    metrics = result["metrics"]
    calls = result["attempted"] + 2         # and the two warm-up calls
    counts = tracing.counters()
    assert metrics["fetch_mb_per_call"]["value"] == pytest.approx(
        counts["sweep.fetch_bytes"] / calls / 1e6)
    assert metrics["scan_rounds_per_call"]["value"] == pytest.approx(
        counts["sweep.scan_rounds"] / calls)
    assert metrics["fetch_mb_per_call"]["value"] == fetch_bytes / 1e6
    assert metrics["scan_rounds_per_call"]["value"] == rounds


def test_counter_readers_find_nothing_without_the_counters(monkeypatch):
    from bench.counters import per_call

    run = argparse.Namespace(calls=3)
    monkeypatch.setitem(sys.modules, "repro.utils.tracing", None)
    assert per_call(run, "sweep.fetch_bytes") is None
    monkeypatch.undo()
    assert per_call(run, "no.such.counter") is None

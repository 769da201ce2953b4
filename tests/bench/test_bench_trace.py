"""The reduction from profiler-trace rows to busy time, idle share,
collective share and the breakdown, on hand-made rows with known
answers, and the reading of a profile this process records (on the
CPU, which has no device plane)."""
from __future__ import annotations

import bench_helpers  # noqa: F401  (puts the checkout on sys.path)
import jax
import jax.numpy as jnp
import pytest

from bench import trace

H, D0, D1 = trace.HOST_PLANE, "/device:TPU:0", "/device:TPU:1"
OPS = trace.OPS_LINE
MS = 1_000_000


def _rows():
    return [
        (H, "main", trace.WINDOW_SPAN, 0, 100 * MS),
        (H, "main", trace.CALL_SPAN, 0, 50 * MS),
        (H, "main", trace.CALL_SPAN, 50 * MS, 100 * MS),
        (H, "main", "host_work", 60 * MS, 90 * MS),
        # device 0: 10-30 and 20-40 overlap -> busy 30 ms; one op outside
        (D0, OPS, "fusion", 10 * MS, 30 * MS),
        (D0, OPS, "fusion", 20 * MS, 40 * MS),
        (D0, OPS, "all-reduce.1", 40 * MS, 45 * MS),
        (D0, OPS, "fusion", 120 * MS, 130 * MS),
        # device 1: 50 ms busy, 5 of it collective
        (D1, OPS, "while", 0, 45 * MS),
        (D1, OPS, "all-reduce.1", 45 * MS, 50 * MS),
        (D1, "XLA Modules", "jit_prog", 0, 100 * MS),
    ]


def test_busy_union_idle_and_collectives():
    s = trace.reduce_rows(_rows(), n_devices=2)
    assert s.devices == 2
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx((0.035 + 0.050) / 2)
    assert s.collective_s == pytest.approx(0.005)
    assert s.device_ops[0] == ["while", pytest.approx(0.045)]
    # the longest gaps, 45-100 ms on device 0 and 50-100 ms on device 1,
    # have their midpoints inside the innermost host span host_work
    assert s.idle_gaps[0] == ["host_work", pytest.approx(0.055)]
    assert s.idle_gaps[1] == ["host_work", pytest.approx(0.050)]
    assert s.idle_gaps[2] == [trace.CALL_SPAN, pytest.approx(0.010)]


def test_only_the_cells_devices_are_read():
    s = trace.reduce_rows(_rows(), n_devices=1)
    assert s.devices == 1 and s.busy_s == pytest.approx(0.035)


def test_nothing_to_read_gives_none():
    rows = _rows()
    assert trace.reduce_rows([r for r in rows if r[2] != trace.WINDOW_SPAN],
                             2) is None
    assert trace.reduce_rows([r for r in rows if r[0] == H], 2) is None



def test_reads_the_host_spans_of_a_recorded_profile(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation(trace.CALL_SPAN):
            jnp.arange(1000).sum().block_until_ready()
    jax.profiler.stop_trace()
    rows = trace.read_rows(str(tmp_path))
    spans = [r for r in rows if r[0] == H and r[2] == trace.WINDOW_SPAN]
    assert len(spans) == 1 and spans[0][3] < spans[0][4]
    calls = [r for r in rows if r[0] == H and r[2] == trace.CALL_SPAN]
    assert spans[0][3] <= calls[0][3] <= calls[0][4] <= spans[0][4]
    assert trace.summarize(str(tmp_path), 1) is None

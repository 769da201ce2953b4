"""The reduction from a profiler trace to device busy time, idle gaps and
collective time.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line
holds one event per operation that ran on it.  The harness's own host
spans (``WINDOW_SPAN`` around the measured window, ``CALL_SPAN`` around
each call) are events on the host plane ``/host:CPU``.  All of them
share one clock.

Busy time is the union of a device's op intervals inside the window.
A gap is a stretch of the window in which the device ran nothing; each
is named by the innermost host event that covers its midpoint.  The
reduction works on plain ``(plane, line, name, start_ns, end_ns)``
rows, so a small recorded trace can test it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv",
                        re.IGNORECASE)
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    devices: int               # device planes read
    window_s: float            # length of the traced window
    busy_s: float              # mean over devices of the busy union
    collective_s: float        # mean over devices of collective op time
    device_ops: list           # [[op name, seconds summed over devices]]
    idle_gaps: list            # [[host span covering the gap, seconds]]


def read_rows(trace_dir: str) -> list[tuple]:
    """Every event of the trace as (plane, line, name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    rows = []
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.end_ns)))
    return rows


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_rows(rows: list[tuple], n_devices: int) -> TraceSummary | None:
    """The summary of a trace's rows over its first ``n_devices`` TPUs,
    or None where the trace has no window span or no device ops."""
    windows = [(s, e) for p, _, n, s, e in rows
               if p == HOST_PLANE and n == WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    per_dev: dict[int, list] = {}
    for plane, line, name, s, e in rows:
        m = DEVICE_PLANE.match(plane)
        if m and line == OPS_LINE and int(m.group(1)) < n_devices:
            per_dev.setdefault(int(m.group(1)), []).append((name, s, e))
    if not per_dev:
        return None
    busy, coll, op_time = [], [], {}
    gaps = []
    for ops in per_dev.values():
        ivs = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(b - a for a, b in ivs))
        coll.append(sum(b - a for a, b in _union(_clip(
            [(s, e) for n, s, e in ops if COLLECTIVE.search(n)], lo, hi))))
        for name, s, e in ops:
            a, b = max(s, lo), min(e, hi)
            if b > a:
                op_time[name] = op_time.get(name, 0) + (b - a)
        edges = [lo] + [x for iv in ivs for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    host = [(s, e, n) for p, _, n, s, e in rows
            if p == HOST_PLANE and s <= hi and e >= lo]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) // 2
        cover = [(e - s, n) for s, e, n in host if s <= mid <= e]
        named.append([min(cover)[1] if cover else "none", (b - a) / 1e9])
    n = len(per_dev)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        devices=n, window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
        collective_s=sum(coll) / n / 1e9,
        device_ops=[[k, v / 1e9] for k, v in top_ops], idle_gaps=named)


def summarize(trace_dir: str, n_devices: int) -> TraceSummary | None:
    return reduce_rows(read_rows(trace_dir), n_devices)

#!/usr/bin/env python3
"""Device idle time of a traced window, split by the program span that
held the device back.

    python3 bench/idle_split.py --workload <name> --seed <n> --seconds <s>

The simulator marks its host phases with spans named ``repro.*``
(``repro.utils.tracing``); they land on the profile's host plane, on
the device planes' clock.  At each instant of the window the span that
owns it is the innermost program span open then: of those open on any
host thread, the one started last.  Each device's idle time at that
instant goes to the owner, so a parent span owns only the idle time its
children leave.  A leaf span is one with no other program span nested
in it on its own thread.  Idle time that no leaf owns, because only a
parent span or no span is open, is untraced.  The leaf seconds plus the
untraced seconds are the idle seconds, device by device; like
``bench.trace.reduce_rows``'s ``busy_s``, every number is the mean over
the cell's devices, so the leaf shares plus the untraced share are
``device_idle_share``.

Run as a script, it makes one ``--trace 1`` run of the cell through
``bench.harness`` (standard output and error as ``bench/run.py`` gives
them) and then prints one more JSON line: the window's idle split as
shares of the window (``%``), and the number of program spans per
call.
"""
from __future__ import annotations

import dataclasses
import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

PROGRAM_SPAN = "repro."
UNTRACED = "untraced"


@dataclasses.dataclass
class IdleSplit:
    devices: int          # device planes read
    window_s: float       # length of the traced window
    idle_s: float         # mean over devices of the idle seconds
    leaf_s: dict          # leaf span name -> idle seconds it owned
    parent_s: dict        # parent span name -> idle seconds it owned
    untraced_s: float     # idle seconds no leaf span owned
    spans: int            # program spans that overlap the window

    def shares(self) -> dict:
        """Each leaf's idle seconds, and the untraced ones, in % of the
        window."""
        out = {n: 100.0 * s / self.window_s for n, s in self.leaf_s.items()}
        out[UNTRACED] = 100.0 * self.untraced_s / self.window_s
        return out


def _program_spans(rows, lo, hi) -> list[tuple[int, int, str, bool]]:
    """The program spans that overlap ``[lo, hi]``, clipped to it, as
    (start, end, name, is_leaf)."""
    by_line: dict[str, list] = {}
    for plane, line, name, s, e in rows:
        if plane == trace.HOST_PLANE and name.startswith(PROGRAM_SPAN):
            by_line.setdefault(line, []).append((s, -e, name))
    spans = []
    for evs in by_line.values():
        evs.sort()
        for k, (s, neg_e, name) in enumerate(evs):
            e = -neg_e
            # spans of one thread nest: a later start inside this span
            # is a child of it
            leaf = k + 1 == len(evs) or evs[k + 1][0] >= e
            if e > lo and s < hi:
                spans.append((max(s, lo), min(e, hi), name, leaf))
    return spans


def idle_by_span(rows: list[tuple], n_devices: int) -> IdleSplit | None:
    """The idle split of a trace's rows over its first ``n_devices``
    TPUs, or None where ``bench.trace.reduce_rows`` finds nothing."""
    windows = [(s, e) for p, _, n, s, e in rows
               if p == trace.HOST_PLANE and n == trace.WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = windows[0]
    per_dev: dict[int, list] = {}
    for plane, line, _, s, e in rows:
        m = trace.DEVICE_PLANE.match(plane)
        if m and line == trace.OPS_LINE and int(m.group(1)) < n_devices:
            per_dev.setdefault(int(m.group(1)), []).append((s, e))
    if not per_dev:
        return None
    # per device, the busy seconds before each edge of its busy union
    busy = []
    for ops in per_dev.values():
        ivs = trace._union(trace._clip(ops, lo, hi))
        ends = [b for _, b in ivs]
        done = [0]
        for a, b in ivs:
            done.append(done[-1] + b - a)
        busy.append((ivs, ends, done))

    def busy_before(t: int) -> int:
        """Busy time in ``[lo, t]``, summed over devices."""
        total = 0
        for ivs, ends, done in busy:
            k = bisect.bisect_right(ends, t)
            total += done[k]
            if k < len(ivs) and ivs[k][0] < t:
                total += t - ivs[k][0]
        return total

    spans = _program_spans(rows, lo, hi)
    edges = sorted({lo, hi, *(s for s, _, _, _ in spans),
                    *(e for _, e, _, _ in spans)})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][0])
    n = len(per_dev)
    leaf_s: dict[str, float] = {}
    parent_s: dict[str, float] = {}
    untraced = 0.0
    open_: set[int] = set()
    nxt = 0
    prev_busy = 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(starts) and spans[starts[nxt]][0] <= a:
            open_.add(starts[nxt])
            nxt += 1
        open_ = {i for i in open_ if spans[i][1] > a}
        now_busy = busy_before(b)
        idle = ((b - a) * n - (now_busy - prev_busy)) / n / 1e9
        prev_busy = now_busy
        if not open_:
            untraced += idle
            continue
        # the innermost: started last, and of those the first to end
        *_, name, leaf = max((spans[i] for i in open_),
                             key=lambda sp: (sp[0], -sp[1]))
        into = leaf_s if leaf else parent_s
        into[name] = into.get(name, 0.0) + idle
        if not leaf:
            untraced += idle
    return IdleSplit(
        devices=n, window_s=(hi - lo) / 1e9,
        idle_s=((hi - lo) * n - busy_before(hi)) / n / 1e9,
        leaf_s=leaf_s, parent_s=parent_s, untraced_s=untraced,
        spans=len(spans))


def run_split(args, t_start: float, **harness_kw
              ) -> tuple[int, IdleSplit | None]:
    """``bench.harness.run`` of ``args`` (a ``--trace 1`` run), and the
    idle split of the trace it reads."""
    from bench import harness

    splits = []
    summarize = trace.summarize

    def summarize_and_split(trace_dir, n_devices):
        rows = trace.read_rows(trace_dir)
        splits.append(idle_by_span(rows, n_devices))
        return trace.reduce_rows(rows, n_devices)

    trace.summarize = summarize_and_split
    try:
        rc = harness.run(args, t_start, **harness_kw)
    finally:
        trace.summarize = summarize
    return rc, (splits[0] if splits else None)


def main(argv=None) -> int:
    from bench import run

    args = run.parse(argv)
    args.trace = 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    from bench import harness

    out = io.StringIO()
    try:
        rc, split = run_split(args, T_START, out=out)
    except harness.RunError as e:
        print(f"idle_split: {e}", file=sys.stderr)
        return 1
    sys.stdout.write(out.getvalue())
    if rc or split is None:
        print("idle_split: the run gave no traced window", file=sys.stderr)
        return rc or 1
    calls = json.loads(out.getvalue().splitlines()[-1])["attempted"]
    print(json.dumps({
        "idle_split_pct": split.shares(),
        "device_idle_share": 100.0 * split.idle_s / split.window_s,
        "parent_idle_pct": {k: 100.0 * v / split.window_s
                            for k, v in split.parent_s.items()},
        "program_spans_per_call": split.spans / calls}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips it asks for.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  A run builds the
cell's inputs from ``--seed``, warms every program the cell runs, then
calls the cell's simulator entry back to back for ``--seconds`` (one
caller, the next call after the last completes).  ``--trace 0`` reports
the end-to-end metrics; ``--trace 1`` profiles the window and reports
the per-layer metrics.  After the window every answer is checked
against the plain references in ``bench/reference``.

Standard error gets the per-call seconds, the simulated accuracy lines
and, last, each compared number beside its limit.  The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``checks``.  The run refuses to start, and prints no result, when
the first device is not a TPU or there are fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no simulator sources at {src}", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else under /tmp
    sys.path[:0] = [p for p in (src, ROOT) if p not in sys.path]
    from bench import harness

    try:
        return harness.run(args, T_START)
    except harness.RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())

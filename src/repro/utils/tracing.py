"""Named spans and counters of the host sweep layer.

A span is ``jax.profiler.TraceAnnotation``: while a profile is being
taken (``jax.profiler.trace``) each span is an event on the profile's
host plane, on the same clock as the device planes, so a device-idle
stretch can be blamed on the host phase that held the chip back.  With
no profile running a span costs about a microsecond, so spans are
always on.

A counter is a process-wide integer, added to with ``count`` from
values the host already holds (array shapes and plans), never from a
device array.  ``counters()`` returns a snapshot; the difference of two
snapshots is what ran between them.

Leaf spans, in the order one lane batch runs them:
``LANE_PLAN`` -> ``DISPATCH`` -> ``FETCH`` -> ``MISS_RUNS`` ->
``DRAM_ROWS``; a campaign then records its results (``RECORD``).  The
interference path plans its lanes (``LANE_PLAN``) and compacts them
(``COMPACT``) before its first lane batch.  A batch of compacted lanes
runs ``LANE_PLAN`` -> ``DISPATCH`` -> ``FETCH`` -> ``DISPATCH`` ->
``FETCH``: the record program, its hits, then the device reduction of
its lanes and their counts, with no ``MISS_RUNS`` or ``DRAM_ROWS``.
``COMPACT`` builds most such lanes' records from the NVDLA chunks
(``DIRECT_RECORD_LANES``), so ``LANE_PLAN`` expands only the rest.
``CAMPAIGN`` and ``LANE_BATCH`` are parents: they own only the time
their leaves leave.
"""
from __future__ import annotations

import functools
import threading

import jax

# parent spans
CAMPAIGN = "repro.campaign.run"        # one run_campaign call
LANE_BATCH = "repro.sweep.lane_batch"  # one lane bucket: one compiled program
# leaf spans
LANE_PLAN = "repro.sweep.lane_plan"    # numpy traces, round plans, padding
COMPACT = "repro.sweep.compact"        # lanes compacted into records
DISPATCH = "repro.sweep.dispatch"      # host-to-device copies, program enqueue
FETCH = "repro.sweep.fetch"            # wait for the program, copy to host
MISS_RUNS = "repro.sweep.miss_runs"    # one lane's missed-block runs
DRAM_ROWS = "repro.sweep.dram_rows"    # one lane's DRAM rows and latency
RECORD = "repro.campaign.record"       # guardrails, journal fsyncs, manifest
LEAVES = (LANE_PLAN, COMPACT, DISPATCH, FETCH, MISS_RUNS, DRAM_ROWS,
          RECORD)

# counters
FETCH_BYTES = "sweep.fetch_bytes"  # bytes of lane-program outputs fetched
SCAN_ROUNDS = "sweep.scan_rounds"  # serial round-scan steps dispatched
PROGRAMS = "sweep.programs"        # lane programs dispatched
MISS_WIDTH = "sweep.miss_width"    # miss-bit widths W of collecting programs
LANE_SEGMENTS = "sweep.lane_segments"          # records the programs scan
LANE_SEGMENTS_RAW = "sweep.lane_segments_raw"  # the same, uncompacted
DEVICE_REDUCED_LANES = "sweep.device_reduced_lanes"  # lanes counted on device
DIRECT_RECORD_LANES = "sweep.direct_record_lanes"  # records, trace unexpanded

_counts: dict[str, int] = {}
_lock = threading.Lock()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A context manager that marks its body as ``name`` in a profile."""
    return jax.profiler.TraceAnnotation(name)


def spanned(name: str):
    """A decorator that runs the function it decorates in ``span(name)``."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def count(name: str, n) -> None:
    """Add ``n`` to the process-wide counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def counters() -> dict[str, int]:
    """A copy of every counter of this process."""
    with _lock:
        return dict(_counts)

"""The program's own counters (``repro.utils.tracing.counters``), per
call of the cell.

A counter covers every call the process made, the warm-up calls
included; every call of a cell does the same work, so its total over
all the calls is the count of one call times their number."""
from __future__ import annotations


def per_call(run, name: str) -> float | None:
    """The counter ``name`` per call of ``run``, or None where the
    program keeps no such counter."""
    try:
        from repro.utils.tracing import counters
    except ImportError:
        return None
    from bench.harness import WARMUP_CALLS

    total = counters().get(name)
    calls = run.calls + WARMUP_CALLS
    return None if total is None or not run.calls else total / calls

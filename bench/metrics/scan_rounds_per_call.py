"""Serial round-scan steps of the lane programs dispatched per call:
the simulator's ``sweep.scan_rounds`` counter, summed over segments of
the largest round count of any lane."""
from bench.counters import per_call


def read(run):
    return per_call(run, "sweep.scan_rounds")

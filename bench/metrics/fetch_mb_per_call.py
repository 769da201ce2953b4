"""MB (10**6 bytes) of lane-program outputs the simulator copies back
to the host per call: its ``sweep.fetch_bytes`` counter."""
from bench.counters import per_call


def read(run):
    total = per_call(run, "sweep.fetch_bytes")
    return None if total is None else total / 1e6

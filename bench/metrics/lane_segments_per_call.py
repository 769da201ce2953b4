"""Records the interference lane programs scan per call, summed over
their lanes, after compaction and before padding: the simulator's
``sweep.lane_segments`` counter (``sweep.lane_segments_raw`` counts the
same lanes uncompacted)."""
from bench.counters import per_call


def read(run):
    return per_call(run, "sweep.lane_segments")

"""Records the way-partitioned lanes scan per call, summed over those
lanes, before padding: the simulator's ``sweep.partitioned_lane_segments``
counter (``sweep.partitioned_record_lanes`` counts the lanes)."""
from bench.counters import per_call


def read(run):
    return per_call(run, "sweep.partitioned_lane_segments")

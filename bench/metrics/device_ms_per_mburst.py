"""Device busy time of all the cell's chips, summed, per million
simulated bursts of the traced window."""


def read(run):
    t = run.trace
    if t is None or not t.busy_s or not run.calls:
        return None
    mbursts = run.calls * run.bursts_per_call / 1e6
    return 1e3 * t.busy_s * t.devices / mbursts

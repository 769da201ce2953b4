"""The largest ``peak_bytes_in_use`` of the cell's chips after the
window, in MB (10**6 bytes)."""


def read(run):
    peak = max(run.peak_bytes, default=0)
    return peak / 1e6 if peak else None

"""DRAM open-row bookkeeping, per access.

Row ``addr // row_bytes`` lives in bank ``row % banks``.  Every bank
starts closed.  An access is a row hit when its bank's open row is its
own row; either way its row is then the bank's open row.  So an access
hits exactly when the previous access to the same bank was to the same
row, which is how it is computed here.
"""
from __future__ import annotations

import numpy as np


def row_hits(byte_addrs, *, banks: int, row_bytes: int) -> np.ndarray:
    """Per-access row-hit bits of the access sequence ``byte_addrs``."""
    rows = np.asarray(byte_addrs, np.int64) // row_bytes
    bank = rows % banks
    order = np.argsort(bank, kind="stable")
    b, r = bank[order], rows[order]
    hit_sorted = np.zeros(rows.shape, bool)
    hit_sorted[1:] = (b[1:] == b[:-1]) & (r[1:] == r[:-1])
    out = np.empty(rows.shape, bool)
    out[order] = hit_sorted
    return out

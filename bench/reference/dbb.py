"""The NVDLA's DBB traffic as compressed (base, stride, count) streams,
built from the configuration's per-op traffic table alone.

Each op streams, once per weight pass: its weight set, then its share of
the input feature map, then its share of the output feature map, each
as consecutive 32-byte bursts.  The weight sets of the frame's ops lie
back to back from the weight region in layer order; an op reads its
input from one feature-map region and writes its output to the other,
the two swapping from op to op.

The DBB arbiter interleaves an op's weight, input and output streams
round robin in ``chunk`` bursts; a window is the first ``max_bursts``
bursts of that interleaving.
"""
from __future__ import annotations

import numpy as np

STREAMS = ("weight", "ifmap", "ofmap")


def _bursts(n_bytes: int, burst: int) -> int:
    return -(-n_bytes // burst)


def op_streams(op, weight_base: int, ifmap_base: int, ofmap_base: int, *,
               burst: int) -> list[tuple[str, int, int]]:
    """One op's ``[weight, ifmap, ofmap, passes]`` row as (stream, base,
    bursts) pieces in issue order."""
    weight, ifmap, ofmap, passes = (int(v) for v in op)
    passes = max(1, passes)
    out, i_done, o_done = [], 0, 0
    for p in range(passes):
        if weight // passes:
            out.append(("weight", weight_base,
                        _bursts(weight // passes, burst)))
        i_next, o_next = ifmap * (p + 1) // passes, ofmap * (p + 1) // passes
        if i_next > i_done:
            out.append(("ifmap", ifmap_base + i_done,
                        _bursts(i_next - i_done, burst)))
        if o_next > o_done:
            out.append(("ofmap", ofmap_base + o_done,
                        _bursts(o_next - o_done, burst)))
        i_done, o_done = i_next, o_next
    return out


def frame(ops, weight_base: int, fmap_a: int, fmap_b: int, *, burst: int):
    """The whole frame: (base, stride, count) arrays and each segment's
    op index."""
    rows, op_of, cursor = [], [], weight_base
    regions = (fmap_a, fmap_b)
    for i, op in enumerate(ops):
        for _, base, n in op_streams(op, cursor, regions[i % 2],
                                     regions[(i + 1) % 2], burst=burst):
            rows.append((base, burst, n))
            op_of.append(i)
        cursor += int(op[0]) // max(1, int(op[3]))
    a = np.asarray(rows, np.int64).reshape(-1, 3)
    return (a[:, 0], a[:, 1], a[:, 2]), np.asarray(op_of, np.int64)


def window(op, weight_base: int, ifmap_base: int, ofmap_base: int, *,
           burst: int, chunk: int, max_bursts: int):
    """One op's arbiter-interleaved window as (base, stride, count)."""
    queues = {s: [] for s in STREAMS}
    for stream, base, n in op_streams(op, weight_base, ifmap_base,
                                      ofmap_base, burst=burst):
        for j in range(0, n, chunk):
            queues[stream].append((base + j * burst, burst, min(chunk, n - j)))
    order = [q for q in queues.values() if q]
    rows, left = [], max_bursts
    for k in range(max(map(len, order), default=0)):
        for q in order:
            if k < len(q) and left > 0:
                base, stride, n = q[k]
                rows.append((base, stride, min(n, left)))
                left -= rows[-1][2]
    a = np.asarray(rows, np.int64).reshape(-1, 3)
    return a[:, 0], a[:, 1], a[:, 2]

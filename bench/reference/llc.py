"""A set-associative LLC, one scan step per access.

The plain semantics the simulator promises: a byte address maps to
block ``addr // block_bytes``, set ``block % sets`` and tag
``block // sets``.  A hit is a tag match in any way of the set.  A miss
allocates (reads and writes alike) into the least recently used way;
ways never filled are taken first, lowest index first.  Every access
is one step: nothing is compressed, planned or closed-form.

``policy="fifo"`` is the control: a hit does not refresh the way's
recency, so eviction follows insertion order.  It breaks the LRU
guarantee the configurations state, and the comparison must catch it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

POLICIES = ("lru", "fifo")


@functools.partial(jax.jit, static_argnames=("sets", "ways", "fifo"))
def _scan(set_idx, tag, *, sets: int, ways: int, fifo: bool):
    def step(carry, x):
        tags, last = carry                      # (sets, ways) each
        s, t, k = x
        row_tags, row_last = tags[s], last[s]
        match = row_tags == t
        hit = jnp.any(match)
        victim = jnp.argmin(row_last)
        way = jnp.where(hit, jnp.argmax(match), victim)
        stamp = jnp.where(hit & fifo, row_last[way], k)
        return (tags.at[s, way].set(t), last.at[s, way].set(stamp)), hit

    init = (jnp.full((sets, ways), -1, jnp.int32),
            jnp.zeros((sets, ways), jnp.int32))
    stamps = jnp.arange(1, set_idx.shape[0] + 1, dtype=jnp.int32)
    _, hits = jax.lax.scan(step, init, (set_idx, tag, stamps))
    return hits


def hits(byte_addrs, *, sets: int, ways: int, block_bytes: int,
         policy: str = "lru") -> np.ndarray:
    """Per-access hit bits of ``byte_addrs`` on a cold cache."""
    if policy not in POLICIES:
        raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
    blocks = np.asarray(byte_addrs, np.int64) // block_bytes
    if blocks.size == 0:
        return np.zeros(0, bool)
    if blocks.min() < 0 or blocks.max() // sets > np.iinfo(np.int32).max:
        raise ValueError("block addresses out of range for int32 tags")
    if blocks.size >= np.iinfo(np.int32).max:
        raise ValueError("trace too long for int32 recency stamps")
    out = _scan(jnp.asarray(blocks % sets, jnp.int32),
                jnp.asarray(blocks // sets, jnp.int32),
                sets=int(sets), ways=int(ways),
                fifo=policy == "fifo")
    return np.asarray(out, bool)

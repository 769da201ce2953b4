"""Runtime environment control: compile cache, x64 precision and
address dtypes.

JAX disables 64-bit types by default; an ``jnp.asarray(x, jnp.int64)``
then *silently* truncates to int32.  For DBB byte addresses that is a
correctness hazard the moment an address crosses 2^31 (an 8 GiB DRAM
map does).  Three tools:

* ``use_compile_cache`` — turn on JAX's persistent compilation cache at
  a fixed place; every entry point calls it before its first compile;
* ``jax_enable_x64`` — flip the global precision switch (call it at
  program start, before any array is built; benchmarks and scripts that
  replay full-frame traces should call it);
* ``as_address_array`` / ``address_dtype`` — build address arrays that
  are int64 under x64 and otherwise int32 *with an explicit overflow
  check*, so truncation can never be silent.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

# <checkout>/.jax_cache: src/repro/utils/env.py is three levels below
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here.  Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE``, a fixed directory inside the checkout: a
    directory that moved from run to run (a tempdir, a pid) would never
    be hit again.  Call before the first compile; importing this module
    changes nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def jax_enable_x64(use_x64: bool = True) -> None:
    """Changes the default precision of arrays in JAX.

    When `use_x64` is True, JAX arrays use 64 bits, else 32 bits.  A
    False argument defers to the ``JAX_ENABLE_X64`` environment
    variable (so scripts can force precision without code changes).
    Call before building any array — flipping mid-program leaves
    already-created arrays at their old width.
    """
    if not use_x64:
        use_x64 = bool(int(os.getenv("JAX_ENABLE_X64", "0")))
    jax.config.update("jax_enable_x64", bool(use_x64))


def x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def address_dtype():
    """Widest integer dtype currently available for byte addresses."""
    return jnp.int64 if x64_enabled() else jnp.int32


def as_address_array(x, *, what: str = "address") -> jax.Array:
    """Build an address array without silent truncation.

    Under x64 this is a plain int64 array.  Without x64 the values are
    range-checked against int32 before the (lossless) narrowing; out-of
    -range addresses raise instead of wrapping.
    """
    arr = np.asarray(x, np.int64)
    if x64_enabled():
        return jnp.asarray(arr, jnp.int64)
    info = np.iinfo(np.int32)
    if arr.size and (int(arr.max()) > info.max or int(arr.min()) < info.min):
        raise OverflowError(
            f"{what} values exceed int32 range and jax_enable_x64 is off; "
            "call repro.utils.env.jax_enable_x64(True) at program start")
    return jnp.asarray(arr, jnp.int32)

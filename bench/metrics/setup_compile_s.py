"""Seconds of backend compiles, loads from the persistent cache
included, during set-up (JAX's compile-duration events)."""


def read(run):
    return run.setup_compile_s

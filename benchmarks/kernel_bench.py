"""Kernel micro-benchmarks: wall time of the jnp reference paths on CPU
(+ analytic TPU-roofline projections for the Pallas kernels).

Pallas interpret mode is a correctness harness, not a performance one —
wall-clock timing happens on the jnp reference path (what XLA:CPU makes
of the same math), while the projected TPU numbers come from the kernels'
FLOP counts against the v5e peaks of ``repro.launch.mesh.CHIP_PEAKS``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.convcore.ref import matmul_int8_ref
from repro.kernels.swa.ref import swa_attention_ref
from repro.launch.mesh import TARGET_KIND, chip_peaks


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))     # one warmup call (works on pytrees)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def run(smoke: bool = False) -> list[tuple]:
    peaks = chip_peaks(TARGET_KIND)
    rows = []
    # convcore GEMM: a darknet-53 mid layer as GEMM (52*52 x 1152 x 256)
    m, k, n = (338, 576, 128) if smoke else (2704, 1152, 256)
    a = jax.random.randint(jax.random.PRNGKey(0), (m, k), -127, 128, jnp.int8)
    b = jax.random.randint(jax.random.PRNGKey(1), (k, n), -127, 128, jnp.int8)
    scale = jnp.ones((n,), jnp.float32)
    bias = jnp.zeros((n,), jnp.float32)
    f = jax.jit(lambda a, b: matmul_int8_ref(a, b, scale, bias))
    dt = _time(f, a, b)
    flops = 2 * m * k * n
    rows.append(("kernel/convcore_gemm_cpu_us", round(dt * 1e6, 1),
                 f"{flops/dt/1e9:.1f} GOP/s on CPU ref"))
    rows.append(("kernel/convcore_gemm_tpu_projected_us",
                 round(flops / peaks.int8_ops * 1e6, 2), "v5e int8 roofline"))

    # swa attention: one mixtral-ish head block
    bh, s, d, w = (2, 256, 64, 128) if smoke else (8, 1024, 128, 256)
    q = jax.random.normal(jax.random.PRNGKey(2), (bh, s, d), jnp.float32)
    kk = jax.random.normal(jax.random.PRNGKey(3), (bh, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(4), (bh, s, d), jnp.float32)
    g = jax.jit(lambda q, k, v: swa_attention_ref(q, k, v, window=w))
    dt = _time(g, q, kk, v)
    # banded flops: 2 matmuls * 2 flops * bh * s * w * d
    fl = 4 * bh * s * w * d
    rows.append(("kernel/swa_cpu_us", round(dt * 1e6, 1),
                 f"banded {fl/dt/1e9:.1f} GFLOP/s on CPU ref"))
    rows.append(("kernel/swa_tpu_projected_us",
                 round(fl / peaks.bf16_flops * 1e6, 2), "v5e bf16 roofline"))
    return rows

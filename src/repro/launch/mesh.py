"""Production mesh construction.

Meshes are built by FUNCTIONS (never module-level constants) so importing
this module does not touch jax device state — required because the dry-run
must set ``XLA_FLAGS`` before the first jax device query.

Production topology (TPU v5e-like):
* single pod:  (16, 16)    -> ("data", "model")   256 chips
* multi-pod:   (2, 16, 16) -> ("pod", "data", "model")  512 chips

Axis semantics (see repro.sharding for the full rule table):
* ``model`` — tensor parallel: heads / mlp / vocab shard here; intra-pod,
  highest-bandwidth dimension.
* ``data`` — batch data parallel + parameter FSDP (weights' d_model dims
  shard over data+pod, ZeRO-3 style).
* ``pod``  — a second data-parallel axis across pods; gradient reduction
  over this axis crosses the slowest links (where the int8 compression
  codec applies).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    return jax.sharding.Mesh(
        np.asarray(devices[:n]).reshape(shape), axes)


def make_sweep_mesh(devices=None):
    """1-D campaign-sweep mesh: every available device along a single
    ``"points"`` axis.  The sweep engine
    (``repro.core.sweep.interference_lane_metrics_batch``) shards its
    lane axis over it, so each device simulates an equal slice of a
    point batch — the run-farm analogue FireSim scales Fig. 5/6 with.

    ``devices=None`` uses all of ``jax.devices()``.  On a CPU-only host
    that is one device unless ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` was exported before the first jax import (how
    tests and CI fan out to N lanes); a single-device mesh is valid —
    it just runs the whole batch on that device."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if not devices:
        raise RuntimeError(
            "no jax devices visible — on a CPU host export "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "any jax import to fan the sweep mesh out to N lanes")
    return jax.sharding.Mesh(np.asarray(devices), ("points",))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over the real local devices (tests / examples)."""
    devices = jax.devices()
    n = len(devices)
    mp = max(1, min(model_parallel, n))
    dp = n // mp
    return jax.sharding.Mesh(
        np.asarray(devices[: dp * mp]).reshape(dp, mp), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator model."""
    bf16_flops: float      # FLOP/s
    int8_ops: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: int
    ici_bw: float          # bytes/s per interconnect link


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4 links.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bw=819e9, hbm_bytes=16 * 10**9,
                             ici_bw=1600e9 / 8 / 4),
}
# the chip the production meshes above describe
TARGET_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; a chip missing from ``CHIP_PEAKS``
    is an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}"
                       ) from None

"""Campaign throughput: sequential vs mesh-sharded batched execution.

The tentpole before/after: the same campaign spec run twice through
``run_campaign`` — once strictly sequentially (``batch_points=1``, the
pre-mesh executor), once as vmapped lane batches sharded over a
``jax.sharding`` mesh of every visible device — with the resulting
manifests asserted byte-identical (the mesh path is exact, not an
approximation).  The mesh spans every device JAX sees; with a single
device the run measures the vmapped-batching win alone.
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (exported before
Python starts) fans out four *virtual CPU* devices: it rehearses the
mesh path on a CPU host and says nothing about accelerator speed.

Full mode is the acceptance campaign: 64 points (16 same-``sets``
geometries x 4 co-runner mixes) on a 16384-burst window, target >= 5x
points/sec with 4 devices.  Smoke is 16 points on a 256-burst window.

Emits ``BENCH_campaign.json`` (override with ``BENCH_CAMPAIGN_JSON``)
so CI can archive the campaign-throughput trajectory.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time


def _acceptance_spec(points: int, window_bursts: int):
    from repro.campaign import (
        CampaignSpec,
        GeometrySpec,
        MixSpec,
        ModelSpec,
    )

    n_mixes = 4
    n_geoms = points // n_mixes
    # one vmap bucket (same set count) of low-associativity lanes:
    # ways x block combinations keep the padded way axis at 4 and every
    # co-runner span >= one chunk, so the lane programs stay dense
    sets = 16
    blocks = (128, 256, 512, 1024)
    geoms = tuple(GeometrySpec(size_kib=sets * w * b / 1024,
                               block=b, ways=w)
                  for b in blocks for w in range(1, n_geoms // len(blocks) + 1))
    mixes = (MixSpec(0, "l1"), MixSpec(1, "llc"),
             MixSpec(2, "llc"), MixSpec(2, "dram"))
    return CampaignSpec(
        name=f"bench-{points}pt",
        models=(ModelSpec(window_bursts=window_bursts),),
        geometries=geoms, mixes=mixes)


def run(smoke: bool = False) -> list[tuple]:
    import jax

    from repro.campaign import run_campaign
    from repro.launch.mesh import make_sweep_mesh

    points, window = (16, 256) if smoke else (64, 16384)
    spec = _acceptance_spec(points, window)
    mesh = make_sweep_mesh(jax.devices())
    n_dev = len(mesh.devices.ravel())

    def campaign(out_dir, **kw):
        t0 = time.perf_counter()
        res = run_campaign(spec, out_dir, **kw)
        dt = time.perf_counter() - t0
        assert res.completed == points and not res.failed, res.manifest
        return res, dt

    work = tempfile.mkdtemp(prefix="campaign_bench_")
    try:
        # warm the lane-engine compile caches so both sides time
        # simulation + journaling, not XLA compilation
        campaign(os.path.join(work, "warm_seq"), batch_points=1)
        campaign(os.path.join(work, "warm_mesh"), mesh=mesh,
                 batch_points=points)

        seq, t_seq = campaign(os.path.join(work, "seq"), batch_points=1)
        msh, t_mesh = campaign(os.path.join(work, "mesh"), mesh=mesh,
                               batch_points=points)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    canon = lambda m: json.dumps(m, sort_keys=True)
    assert canon(seq.manifest) == canon(msh.manifest), \
        "mesh campaign manifest diverged from sequential"

    speedup = t_seq / t_mesh
    rows = [
        ("campaign/points", points, f"{window}-burst window"),
        ("campaign/devices", n_dev,
         f"{jax.devices()[0].platform} devices in the sweep mesh"),
        ("campaign/seq_pts_per_s", round(points / t_seq, 2),
         "batch_points=1, journaled"),
        ("campaign/mesh_pts_per_s", round(points / t_mesh, 2),
         "vmapped lane batches over the device mesh, journaled"),
        ("campaign/mesh_speedup_x", round(speedup, 1),
         "target >= 5x at 4 devices, bit-identical manifests"
         if not smoke else "smoke grid"),
    ]

    doc = {
        "generated_by": "benchmarks/campaign_bench.py",
        "smoke": smoke,
        "points": points,
        "window_bursts": window,
        "devices": n_dev,
        "seq_pts_per_s": round(points / t_seq, 3),
        "mesh_pts_per_s": round(points / t_mesh, 3),
        "speedup_x": round(speedup, 2),
        "manifests_identical": True,
    }
    path = os.environ.get("BENCH_CAMPAIGN_JSON", "BENCH_campaign.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    rows.append(("campaign/bench_json", path, "machine-readable metrics"))
    return rows


if __name__ == "__main__":
    import sys

    from repro.utils.env import use_compile_cache

    use_compile_cache()
    print("name,value,note")
    for row in run(smoke="--smoke" in sys.argv):
        print(",".join(str(x) for x in row))

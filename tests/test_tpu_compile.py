"""Compile-only tests for a described TPU v5e chip: the kernels and lane
programs of the simulator's main path, at the sizes ``chip_smoke.py``
runs them, must be accepted by the chip's compiler and fit its memory.

Nothing runs: this proves the compiler takes the programs, not that
they give the right answers on the chip (``chip_smoke.py`` does that).
The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU compiler."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sweep
from repro.launch.mesh import chip_peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def hbm_bytes(topo):
    return chip_peaks(topo.devices[0].device_kind).hbm_bytes


def _on_chip(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _fits(compiled, hbm_bytes) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0 < total < hbm_bytes
    return total


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (173056, 128, 128, 512, 128, 128),   # darknet layer 0, padded im2col
    (2048, 4608, 1024, 512, 256, 512),   # a deep darknet layer
], ids=["layer0", "deep"])
def test_matmul_int8_kernel_compiles(one_chip, hbm_bytes, m, k, n, bm, bn,
                                     bk):
    from repro.kernels.convcore.kernel import matmul_int8_kernel

    f = jax.jit(lambda a, b, s, c: matmul_int8_kernel(
        a, b, s, c, bm=bm, bn=bn, bk=bk, relu=True, out_dtype=jnp.float32))
    compiled = f.lower(_on_chip(one_chip, (m, k), jnp.int8),
                       _on_chip(one_chip, (k, n), jnp.int8),
                       _on_chip(one_chip, (n,), jnp.float32),
                       _on_chip(one_chip, (n,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, hbm_bytes)


def test_postprocess_kernel_compiles(one_chip, hbm_bytes):
    from repro.kernels.postproc.kernel import postprocess_kernel

    f = jax.jit(lambda x, s, b: postprocess_kernel(
        x, s, b, act="none", pool=2, out_dtype=jnp.float32))
    compiled = f.lower(_on_chip(one_chip, (1, 416, 416, 64), jnp.float32),
                       _on_chip(one_chip, (64,), jnp.float32),
                       _on_chip(one_chip, (64,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled, hbm_bytes)


class _Captured(Exception):
    pass


def _first_lane_program(monkeypatch, run) -> tuple:
    """Run ``run`` up to its first lane-program call and return that
    program's static arguments and operand shapes; the program itself
    never runs.  Buckets go in descending set count, so the first call
    is the widest program."""
    real = sweep._lane_engine
    got = {}

    def engine(*static, **kw):
        def call(*args):
            got.update(static=static, kw=kw,
                       shapes=[(np.shape(a), np.asarray(a).dtype)
                               for a in args])
            raise _Captured
        return call

    monkeypatch.setattr(sweep, "_lane_engine", engine)
    with pytest.raises(_Captured):
        run()
    return real(*got["static"], **got["kw"]), got["shapes"]


def _benchmarks(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    sys.modules.pop("benchmarks", None)


def test_campaign_lane_engine_compiles(monkeypatch, one_chip, hbm_bytes):
    """The 64-point, 16384-burst acceptance campaign as one batch: a
    64-lane program with miss-bit collection."""
    _benchmarks(monkeypatch)
    from benchmarks.campaign_bench import _acceptance_spec
    from repro.campaign.executor import run_batch

    points = _acceptance_spec(64, 16384).expand()
    engine, shapes = _first_lane_program(
        monkeypatch, lambda: run_batch(points, points[0].model.trace()))
    assert shapes[0][0][0] == 64
    compiled = engine.lower(
        *(_on_chip(one_chip, s, d) for s, d in shapes)).compile()
    _fits(compiled, hbm_bytes)


def test_full_frame_fig5_lane_engine_compiles(monkeypatch, one_chip,
                                              hbm_bytes):
    """The whole YOLOv3 frame's trace over the widest Fig. 5 bucket
    (4096 KiB, 16384 sets) at the real segment count."""
    _benchmarks(monkeypatch)
    from benchmarks.fig5_llc import frame_grid

    _, per_op, _, cfgs = frame_grid()
    flat = [seg for segs in per_op for seg in segs]
    engine, shapes = _first_lane_program(
        monkeypatch, lambda: sweep.segment_lane_hit_counts(flat, cfgs))
    assert shapes[0][0] == (len(flat),)
    compiled = engine.lower(
        *(_on_chip(one_chip, s, d) for s, d in shapes)).compile()
    _fits(compiled, hbm_bytes)


def test_full_frame_record_engine_compiles(monkeypatch, one_chip,
                                           hbm_bytes):
    """The whole YOLOv3 frame beside 0-4 dram-class co-runners, its five
    lanes compacted into records: the program the ``fig6-frame-dram``
    cell runs, at its real record count."""
    from repro.campaign import CampaignSpec, GeometrySpec, MixSpec, ModelSpec
    from repro.campaign.executor import run_batch

    points = CampaignSpec(
        name="frame", models=(ModelSpec(window_bursts=None),),
        geometries=(GeometrySpec(size_kib=2048, block=64, ways=8),),
        mixes=tuple(MixSpec(n, "dram") for n in range(5))).expand()
    real, got = sweep._record_engine, {}

    def engine(*static):
        def call(*args):
            got.update(static=static,
                       shapes=[(np.shape(a), np.asarray(a).dtype)
                               for a in args])
            raise _Captured
        return call

    monkeypatch.setattr(sweep, "_record_engine", engine)
    with pytest.raises(_Captured):
        run_batch(points, points[0].model.trace())
    lanes, records, members = got["shapes"][0][0]
    assert (lanes, members) == (5, 5) and records < 1000
    compiled = real(*got["static"]).lower(
        *(_on_chip(one_chip, s, d) for s, d in got["shapes"])).compile()
    _fits(compiled, hbm_bytes)


def test_full_frame_row_reduction_compiles(monkeypatch, one_chip,
                                           hbm_bytes):
    """The device reduction of the same five compacted frame lanes
    (``sweep._record_rows``) at their real unit and visit counts, fed
    the record engine's hit codes.  Every member is taken to have round
    scan hits, more than the frame has (about 1.05 M of them, all but
    about 0.4 M in the solo lane's plain records)."""
    from repro.campaign import CampaignSpec, GeometrySpec, MixSpec, ModelSpec
    from repro.campaign.executor import run_batch
    from repro.core.dram import DRAMConfig

    points = CampaignSpec(
        name="frame", models=(ModelSpec(window_bursts=None),),
        geometries=(GeometrySpec(size_kib=2048, block=64, ways=8),),
        mixes=tuple(MixSpec(n, "dram") for n in range(5))).expand()
    got = {}

    def program(recs, cfgs_b):
        got.update(recs=recs, cfgs=cfgs_b)
        raise _Captured

    monkeypatch.setattr(sweep, "_record_program", program)
    with pytest.raises(_Captured):
        run_batch(points, points[0].model.trace())
    recs, cfgs = got["recs"], got["cfgs"]
    s_pad = max(r.raw.shape[0] for r in recs)
    n_mem = max(r.members for r in recs)
    r_pad = max(int(sweep._record_rounds(r, c).max()) for r, c
                in zip(recs, cfgs))
    hits = np.zeros((len(recs), s_pad, n_mem), np.int64)
    for row, r in enumerate(recs):
        k, p = r.counts.shape
        hits[row, :k, 0] = r.counts[:, 0]     # every NVDLA member hit
    plan = sweep._record_rows_plan(recs, cfgs, [DRAMConfig()] * len(recs),
                                   hits, r_pad, 8)
    n_units, width = plan.static[:2]
    assert n_units >= 1923892 and width == 2
    codes = (len(recs), s_pad, r_pad, max(c.sets for c in cfgs))
    compiled = sweep._rows_engine(*plan.static).lower(
        _on_chip(one_chip, codes, jnp.int8),
        *(_on_chip(one_chip, a.shape, a.dtype) for a in plan.arrays)
    ).compile()
    _fits(compiled, hbm_bytes)

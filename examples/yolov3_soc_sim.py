"""YOLOv3 on the NVDLA/RISC-V SoC model — the paper's full case study.

Three parts:
1. the command stream (the accel/CPU split of all 107 layers),
2. a *numeric* int8 inference of darknet's first YOLO stage through
   the compiled convcore + postproc Pallas kernels at the network's
   416x416 input (64x64 in interpret mode) — validating the computation
   the perf model accounts for,
3. the three performance experiments (Figs 4/5/6).

Run on a TPU:  PYTHONPATH=src python examples/yolov3_soc_sim.py
On a CPU the Pallas kernels run only in interpret mode, and slowly:
PYTHONPATH=src JAX_PLATFORMS=cpu python examples/yolov3_soc_sim.py --interpret
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    interference_sweep,
    llc_sweep,
    platform_table,
    run_yolov3,
)
from repro.core.quant import calibrate, quantize, quantize_conv_weights
from repro.core.runtime import compile_network
from repro.core.yolov3 import LAYERS, total_gops
from repro.kernels.convcore import conv2d_int8
from repro.kernels.convcore.ref import conv2d_int8_ref
from repro.kernels.postproc import postprocess
from repro.utils.env import use_compile_cache


def show_command_stream():
    stream = compile_network()
    print(f"YOLOv3-416: {len(LAYERS)} layers, {total_gops():.1f} GOP/frame")
    print(f"  accelerator ops: {len(stream.accel_ops)} "
          f"(convs+shortcuts), traffic {stream.accel_traffic/1e6:.0f} MB")
    print(f"  cpu ops:         {len(stream.cpu_ops)} "
          f"(upsample/route/yolo/casts)")
    heavy = max(stream.accel_ops, key=lambda op: op.macs)
    print(f"  heaviest conv: layer {heavy.layer.index} "
          f"{heavy.layer.h}x{heavy.layer.w}x{heavy.layer.cin}"
          f"->{heavy.layer.cout}, {heavy.macs/1e9:.2f} GMAC, "
          f"{heavy.weight_passes} weight pass(es)")


def numeric_int8_stage(*, interpret: bool = False) -> list[float]:
    """Run darknet's first two conv layers numerically in int8 on the
    convcore kernel over the network's 416x416 input, and return each
    layer's largest kernel-vs-reference error.  ``interpret`` runs the
    Pallas kernels in interpret mode, the only way they run on a CPU,
    over a 64x64 input."""
    print("\nnumeric int8 stage (convcore + postproc kernels):")
    hw = 64 if interpret else 416
    errs = []
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, hw, hw, 3), jnp.float32)
    sx = calibrate(x)
    xq = quantize(x, sx)
    acc = xq
    for i, (cout, k, stride) in enumerate([(32, 3, 1), (64, 3, 2)]):
        kw = jax.random.fold_in(key, i)
        w = jax.random.normal(kw, (k, k, acc.shape[-1], cout)) * 0.1
        wq, sw = quantize_conv_weights(w)
        scale = sx * sw
        out = conv2d_int8(acc, wq, scale, jnp.zeros((cout,)), stride=stride,
                          padding=1, relu=True, out_dtype=jnp.float32,
                          interpret=interpret)
        ref = conv2d_int8_ref(acc, wq, scale, jnp.zeros((cout,)),
                              stride=stride, padding=1, relu=True,
                              out_dtype=jnp.float32)
        err = float(jnp.max(jnp.abs(out - ref)))
        errs.append(err)
        print(f"  conv{i}: {acc.shape} -> {out.shape}, kernel==ref err {err:.2e}")
        sx = calibrate(out)
        acc = quantize(out, sx)
    pooled = postprocess(out, jnp.ones((out.shape[-1],)),
                         jnp.zeros((out.shape[-1],)), act="none", pool=2,
                         interpret=interpret)
    print(f"  postproc 2x2 maxpool: {out.shape} -> {pooled.shape}")
    return errs


def performance_experiments():
    print("\nperformance experiments:")
    t = platform_table()
    for k, v in t.items():
        if k != "_meta":
            print(f"  {k:28s} {v:8.3f} fps")
    m = t["_meta"]
    print(f"  NVDLA split: {m['nvdla_accel_ms']:.1f} ms accel + "
          f"{m['nvdla_cpu_ms']:.1f} ms cpu (paper: 67 + 66)")

    sw = llc_sweep(sizes_kib=(0.5, 64, 1024, 4096), blocks=(32, 64, 128))
    print("  LLC speedup grid (vs no LLC):")
    for (size, block), sp in sorted(sw["grid"].items()):
        print(f"    {size:7.1f} KiB / {block:3d} B : {sp:.3f}x")

    isw = interference_sweep()
    print("  interference (normalized NVDLA time):")
    for wss in ("l1", "llc", "dram"):
        row = "  ".join(f"{isw[wss][n]:.2f}" for n in (0, 1, 2, 3, 4))
        print(f"    WSS={wss:4s}: {row}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode, at "
                         "64x64 (CPU)")
    args = ap.parse_args()
    use_compile_cache()
    show_command_stream()
    numeric_int8_stage(interpret=args.interpret)
    performance_experiments()


if __name__ == "__main__":
    main()

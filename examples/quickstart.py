"""Quickstart: the three things this framework does, in 60 seconds on CPU.

1. reproduce the paper's headline result (NVDLA running YOLOv3 behind a
   shared LLC: fps, LLC block-size effect, co-runner interference);
2. train a small LM with the production train step (any of the ten
   assigned architectures — here qwen2's reduced config);
3. serve it with batched prefill+decode.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.configs import get_smoke_config
from repro.core import interference_sweep, llc_sweep, run_yolov3
from repro.data.synthetic import SyntheticStream
from repro.models import init_params
from repro.serve import Request, ServeEngine
from repro.train import AdamWConfig, init_train_state, make_train_step
from repro.types import param_values
from repro.utils.env import use_compile_cache


def paper_experiments():
    print("== paper: NVDLA + RISC-V SoC on FireSim ==")
    r = run_yolov3()
    print(f"YOLOv3-416: accel {r.accel_s*1e3:.1f} ms + cpu {r.cpu_s*1e3:.1f} ms"
          f" -> {r.fps:.2f} fps   (paper: 67 ms + 66 ms -> 7.5 fps)")
    sw = llc_sweep(sizes_kib=(1024,), blocks=(32, 64, 128))
    sp = {b: sw["grid"][(1024, b)] for b in (32, 64, 128)}
    print(f"LLC 1 MiB speedup by block size: 32B {sp[32]:.2f}x  "
          f"64B {sp[64]:.2f}x  128B {sp[128]:.2f}x   (paper: 1.01/1.25/1.51)")
    isw = interference_sweep(corunners=(0, 4))
    print(f"4 BwWrite co-runners: LLC-WSS {isw['llc'][4]:.2f}x, "
          f"DRAM-WSS {isw['dram'][4]:.2f}x slowdown  (paper: 2.1x / 2.5x)")


def train_small_lm(steps=20):
    print("\n== train: qwen2 (reduced) ==")
    cfg = get_smoke_config("qwen2-0.5b")
    params = param_values(init_params(jax.random.PRNGKey(0), cfg))
    state = init_train_state(params)
    step_fn = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=3e-3, warmup_steps=5, decay_steps=100)))
    stream = SyntheticStream(cfg, global_batch=4, seq_len=64)
    for i in range(steps):
        state, m = step_fn(state, stream.batch_at(i))
        if i % 5 == 0:
            print(f"  step {i:3d}  loss {float(m['loss']):.3f}")
    return cfg, state


def serve_small_lm(cfg, state):
    print("\n== serve: continuous batching on the simulated SoC clock ==")
    import numpy as np

    eng = ServeEngine(cfg, state.params, cache_len=128, max_slots=2,
                      eos_id=0)
    rng = np.random.default_rng(7)
    for i in range(4):
        eng.submit(Request(
            rid=i, tokens=tuple(int(t) for t in
                                rng.integers(3, cfg.vocab_size, 32)),
            max_new=16, arrival_s=i * 1e-4))
    stats = eng.run()
    print(f"  served {stats.requests} requests / {stats.tokens} tokens "
          f"in {stats.steps} steps")
    print(f"  simulated: {stats.tokens_per_s:.0f} tok/s, "
          f"p50 {stats.latency_p50_s * 1e3:.3f} ms, "
          f"p99 {stats.latency_p99_s * 1e3:.3f} ms, "
          f"peak occupancy {stats.max_occupancy}")


if __name__ == "__main__":
    use_compile_cache()
    paper_experiments()
    cfg, state = train_small_lm()
    serve_small_lm(cfg, state)
    print("\nquickstart complete.")

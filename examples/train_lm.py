"""End-to-end training driver with fault tolerance.

Presets:
* ``--preset smoke``  (default) — reduced model, quick on the CPU CI box;
* ``--preset 100m``   — a ~100M-param qwen2-family model for a few hundred
  steps; this is the configuration to run on a real TPU slice (on CPU it
  works but is slow);
* ``--arch <id>``     — any of the ten assigned architectures.

Demonstrates the production loop: deterministic resumable data, atomic
checkpoints, watchdog/straggler log, optional simulated failure.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 30
"""
import argparse
import dataclasses

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.train.loop import LoopConfig, train
from repro.train.optim import AdamWConfig
from repro.utils.env import use_compile_cache


def preset_100m() -> ModelConfig:
    return ModelConfig(
        name="qwen2-100m", family="dense", num_layers=12, d_model=512,
        num_heads=8, num_kv_heads=4, head_dim=64, d_ff=2048,
        vocab_size=32_000, attn_bias=True, act="silu", gated_mlp=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=("smoke", "100m"), default="smoke")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not reduced) arch config")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="raise a simulated node failure at this step")
    args = ap.parse_args()
    use_compile_cache()

    if args.preset == "100m":
        cfg = preset_100m()
        args.batch, args.seq = max(args.batch, 8), max(args.seq, 256)
    elif args.full_config:
        cfg = get_config(args.arch)
    else:
        cfg = get_smoke_config(args.arch)

    loop_cfg = LoopConfig(total_steps=args.steps,
                          checkpoint_every=max(5, args.steps // 4),
                          checkpoint_dir=args.ckpt_dir, async_save=True,
                          log_every=max(1, args.steps // 20))

    def failure_hook(step):
        if step == args.inject_failure:
            args.inject_failure = -1
            raise RuntimeError(f"injected failure at step {step}")

    res = train(cfg, AdamWConfig(lr=3e-3, warmup_steps=10,
                                 decay_steps=max(100, args.steps)),
                loop_cfg, global_batch=args.batch, seq_len=args.seq,
                failure_hook=failure_hook if args.inject_failure >= 0 else None)
    print(f"\nfinal loss {res.losses[-1]:.4f} after {len(res.losses)} steps "
          f"({res.restarts} restarts, {len(res.straggler_steps)} straggler "
          f"events)")


if __name__ == "__main__":
    main()

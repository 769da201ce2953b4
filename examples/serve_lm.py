"""Continuous-batching serving driver — the inference-engine shape of
the paper.

NVDLA is an inference offload engine behind a shared memory system; the
LM-serving analogue is a continuous-batching engine whose paged KV
blocks are the memory-system residents.  Requests arrive at an offered
load, queue for slots, and every scheduler step is priced by the SoC
latency oracle — so throughput and tail latency come out in *simulated
SoC seconds*, with LLC contention from slot occupancy visible in the
p99 (the paper's Fig. 6 effect, serving-side).

Run:  PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_smoke_config
from repro.models import init_params
from repro.serve import Request, ServeEngine
from repro.types import param_values
from repro.utils.env import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--gap-us", type=float, default=100.0,
                    help="arrival gap between requests (simulated µs)")
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    params = param_values(init_params(jax.random.PRNGKey(0), cfg))
    eng = ServeEngine(cfg, params,
                      cache_len=args.prompt_len + args.max_new + 8,
                      max_slots=args.max_slots, eos_id=0,
                      temperature=args.temperature)

    rng = np.random.default_rng(1)
    for i in range(args.requests):
        eng.submit(Request(
            rid=i,
            tokens=tuple(int(t) for t in
                         rng.integers(3, cfg.vocab_size, args.prompt_len)),
            max_new=args.max_new, arrival_s=i * args.gap_us * 1e-6))

    t0 = time.perf_counter()
    stats = eng.run()
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name}  requests={args.requests}  "
          f"slots={args.max_slots}  prompt={args.prompt_len}")
    print(f"host: {stats.tokens} tokens in {dt:.2f}s wall "
          f"({stats.tokens / dt:.1f} tok/s incl. compile)")
    print(f"simulated SoC: {stats.tokens_per_s:.0f} tok/s over "
          f"{stats.sim_time_s * 1e3:.3f} ms "
          f"(p50 {stats.latency_p50_s * 1e3:.3f} ms, "
          f"p99 {stats.latency_p99_s * 1e3:.3f} ms)")
    print(f"steps: {stats.prefill_steps} prefill / {stats.decode_steps} "
          f"decode / {stats.idle_steps} idle; "
          f"occupancy mean {stats.mean_occupancy:.2f} "
          f"max {stats.max_occupancy}")
    decode_hits = [r.llc_hit_rate for r in eng.step_log
                   if r.kind == "decode" and r.llc_hit_rate is not None]
    if decode_hits:
        print(f"decode LLC hit rate: min {min(decode_hits):.3f} "
              f"max {max(decode_hits):.3f}")
    sample = eng.finished[0]
    print(f"sample rid={sample['rid']}: {sample['tokens'][:10]} "
          f"(latency {sample['latency_s'] * 1e3:.3f} ms)")


if __name__ == "__main__":
    main()

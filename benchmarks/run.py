"""Benchmark runner — one module per paper table/figure.

Prints ``name,value,note`` CSV.  ``python -m benchmarks.run [--only fig5]``.
``--smoke`` runs every suite on tiny grids (CI's benchmark job: proves
the drivers execute end to end and emits the ``BENCH_*.json`` artifacts
— sweep, campaign, serve, npu — without burning minutes of runner
time).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import campaign_bench, fig4_platforms, fig5_llc
from benchmarks import fig6_interference, fig6_tail, kernel_bench
from benchmarks import roofline, serve_bench, socsim_bench
from repro.utils.env import use_compile_cache

SUITES = {
    "fig4": fig4_platforms.run,
    "fig5": fig5_llc.run,
    "fig6": fig6_interference.run,
    "fig6_tail": fig6_tail.run,
    "kernels": kernel_bench.run,
    "roofline": roofline.run,
    "socsim": socsim_bench.run,
    "campaign": campaign_bench.run,
    "serve": serve_bench.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids, assert JSON emission (CI)")
    args = ap.parse_args()
    use_compile_cache()
    suites = {args.only: SUITES[args.only]} if args.only else SUITES
    print("name,value,note")
    status: dict[str, tuple[bool, float, str]] = {}
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn(smoke=args.smoke):
                print(",".join(str(x) for x in row))
            status[name] = (True, time.time() - t0, "")
        except Exception as e:  # keep the remaining suites going
            status[name] = (False, time.time() - t0,
                            f"{type(e).__name__}: {e}")
            print(f"{name}/ERROR,{type(e).__name__},{e}", file=sys.stderr)
        print(f"_meta/{name}_seconds,{time.time()-t0:.1f},")
    json_notes = []
    if args.smoke and not args.only:
        contracts = (
            ("bench_json", "BENCH_SWEEP_JSON", "BENCH_sweep.json"),
            ("campaign_json", "BENCH_CAMPAIGN_JSON", "BENCH_campaign.json"),
            ("serve_json", "BENCH_SERVE_JSON", "BENCH_serve.json"),
            ("npu_json", "BENCH_NPU_JSON", "BENCH_npu.json"),
            ("noc_json", "BENCH_NOC_JSON", "BENCH_noc.json"),
        )
        for key, env, default in contracts:
            path = os.environ.get(env, default)
            try:
                with open(path) as f:   # smoke contract: JSON must exist
                    json.load(f)
                json_notes.append(f"_meta/{key},{path},valid")
            except (OSError, json.JSONDecodeError) as e:
                status[key] = (False, 0.0, f"{type(e).__name__}: {e}")
    # per-benchmark pass/fail summary — CI's log tail says exactly what
    # broke instead of silently archiving a partial BENCH_sweep.json
    print("== benchmark summary ==", file=sys.stderr)
    for name, (ok, secs, err) in status.items():
        line = (f"  {name:<10} {'PASS' if ok else 'FAIL':<4} {secs:6.1f}s"
                + (f"  {err}" if err else ""))
        print(line, file=sys.stderr)
    failed = [n for n, (ok, _, _) in status.items() if not ok]
    if failed:
        raise SystemExit(f"{len(failed)}/{len(status)} benchmark suites "
                         f"failed: {', '.join(failed)}")
    for note in json_notes:
        print(note)


if __name__ == "__main__":
    main()

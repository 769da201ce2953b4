#!/usr/bin/env python3
"""Readings that set the limits of a cell's check, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For each seed, in one process: the cell's call once through the
program, its answers compared with the plain reference (the lower
readings), and the control compared with the same answers (the upper
readings).  The control is the reference with one guarantee of the
configuration broken: LRU replacement becomes FIFO (``frame_grid``),
or the DRAM banks' open rows are kept per master rather than shared
(``campaign``).  One JSON line per seed; the benchmark's own runs never
run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                    if p not in sys.path]
    import jax

    from bench import generator, harness
    from repro.utils.env import use_compile_cache

    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 1
    devices = devices[:wl["chips"]]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    cfg_path, traffic_path = harness.cell_files(bench, wl)
    with open(cfg_path) as f:
        config = json.load(f)
    with open(traffic_path) as f:
        traffic = json.load(f)
    cpu = jax.devices("cpu")[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = generator.build(config, traffic, seed, devices)
        try:
            with jax.default_device(devices[0]):
                out = cell.call()
            with jax.default_device(cpu):
                program = cell.check([out])
                control = cell.check([out], control=True)
        finally:
            cell.close()
        print(json.dumps({
            "workload": wl["name"], "seed": seed,
            "program": {k: v for k, (v, _) in program.numbers.items()},
            "control": {k: v for k, (v, _) in control.numbers.items()},
            "program_correct": program.correct,
            "control_correct": control.correct,
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

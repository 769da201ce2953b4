import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import (jax locks the device count and the
# platform on first init).  This module is the only place the 512-device
# placeholder world is created; tests and benchmarks see the real single
# CPU device.  It is a CPU-only compile tool: pinning the platform keeps
# it off an accelerator another process holds.
# ruff: noqa: E402
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Per cell, two kinds of compiles:

1. **Validation compile** — the *full* configuration with rolled scans:
   proves the sharding config is coherent on the production mesh (a
   sharding mismatch / unsupported collective / layout conflict fails
   here), and yields ``memory_analysis`` for the fits-in-HBM check.

2. **Cost probes** — XLA's ``cost_analysis`` counts a ``while``-loop body
   exactly once, so a rolled-scan module under-reports per-step cost by
   the trip count.  Probes therefore lower *small unrolled* variants with
   ``num_layers = m`` and ``2m`` (m = pattern length) and reconstruct the
   full-depth cost affinely:

       total = probe1 + (n_groups - 1 + rem/m) * (probe2 - probe1)

   which is exact for FLOPs/collective-bytes (per-layer-group costs are
   identical) and a close approximation for bytes-accessed.  The same
   reconstruction applies to the collective inventory.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch import mesh as mesh_mod
from repro.launch.hlo_analysis import parse_collectives, roofline_terms
from repro.launch.specs import (
    abstract_decode_state,
    abstract_params,
    abstract_train_state,
    batch_shardings,
    batch_specs,
    param_sharding_tree,
    token_count,
)
from repro.models import pattern_split, prefill, slot_decode_step
from repro.sharding import activate_rules
from repro.train.optim import AdamWConfig
from repro.train.step import make_train_step
from repro.types import param_values

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "../../../experiments/artifacts/dryrun")


# --------------------------------------------------------------------------
# lowering builders
# --------------------------------------------------------------------------
def build_lowered(cfg, shape, *, donate: bool = True, microbatches: int = 1):
    """Lower the cell's step function under the active mesh rules."""
    if shape.mode == "train":
        state, state_sh = abstract_train_state(cfg)
        batch = batch_specs(cfg, shape, with_labels=True)
        b_sh = batch_shardings(batch)
        step = make_train_step(cfg, AdamWConfig(), microbatches=microbatches)
        jitted = jax.jit(step, in_shardings=(state_sh, b_sh),
                         donate_argnums=(0,) if donate else ())
        return jitted.lower(state, batch)
    if shape.mode == "prefill":
        params_p = abstract_params(cfg)
        params = param_values(params_p)
        p_sh = param_sharding_tree(params_p)
        batch = batch_specs(cfg, shape, with_labels=False)
        b_sh = batch_shardings(batch)
        fn = lambda p, b: prefill(p, b, cfg, shape.seq_len)
        return jax.jit(fn, in_shardings=(p_sh, b_sh)).lower(params, batch)
    # decode: per-slot positions (the serving engine's step)
    args, shardings = abstract_decode_state(cfg, shape)
    fn = lambda p, c, tok, ts: slot_decode_step(p, c, tok, ts, cfg)
    jitted = jax.jit(fn, in_shardings=shardings,
                     donate_argnums=(1,) if donate else ())
    return jitted.lower(*args)


def _memory_analysis(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
        return {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
        }
    except Exception as e:  # some backends lack memory_analysis
        return {"error": f"{type(e).__name__}: {e}"}


def _cost_analysis(compiled) -> dict:
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        return {k: float(v) for k, v in c.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def _probe_cfg(cfg, n_layers: int):
    kw = {"num_layers": n_layers, "unroll_scans": True}
    if cfg.is_encoder_decoder:
        kw["num_encoder_layers"] = n_layers
    return dataclasses.replace(cfg, **kw)


def _probe_cost(cfg, shape, n_dev: int, *, microbatches: int = 1) -> dict:
    lowered = build_lowered(cfg, shape, donate=False,
                            microbatches=microbatches)
    compiled = lowered.compile()
    cost = _cost_analysis(compiled)
    stats = parse_collectives(compiled.as_text(), n_devices=n_dev)
    return {
        "flops": cost.get("flops", 0.0),
        "bytes": cost.get("bytes accessed", 0.0),
        "wire_bytes": stats.wire_bytes,
        "coll_counts": stats.counts,
    }


def _reconstruct(p1: dict, p2: dict, scale: float) -> dict:
    out = {}
    for k in ("flops", "bytes", "wire_bytes"):
        out[k] = p1[k] + scale * (p2[k] - p1[k])
    out["coll_counts"] = {
        op: round(p1["coll_counts"].get(op, 0)
                  + scale * (p2["coll_counts"].get(op, 0)
                             - p1["coll_counts"].get(op, 0)))
        for op in set(p1["coll_counts"]) | set(p2["coll_counts"])}
    return out


def _model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS (6ND train, 2ND prefill, 2N/token decode)."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * token_count(cfg, shape)
    if shape.mode == "train":
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             overrides: dict | None = None,
             rule_overrides: dict | None = None,
             microbatches: int = 1) -> dict:
    """rule_overrides: logical-axis -> mesh-axes mapping overrides (the
    hillclimb knob — e.g. {"act_seq": ("model",)} turns on sequence
    parallelism for activations/saved carries)."""
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multipod" if multi_pod else "pod"
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "mode": shape.mode, "rule_overrides": rule_overrides}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        out["skipped"] = reason
        return out

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    out["n_devices"] = n_dev

    with activate_rules(mesh, rule_overrides) as rules:
        # ---- 1. validation compile: full config, rolled scans ----------
        t0 = time.time()
        lowered = build_lowered(cfg, shape, microbatches=microbatches)
        out["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        out["compile_s"] = round(time.time() - t1, 2)
        out["dropped_axes"] = sorted(str(d) for d in rules.dropped)
        out["memory"] = _memory_analysis(compiled)

        # ---- 2. cost probes: small unrolled variants --------------------
        pattern, n_full, rem = pattern_split(cfg)
        m = len(pattern)
        p1 = _probe_cost(_probe_cfg(cfg, m), shape, n_dev,
                         microbatches=microbatches)
        p2 = _probe_cost(_probe_cfg(cfg, 2 * m), shape, n_dev,
                         microbatches=microbatches)
        scale = (n_full - 1) + rem / m
        cost = _reconstruct(p1, p2, scale)
        out["probe"] = {"p1": p1, "p2": p2, "scale": scale}
        out["cost"] = cost

    peaks = mesh_mod.chip_peaks(mesh_mod.TARGET_KIND)
    out["roofline"] = roofline_terms(
        flops=cost["flops"], bytes_accessed=cost["bytes"],
        wire_bytes=cost["wire_bytes"],
        peak_flops=peaks.bf16_flops, hbm_bw=peaks.hbm_bw,
        link_bw=peaks.ici_bw)
    mf = _model_flops(cfg, shape)
    out["model_flops"] = mf
    hlo_total = cost["flops"] * n_dev
    out["model_flops_ratio"] = (mf / hlo_total) if hlo_total else None
    return out


def _write(out: dict, artifact_dir: str) -> str:
    d = os.path.join(artifact_dir, out["mesh"])
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, f"{out['arch']}__{out['shape']}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"), default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--perf", action="store_true",
                    help="apply the tuned PERF_PRESETS where available "
                         "(writes artifacts under <out>-perf)")
    args = ap.parse_args()
    if args.perf and args.out == ARTIFACT_DIR:
        args.out = ARTIFACT_DIR + "-perf"

    meshes = [False, True] if args.mesh == "both" else [args.mesh == "multipod"]
    cells = ([(a, s) for a in ARCHS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            mesh_name = "multipod" if mp else "pod"
            tag = f"{arch} x {shape_name} x {mesh_name}"
            path = os.path.join(args.out, mesh_name,
                                f"{arch}__{shape_name}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if "error" not in prev:
                    print(f"[keep] {tag}")
                    continue
            kw = {}
            if args.perf:
                from repro.launch.presets import preset_for

                p = preset_for(arch, shape_name)
                if p:
                    kw = {"overrides": p.get("overrides") or None,
                          "rule_overrides": p.get("rule_overrides") or None,
                          "microbatches": p.get("microbatches", 1)}
            try:
                out = run_cell(arch, shape_name, mp, **kw)
            except Exception:
                out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                       "error": traceback.format_exc()}
                failures += 1
                print(f"[FAIL] {tag}")
                print(out["error"].splitlines()[-1])
            else:
                if "skipped" in out:
                    print(f"[skip] {tag}: {out['skipped']}")
                else:
                    r = out["roofline"]
                    print(f"[ ok ] {tag}: compile {out['compile_s']}s "
                          f"dominant={r['dominant']} "
                          f"compute={r['compute_s']:.4f}s "
                          f"mem={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"temp={out['memory'].get('temp_bytes', 0)/2**30:.1f}GiB",
                          flush=True)
            _write(out, args.out)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()

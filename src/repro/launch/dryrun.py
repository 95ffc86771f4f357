import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input shape x mesh) cell with
ShapeDtypeStruct parameters/inputs -- no allocation -- and records
memory_analysis / cost_analysis / collective-bytes JSON artefacts that the
roofline report (deliverable g) consumes.

The two lines above MUST stay the first statements in this module: jax
locks the device count at first initialisation, and the production meshes
need 512 host devices.  Never import this module from tests/benches that
expect 1 CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --force
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.analysis.hlo import (collective_bytes, collective_counts,
                                cost_analysis_dict)
from repro.configs import INPUT_SHAPES, all_configs, shape_skips
from repro.configs.base import InputShape, ModelConfig
from repro.launch import partition as PT
from repro.launch.mesh import auto_axes, make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "out", "dryrun")

LONG_WINDOW = 8192


def cell_config(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Per-cell variant: dense/MoE/VLM archs run long_500k with the
    sliding-window attention variant (DESIGN.md section 5); SSM/hybrid run
    natively."""
    if shape.name == "long_500k" and cfg.pattern in ("attn_mlp", "attn_moe") \
            and not cfg.sliding_window:
        return dataclasses.replace(cfg, sliding_window=LONG_WINDOW)
    return cfg


def _measure(cfg: ModelConfig, shape: InputShape, mesh, *,
             unroll_layers: bool, scan_unroll: int):
    """Lower + compile one variant; return scalar cost terms + artefacts."""
    from repro.models import layers as Lmod
    from repro.models import moe_ep
    mesh = auto_axes(mesh)
    Lmod.SCAN_UNROLL = scan_unroll
    Lmod.HINT_AXIS = "model"      # TP sharding hints (§Perf P3)
    Lmod.HINT_MESH = mesh
    # §Perf P1: expert-parallel all-to-all dispatch whenever E % model == 0
    moe_ep.EP_MESH = mesh if os.environ.get("REPRO_MOE_EP", "1") == "1" \
        else None
    t0 = time.time()
    try:
        params = PT.param_struct(cfg, mesh, mode=shape.mode)
        batch = PT.batch_struct(cfg, shape, mesh)
        if shape.mode == "train":
            step = PT.make_train_step(cfg, unroll_layers=unroll_layers)
            opt_state = PT.opt_state_struct(params)
            # donate params+opt so outputs alias inputs (in-place update)
            lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
                params, opt_state, batch)
        elif shape.mode == "prefill":
            if cfg.is_encoder:
                step = PT.make_encode_step(cfg, unroll_layers=unroll_layers)
                lowered = jax.jit(step).lower(params, batch)
            else:
                step = PT.make_prefill_step(cfg,
                                            unroll_layers=unroll_layers)
                cache = PT.cache_struct(cfg, shape, mesh)
                lowered = jax.jit(step).lower(params, batch, cache)
        else:  # decode: ONE token against a seq_len cache
            step = PT.make_decode_step(cfg, unroll_layers=unroll_layers)
            cache = PT.cache_struct(cfg, shape, mesh)
            lowered = jax.jit(step, donate_argnums=(2,)).lower(
                params, batch["tokens"], cache)
        compiled = lowered.compile()
    finally:
        Lmod.SCAN_UNROLL = 1
        Lmod.HINT_AXIS = None
        Lmod.HINT_MESH = None
        moe_ep.EP_MESH = None
    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    ma = compiled.memory_analysis()
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
        "coll_counts": collective_counts(hlo),
        "memory": {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")},
        "hlo_bytes": len(hlo),
        "wall_s": round(time.time() - t0, 2),
    }


# Loop-cost extrapolation (see EXPERIMENTS.md 'Dry-run methodology').
# XLA's cost_analysis counts a while-loop body ONCE, not x trip count
# (verified experimentally).  We therefore compile small python-unrolled
# variants (loop-free HLO => exact costs, linear in layer count) and
# reconstruct the true totals; inner sequential scans (mamba2 chunks,
# rwkv6 tokens) get one extra compile at scan-unroll=2 to separate the
# inner-body cost.  memory_analysis comes from the REAL config's compile
# (buffer sizes are exact regardless of loops).
def _inner_trips(cfg: ModelConfig, shape: InputShape) -> int:
    if shape.mode == "decode":
        return 1 if cfg.pattern == "rwkv" else 0
    if cfg.pattern == "rwkv":
        return shape.seq_len
    if cfg.pattern == "mamba":
        return -(-shape.seq_len // 64)      # mamba2 chunk=64
    return 0


def _extrapolate(vals: dict[str, float], cfg: ModelConfig,
                 shape: InputShape) -> float:
    """vals: measured scalar per variant tag -> true total."""
    # Every coefficient is a sum of HLO op costs, hence non-negative in
    # truth; measured deltas can go negative when XLA fuses across the
    # unrolled copies (notably 'bytes accessed'), so clamp per-coefficient.
    if cfg.pattern == "mamba" and cfg.attn_every:
        n_seg, _ = __import__(
            "repro.models.transformer", fromlist=["x"])._zamba_segments(cfg)
        k = cfg.attn_every
        q1 = max(vals["Z2"] - vals["Z1"], 0.0)
        c0 = max(vals["Z1"] - q1, 0.0)
        t3 = _inner_trips(cfg, shape)
        i = max((vals["C"] - vals["Z2"]) / (2 * k), 0.0) \
            if "C" in vals else 0.0
        per_seg = q1 + k * i * max(t3 - 1, 0)
        return c0 + n_seg * per_seg
    slope = max((vals["B4"] - vals["B2"]) / 2.0, 0.0)
    c0 = max(vals["B2"] - 2 * slope, 0.0)
    t2 = _inner_trips(cfg, shape)
    i = max((vals["C"] - vals["B2"]) / 2.0, 0.0) if "C" in vals else 0.0
    per_layer = slope + i * max(t2 - 1, 0)
    return c0 + cfg.num_layers * per_layer


def _variant_plan(cfg: ModelConfig, shape: InputShape):
    """[(tag, cfg_variant, unroll_layers, scan_unroll)]"""
    need_inner = _inner_trips(cfg, shape) > 1
    if cfg.pattern == "mamba" and cfg.attn_every:
        k = cfg.attn_every
        plan = [("Z1", dataclasses.replace(cfg, num_layers=k), True, 1),
                ("Z2", dataclasses.replace(cfg, num_layers=2 * k), True, 1)]
        if need_inner:
            plan.append(("C", dataclasses.replace(cfg, num_layers=2 * k),
                         True, 2))
        return plan
    plan = [("B2", dataclasses.replace(cfg, num_layers=2), True, 1),
            ("B4", dataclasses.replace(cfg, num_layers=4), True, 1)]
    if need_inner:
        plan.append(("C", dataclasses.replace(cfg, num_layers=2), True, 2))
    return plan


def lower_cell(cfg: ModelConfig, shape: InputShape, mesh, mesh_name: str):
    """Compile the real cell + extrapolation variants; return the record."""
    cfg = cell_config(cfg, shape)
    with jax.default_device(jax.devices("cpu")[0]):
        real = _measure(cfg, shape, mesh, unroll_layers=False,
                        scan_unroll=1)
        variants = {}
        for tag, vcfg, unroll, su in _variant_plan(cfg, shape):
            variants[tag] = _measure(vcfg, shape, mesh,
                                     unroll_layers=unroll, scan_unroll=su)

    def extract(key, sub=None):
        vals = {t: (m[key] if sub is None else m[key].get(sub, 0.0))
                for t, m in variants.items()}
        return _extrapolate(vals, cfg, shape)

    coll_kinds = set()
    for m in list(variants.values()) + [real]:
        coll_kinds |= set(m["coll"])
    coll_true = {kind: extract("coll", kind) for kind in coll_kinds}
    coll_true["total"] = sum(v for k, v in coll_true.items()
                             if k != "total")
    rec = {
        "arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
        "num_devices": int(mesh.devices.size),
        "mode": shape.mode,
        "sliding_window": cfg.sliding_window,
        "cost": {"flops": extract("flops"),
                 "bytes accessed": extract("bytes")},
        "cost_scan_raw": {"flops": real["flops"],
                          "bytes accessed": real["bytes"]},
        "memory": real["memory"],
        "collective_bytes": coll_true,
        "collective_bytes_raw": real["coll"],
        "collective_counts": real["coll_counts"],
        "model_flops": cfg.model_flops(
            seq_len=shape.seq_len, batch=shape.global_batch,
            mode=shape.mode),
        "compile_s": real["wall_s"],
        "variant_wall_s": {t: m["wall_s"] for t, m in variants.items()},
        "hlo_bytes": real["hlo_bytes"],
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi2x16x16", make_production_mesh(multi_pod=True)))

    cfgs = all_configs()
    archs = [args.arch] if args.arch else sorted(cfgs)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    n_ok = n_skip = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            cfg = cfgs[arch]
            for shape_name in shapes:
                shape = INPUT_SHAPES[shape_name]
                tag = f"{mesh_name}.{arch}.{shape_name}"
                path = os.path.join(OUT_DIR, f"{tag}.json")
                skip = shape_skips(cfg, shape)
                if skip:
                    print(f"SKIP {tag}: {skip}", flush=True)
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name,
                                   "mesh": mesh_name, "skipped": skip}, f)
                    n_skip += 1
                    continue
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        if "error" not in json.load(f):
                            print(f"CACHED {tag}", flush=True)
                            n_ok += 1
                            continue
                try:
                    rec = lower_cell(cfg, shape, mesh, mesh_name)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=1)
                    print(f"OK {tag}: flops/dev={rec['cost'].get('flops', 0):.3e} "
                          f"coll={rec['collective_bytes'].get('total', 0):.3e}B "
                          f"compile={rec['compile_s']}s", flush=True)
                    n_ok += 1
                except Exception as e:   # noqa: BLE001 -- record and continue
                    n_fail += 1
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name,
                                   "mesh": mesh_name, "error": str(e)}, f)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

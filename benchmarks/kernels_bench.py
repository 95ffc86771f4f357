"""Kernel microbenchmarks: wall time (on the CPU the kernels run in the
Pallas interpreter -- relative numbers only; on a TPU they compile) plus
the analytic MXU utilisation each BlockSpec tiling would claim on v5e."""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import ensure_out, save_json, time_us
from repro.core.costs import INT8_FRAME_OVERHEAD_BYTES, WIRE_SCALE_BYTES
from repro.core.hardware import V5E_PEAK_FLOPS_BF16
from repro.kernels import conv2d as conv2d_mod
from repro.kernels import ops, ref
from repro.kernels.conv2d import conv_vmem_bytes, plan_conv


def _pool_triples(model: str) -> list[tuple]:
    """(name, cin, hw, cout, K, stride, pad, act, pool_k, pool_s) for every
    conv->relu->maxpool triple the model executes at 224 px (enumeration
    shared with the fusion walk via cnn.conv_pool_triples)."""
    from repro.models import cnn
    layers = cnn.CNN_MODELS[model]
    conv_ordinal = {i: n + 1 for n, i in enumerate(
        i for i, l in enumerate(layers) if l.kind == "conv")}
    return [(f"{model}_conv{conv_ordinal[i]}", cin, hw, cout, K, s, p,
             act, pk, ps)
            for i, cin, hw, cout, K, s, p, act, pk, ps
            in cnn.conv_pool_triples(layers)]


def conv_fusion_report() -> list[tuple]:
    """Fused conv+relu+maxpool triple vs the unfused two-launch path for
    every AlexNet/VGG16 pool triple: interpret-mode wall time (relative
    only -- compile on TPU for real numbers), predicted per-tile VMEM, and
    the analytic HBM-traffic proxy fusion removes (the conv activation
    write + re-read).  Emits BENCH_conv_fusion.json so the perf trajectory
    records launch counts and bandwidth proxies over time."""
    rows, triples = [], []
    key = jax.random.PRNGKey(42)
    for model in ("alexnet", "vgg16"):
        for name, cin, hw, cout, K, s, p, act, pk, ps in \
                _pool_triples(model):
            x = jax.random.normal(key, (1, cin, hw, hw), jnp.float32) * 0.3
            w = jax.random.normal(jax.random.fold_in(key, 1),
                                  (cout, cin, K, K), jnp.float32) * 0.1
            b = jax.random.normal(jax.random.fold_in(key, 2),
                                  (cout,), jnp.float32) * 0.1
            plan = plan_conv(x.shape, w.shape, stride=s, pad=p,
                             pool_k=pk, pool_s=ps)
            us_f = time_us(lambda: jax.block_until_ready(
                ops.conv2d(x, w, stride=s, pad=p, bias=b, activation=act,
                           pool_k=pk, pool_s=ps)), repeats=3)
            pool = jax.jit(lambda y: jax.lax.reduce_window(
                y, -jnp.inf, jax.lax.max, (1, 1, pk, pk), (1, 1, ps, ps),
                "VALID"))
            us_u = time_us(lambda: jax.block_until_ready(pool(
                ops.conv2d(x, w, stride=s, pad=p, bias=b,
                           activation=act))), repeats=3)
            jx = jax.jit(lambda a, c, d: pool(ref.conv2d_ref(
                a, c, stride=s, pad=p, bias=d, activation=act)))
            us_x = time_us(lambda: jax.block_until_ready(jx(x, w, b)),
                           repeats=3)
            # bandwidth proxy: the unfused path writes the conv activation
            # to HBM and reads it back for the pool; fusion removes both
            act_b = 4 * cout * plan.h_out * plan.w_out
            pooled_b = 4 * cout * plan.p_out * plan.pw_out
            in_b = 4 * cin * hw * hw
            w_b = 4 * cout * cin * K * K
            rows.append((
                f"kernels.conv_fusion.{name}_pool{pk}s{ps}", us_f,
                f"unfused_us={us_u:.1f} tile_h={plan.tile_h} "
                f"vmem_bytes={plan.vmem_bytes} "
                f"act_hbm_bytes_avoided={2 * act_b}"))
            triples.append({
                "name": name, "model": model,
                "shape": {"cin": cin, "hw": hw, "cout": cout, "K": K,
                          "stride": s, "pad": p, "pool_k": pk,
                          "pool_s": ps},
                "fused_us": us_f, "unfused_us": us_u, "xla_us": us_x,
                "launches_fused": 1,          # one pallas_call, pool inside
                "launches_unfused": 2,        # pallas_call + reduce_window
                "ops_seed": 4,                # conv, bias, relu, pool
                "tile_h": plan.tile_h, "tile_conv_h": plan.tile_conv_h,
                "vmem_bytes": plan.vmem_bytes,
                "hbm_bytes_fused": in_b + w_b + pooled_b,
                "hbm_bytes_unfused": in_b + w_b + pooled_b + 2 * act_b,
                "act_hbm_bytes_avoided": 2 * act_b,
            })
    path = save_json("", "BENCH_conv_fusion.json", {
        "triples": triples,
        "totals": {
            "n_triples": len(triples),
            "launches_fused": sum(t["launches_fused"] for t in triples),
            "launches_unfused": sum(t["launches_unfused"] for t in triples),
            "hbm_bytes_saved": sum(t["act_hbm_bytes_avoided"]
                                   for t in triples),
        }})
    rows.append(("kernels.conv_fusion.json", None, path))
    return rows


def model_conv_specs(model: str) -> list[tuple]:
    """(name, cin, hw, cout, K, stride, pad, act, pool_k, pool_s) for every
    conv paper-layer the model executes at 224 px.  ``pool_k/pool_s`` are
    non-zero when the conv heads a conv->relu->maxpool triple that the
    pallas backend fuses into one launch (``cnn.conv_pool_triples``)."""
    from repro.models import cnn
    layers = cnn.CNN_MODELS[model]
    triples = {t[0]: t for t in cnn.conv_pool_triples(layers)}
    shape = cnn.INPUT_SHAPE
    out, n = [], 0
    for i, l in enumerate(layers):
        if l.kind == "conv":
            n += 1
            nxt = layers[i + 1].kind if i + 1 < len(layers) else ""
            act = nxt if nxt in ("relu", "relu6") else None
            pk, ps = (triples[i][-2], triples[i][-1]) if i in triples \
                else (0, 0)
            out.append((f"{model}_conv{n}", shape[0], shape[1], l.cout,
                        l.ksize, l.stride, l.pad, act, pk, ps))
        shape = cnn.layer_out_shape(l, shape)
    return out


def dtype_plan_stats(cin: int, hw: int, cout: int, K: int, stride: int,
                     pad: int, pool_k: int = 0, pool_s: int = 0,
                     batch: int = 1) -> dict:
    """fp32-vs-bf16 planner comparison for one conv (+fused pool) shape.

    Three numbers matter: VMEM per tile at the *same* tile geometry (the
    apples-to-apples storage saving -- the fp32 accumulator stays, so the
    ratio is < 2x), the ``tile_h`` the planner buys back with the freed
    headroom, and the launch count that falls out of the bigger tiles."""
    x_shape = (batch, cin, hw, hw)
    w_shape = (cout, cin, K, K)
    plans = {}
    stats = {}
    for policy, nbytes in (("fp32", 4), ("bf16", 2)):
        plan = plan_conv(x_shape, w_shape, stride=stride, pad=pad,
                         pool_k=pool_k, pool_s=pool_s, dtype_bytes=nbytes)
        plans[policy] = plan
        stats[policy] = {
            "tile_h": plan.tile_h, "tile_w": plan.tile_w,
            "n_h_blocks": plan.n_h_blocks, "n_w_blocks": plan.n_w_blocks,
            "launches": plan.launches,
            "vmem_bytes_per_tile": plan.vmem_bytes,
            "out_bytes": batch * cout * plan.p_out * plan.pw_out * nbytes,
        }
    p32 = plans["fp32"]
    same_tile = conv_vmem_bytes(
        cin_block=p32.cin_block, block_co=p32.block_co, tile_h=p32.tile_h,
        w_out=p32.w_out, K=K, stride=stride,
        cin_per_group=cin, dtype_bytes=2, pool_k=p32.pool_k,
        pool_s=p32.pool_s,
        tile_w=p32.tile_w if p32.n_w_blocks > 1 else 0)
    stats["vmem_bytes_bf16_at_fp32_tile"] = same_tile
    stats["vmem_per_tile_ratio"] = p32.vmem_bytes / same_tile
    stats["launch_ratio"] = (stats["fp32"]["launches"]
                             / stats["bf16"]["launches"])
    stats["transfer_bytes_ratio"] = (stats["fp32"]["out_bytes"]
                                     / stats["bf16"]["out_bytes"])
    return stats


_SMOKE_CONV_SPECS = [
    # one tiny shape per conv family: plain conv+relu, fused pool triple
    ("smoke_conv", 8, 16, 16, 3, 1, 1, "relu", 0, 0),
    ("smoke_triple", 8, 16, 16, 3, 1, 1, "relu", 2, 2),
]


def dtype_sweep_report(smoke: bool = False) -> list[tuple]:
    """fp32 vs bf16 storage for every AlexNet/VGG16 conv (+fused pool
    triple) shape: planner stats (VMEM per tile, tile_h, launch counts),
    interpret-mode wall time, and max-abs error of the bf16 kernel against
    the fp32 XLA reference.  Emits BENCH_dtype_sweep.json.

    ``smoke`` runs one tiny shape per family so CI can exercise the whole
    bench path (planning, execution, JSON emission) in seconds."""
    key = jax.random.PRNGKey(7)
    specs = _SMOKE_CONV_SPECS if smoke else [
        s for m in ("alexnet", "vgg16") for s in model_conv_specs(m)]
    rows, entries = [], []
    for name, cin, hw, cout, K, s, p, act, pk, ps in specs:
        stats = dtype_plan_stats(cin, hw, cout, K, s, p, pk, ps)
        x = jax.random.normal(key, (1, cin, hw, hw), jnp.float32) * 0.3
        w = jax.random.normal(jax.random.fold_in(key, 1),
                              (cout, cin, K, K), jnp.float32) * 0.1
        b = jax.random.normal(jax.random.fold_in(key, 2),
                              (cout,), jnp.float32) * 0.1
        want = ref.conv2d_ref(x, w, stride=s, pad=p, bias=b, activation=act)
        if pk:
            want = jax.lax.reduce_window(
                want, -jnp.inf, jax.lax.max, (1, 1, pk, pk),
                (1, 1, ps, ps), "VALID")
        want = jax.block_until_ready(want)
        macs = K * K * cin * cout * hw * hw
        repeats = 1 if macs > 5e8 else 3
        us, err = {}, {}
        for policy in ("fp32", "bf16"):
            def run(policy=policy):
                return jax.block_until_ready(ops.conv2d(
                    x, w, stride=s, pad=p, bias=b, activation=act,
                    pool_k=pk, pool_s=ps, dtype=policy))
            got = run().astype(jnp.float32)      # doubles as the warmup
            us[policy] = time_us(run, repeats=repeats, warmup=0)
            err[policy] = float(jnp.max(jnp.abs(got - want)))
        denom = float(jnp.max(jnp.abs(want)))
        # wire column: this activation shipped as the split boundary --
        # int8 = 1 byte/elem + per-channel fp32 scales + two-part framing
        out_elems = stats["fp32"]["out_bytes"] // 4
        wire_fp32 = stats["fp32"]["out_bytes"]
        wire_int8 = out_elems + WIRE_SCALE_BYTES * cout \
            + INT8_FRAME_OVERHEAD_BYTES
        entries.append({
            "name": name,
            "shape": {"cin": cin, "hw": hw, "cout": cout, "K": K,
                      "stride": s, "pad": p, "act": act,
                      "pool_k": pk, "pool_s": ps},
            **stats,
            "fp32_us": us["fp32"], "bf16_us": us["bf16"],
            "max_abs_err_fp32": err["fp32"],
            "max_abs_err_bf16": err["bf16"],
            "max_rel_err_bf16": err["bf16"] / denom if denom else 0.0,
            "wire_bytes_fp32": wire_fp32,
            "wire_bytes_int8": wire_int8,
            "wire_int8_reduction": wire_fp32 / wire_int8,
        })
        rows.append((
            f"kernels.dtype_sweep.{name}", us["bf16"],
            f"fp32_us={us['fp32']:.1f} "
            f"tile_h={stats['fp32']['tile_h']}->{stats['bf16']['tile_h']} "
            f"launches={stats['fp32']['launches']}->"
            f"{stats['bf16']['launches']} "
            f"vmem_ratio={stats['vmem_per_tile_ratio']:.2f} "
            f"max_abs_err={err['bf16']:.3e}"))
    fname = "BENCH_dtype_sweep_smoke.json" if smoke \
        else "BENCH_dtype_sweep.json"
    path = save_json("", fname, {
        "smoke": smoke,
        "entries": entries,
        "totals": {
            "n_shapes": len(entries),
            "launches_fp32": sum(e["fp32"]["launches"] for e in entries),
            "launches_bf16": sum(e["bf16"]["launches"] for e in entries),
            "min_vmem_per_tile_ratio": min(
                e["vmem_per_tile_ratio"] for e in entries),
            "max_abs_err_bf16": max(
                e["max_abs_err_bf16"] for e in entries),
            "wire_bytes_fp32": sum(e["wire_bytes_fp32"] for e in entries),
            "wire_bytes_int8": sum(e["wire_bytes_int8"] for e in entries),
            "min_wire_int8_reduction": min(
                e["wire_int8_reduction"] for e in entries),
        }})
    rows.append(("kernels.dtype_sweep.json", None, path))
    return rows


def _plan_stats(plan) -> dict:
    """The comparable numbers of one ConvPlan for the tiling JSONs."""
    return {"block_co": plan.block_co, "tile_h": plan.tile_h,
            "tile_w": plan.tile_w, "n_h_blocks": plan.n_h_blocks,
            "n_w_blocks": plan.n_w_blocks, "launches": plan.launches,
            "vmem_bytes": plan.vmem_bytes, "cost_bytes": plan.cost_bytes}


# Wide-input client workloads (1080p camera frame, panoramic strips) the
# paper's smartphone setting implies.  The two *_row_buster strips keep H
# small so interpret mode stays tractable, but their single output row
# overflows the 12 MiB budget: ValueError on the greedy planner, runnable
# only with column tiles.
_WIDE_SPECS = [
    # name, cin, H, W, cout, K, stride, pad, act, pool_k, pool_s
    ("hd1080_conv1", 3, 1080, 1920, 64, 3, 1, 1, "relu", 0, 0),
    ("pano512x2048_conv1", 3, 512, 2048, 64, 11, 4, 2, "relu", 3, 2),
    ("strip7680_row_buster", 64, 16, 7680, 64, 3, 1, 1, "relu", 0, 0),
    ("strip6144_pool_row_buster", 64, 17, 6144, 64, 3, 1, 1, "relu", 2, 2),
]

# Smoke twins: one wide shape per conv family (plain conv, fused pool
# triple) shrunk so CI exercises column tiling in seconds.  The tiny
# explicit VMEM budget is what makes a 96-px row "wide" (one full row
# needs ~632 KiB in padded VMEM tiles, one column ~124 KiB): the greedy
# row-only planner raises on it, the search splits columns.
_SMOKE_WIDE_BUDGET = 256 * 1024
_SMOKE_WIDE_SPECS = [
    ("smoke_wide_conv", 8, 12, 96, 16, 3, 1, 1, "relu", 0, 0),
    ("smoke_wide_triple", 8, 13, 96, 16, 3, 1, 1, "relu", 2, 2),
]


def tiling_search_report(smoke: bool = False) -> list[tuple]:
    """Greedy-vs-joint-search planner comparison plus the wide-input sweep.

    Full mode: every AlexNet/VGG16/MobileNetV2 conv shape at fp32 and
    bf16 -- launch counts, per-tile VMEM, cost-model bytes, and
    interpret-mode wall time (relative only) for both planners -- plus
    the ``_WIDE_SPECS`` high-resolution shapes, recording which ones the
    greedy planner rejects outright and the parity of the column-tiled
    kernel against ``ref.conv2d_ref``.  Smoke mode runs the two tiny
    wide shapes under a 256 KiB budget so CI exercises column tiling on
    every push.  Emits BENCH_tiling_search{_smoke}.json."""
    key = jax.random.PRNGKey(11)
    rows, entries, wide = [], [], []
    if not smoke:
        specs = [s for m in ("alexnet", "vgg16", "mobilenetv2")
                 for s in model_conv_specs(m)]
        for name, cin, hw, cout, K, s, p, act, pk, ps in specs:
            x = jax.random.normal(key, (1, cin, hw, hw), jnp.float32) * 0.3
            w = jax.random.normal(jax.random.fold_in(key, 1),
                                  (cout, cin, K, K), jnp.float32) * 0.1
            b = jax.random.normal(jax.random.fold_in(key, 2),
                                  (cout,), jnp.float32) * 0.1
            entry = {"name": name,
                     "shape": {"cin": cin, "hw": hw, "cout": cout, "K": K,
                               "stride": s, "pad": p, "act": act,
                               "pool_k": pk, "pool_s": ps}}
            for policy, nbytes in (("fp32", 4), ("bf16", 2)):
                cmp, plans = {}, {}
                for mode, searched in (("greedy", False), ("search", True)):
                    plans[mode] = _plan_stats(plan_conv(
                        (1, cin, hw, hw), (cout, cin, K, K),
                        stride=s, pad=p, pool_k=pk, pool_s=ps,
                        dtype_bytes=nbytes, search=searched))
                    st = dict(plans[mode])
                    if mode == "search" and plans["search"] == \
                            plans["greedy"]:
                        # identical plan: reuse the greedy measurement
                        st["us"] = cmp["greedy"]["us"]
                    else:
                        st["us"] = time_us(
                            lambda se=searched, po=policy:
                            jax.block_until_ready(ops.conv2d(
                                x, w, stride=s, pad=p, bias=b,
                                activation=act, pool_k=pk, pool_s=ps,
                                dtype=po, search=se)),
                            repeats=1)
                    cmp[mode] = st
                entry[policy] = cmp
            entries.append(entry)
            f32 = entry["fp32"]
            rows.append((
                f"kernels.tiling_search.{name}", f32["search"]["us"],
                f"greedy_us={f32['greedy']['us']:.1f} "
                f"launches={f32['greedy']['launches']}->"
                f"{f32['search']['launches']} "
                f"tile={f32['search']['tile_h']}x{f32['search']['tile_w']} "
                f"bc={f32['search']['block_co']}"))

    wide_specs = _SMOKE_WIDE_SPECS if smoke else _WIDE_SPECS
    budget = _SMOKE_WIDE_BUDGET if smoke \
        else conv2d_mod.DEFAULT_VMEM_BUDGET
    for name, cin, H, W, cout, K, s, p, act, pk, ps in wide_specs:
        x = jax.random.normal(key, (1, cin, H, W), jnp.float32) * 0.3
        w = jax.random.normal(jax.random.fold_in(key, 3),
                              (cout, cin, K, K), jnp.float32) * 0.1
        b = jax.random.normal(jax.random.fold_in(key, 4),
                              (cout,), jnp.float32) * 0.1
        entry = {"name": name,
                 "shape": {"cin": cin, "H": H, "W": W, "cout": cout,
                           "K": K, "stride": s, "pad": p, "act": act,
                           "pool_k": pk, "pool_s": ps},
                 "vmem_budget": budget}
        try:
            entry["greedy_fp32"] = _plan_stats(plan_conv(
                x.shape, w.shape, stride=s, pad=p, pool_k=pk, pool_s=ps,
                vmem_budget=budget, search=False))
        except ValueError as e:
            entry["greedy_fp32"] = {"error": str(e)}
        for policy, nbytes in (("fp32", 4), ("bf16", 2)):
            entry[f"search_{policy}"] = _plan_stats(plan_conv(
                x.shape, w.shape, stride=s, pad=p, pool_k=pk, pool_s=ps,
                dtype_bytes=nbytes, vmem_budget=budget, search=True))
        # execute the searched fp32 plan once (interpret mode is slow on
        # these shapes): the same run provides the timing and the parity
        got = None

        def run_wide():
            nonlocal got
            got = jax.block_until_ready(conv2d_mod.conv2d(
                x, w, stride=s, pad=p, bias=b, activation=act,
                pool_k=pk, pool_s=ps, vmem_budget=budget, search=True))

        us = time_us(run_wide, repeats=1, warmup=0)
        want = ref.conv2d_ref(x, w, stride=s, pad=p, bias=b,
                              activation=act)
        if pk:
            want = jax.lax.reduce_window(
                want, -jnp.inf, jax.lax.max, (1, 1, pk, pk),
                (1, 1, ps, ps), "VALID")
        entry["us"] = us
        entry["max_abs_err"] = float(jnp.max(jnp.abs(got - want)))
        wide.append(entry)
        sp = entry["search_fp32"]
        rows.append((
            f"kernels.tiling_search.wide.{name}", us,
            f"greedy={'raises' if 'error' in entry['greedy_fp32'] else 'ok'}"
            f" grid={sp['n_h_blocks']}x{sp['n_w_blocks']}"
            f" tile={sp['tile_h']}x{sp['tile_w']}"
            f" max_abs_err={entry['max_abs_err']:.3e}"))

    fname = "BENCH_tiling_search_smoke.json" if smoke \
        else "BENCH_tiling_search.json"
    totals = {"n_shapes": len(entries), "n_wide": len(wide),
              "wide_greedy_rejected": sum(
                  1 for e in wide if "error" in e["greedy_fp32"]),
              "max_wide_abs_err": max(
                  (e["max_abs_err"] for e in wide), default=0.0)}
    for policy in ("fp32", "bf16"):
        totals[f"launches_greedy_{policy}"] = sum(
            e[policy]["greedy"]["launches"] for e in entries)
        totals[f"launches_search_{policy}"] = sum(
            e[policy]["search"]["launches"] for e in entries)
        totals[f"n_reduced_{policy}"] = sum(
            e[policy]["search"]["launches"] < e[policy]["greedy"]["launches"]
            for e in entries)
    path = save_json("", fname, {"smoke": smoke, "entries": entries,
                                 "wide": wide, "totals": totals})
    rows.append(("kernels.tiling_search.json", None, path))
    return rows


def kernel_summary_report(smoke: bool = False) -> list[tuple]:
    """Aggregate the kernel JSON artefacts of this run into one stable
    headline series, BENCH_kernel_summary{_smoke}.json: total launches
    (greedy vs search, fp32 vs bf16), max per-tile VMEM, fused-vs-unfused
    and dtype aggregates.  Sections whose artefact is absent (e.g. the
    fusion report has no smoke variant) are skipped, so the summary is
    emittable from both the full bench and the CI smoke gate."""
    sfx = "_smoke" if smoke else ""
    out_dir = ensure_out("")

    def load(name):
        p = os.path.join(out_dir, name)
        if not os.path.exists(p):
            return None
        with open(p) as f:
            return json.load(f)

    summary = {"smoke": smoke, "sections": {}}
    fusion = load("BENCH_conv_fusion.json") if not smoke else None
    if fusion:
        ratios = sorted(t["unfused_us"] / t["fused_us"]
                        for t in fusion["triples"] if t["fused_us"])
        summary["sections"]["conv_fusion"] = {
            **fusion["totals"],
            "median_unfused_over_fused_us": ratios[len(ratios) // 2],
        }
    dtype = load(f"BENCH_dtype_sweep{sfx}.json")
    if dtype:
        summary["sections"]["dtype_sweep"] = dict(dtype["totals"])
    tiling = load(f"BENCH_tiling_search{sfx}.json")
    if tiling:
        sec = dict(tiling["totals"])
        vmems = [e[p]["search"]["vmem_bytes"]
                 for e in tiling["entries"] for p in ("fp32", "bf16")] + \
                [e["search_fp32"]["vmem_bytes"] for e in tiling["wide"]]
        sec["max_vmem_bytes_per_tile"] = max(vmems, default=0)
        summary["sections"]["tiling_search"] = sec
    quant = load(f"BENCH_boundary_quant{sfx}.json")
    if quant:
        summary["sections"]["boundary_quant"] = dict(quant["totals"])
    head = {}
    ts = summary["sections"].get("tiling_search", {})
    if ts:
        head["total_launches_greedy_fp32"] = ts.get("launches_greedy_fp32")
        head["total_launches_search_fp32"] = ts.get("launches_search_fp32")
        head["total_launches_search_bf16"] = ts.get("launches_search_bf16")
        head["max_vmem_bytes_per_tile"] = ts.get("max_vmem_bytes_per_tile")
        head["wide_shapes_unlocked"] = ts.get("wide_greedy_rejected")
    ds = summary["sections"].get("dtype_sweep", {})
    if "wire_bytes_int8" in ds:
        head["wire_bytes_fp32"] = ds["wire_bytes_fp32"]
        head["wire_bytes_int8"] = ds["wire_bytes_int8"]
    bq = summary["sections"].get("boundary_quant", {})
    if bq:
        head["min_boundary_int8_reduction"] = bq.get("min_int8_reduction")
        head["min_top1_agreement_int8"] = bq.get("min_top1_agreement_int8")
    summary["headline"] = head
    path = save_json("", f"BENCH_kernel_summary{sfx}.json", summary)
    return [("kernels.summary.json", None, path)]


def run_smoke() -> list[tuple]:
    """One tiny shape per kernel family, in seconds: the CI bench-smoke
    gate that keeps the bench path itself from rotting."""
    rows = []
    key = jax.random.PRNGKey(0)

    # conv family (tiled kernel + fused triple + dtype sweep JSON)
    rows += dtype_sweep_report(smoke=True)

    # wide-input column tiling (one shape per conv family, tiny budget)
    rows += tiling_search_report(smoke=True)

    # boundary quantize: one AlexNet-pool5-sized activation
    from repro.kernels.quant import quantize_boundary
    xq = jax.random.normal(key, (1, 256, 6, 6), jnp.float32)
    us = time_us(lambda: jax.block_until_ready(quantize_boundary(xq)),
                 repeats=1)
    rows.append(("kernels.smoke.quantize_boundary.256x6x6", us,
                 "per-channel int8 + fp32 scales"))

    # flash attention: one 128-token tile pair
    B, S, H, KV, hd = 1, 128, 2, 1, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd),
                          jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd),
                          jnp.float32) * 0.3
    us = time_us(lambda: jax.block_until_ready(
        ops.flash_attention_gqa(q, k, v, block_q=64, block_k=64)),
        repeats=1)
    rows.append(("kernels.smoke.flash_attention.128x64", us, "interpret"))

    # rwkv6 wkv: 32 tokens x 1 head
    r = jax.random.normal(key, (1, 32, 1, 32)) * 0.3
    kk = jax.random.normal(jax.random.fold_in(key, 4), (1, 32, 1, 32)) * 0.3
    vv = jax.random.normal(jax.random.fold_in(key, 5), (1, 32, 1, 32)) * 0.3
    ww = jax.nn.sigmoid(
        jax.random.normal(jax.random.fold_in(key, 6), (1, 32, 1, 32))) \
        * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(key, 7), (1, 32)) * 0.1
    us = time_us(lambda: jax.block_until_ready(
        ops.rwkv6_wkv(r, kk, vv, ww, u, block_t=16)), repeats=1)
    rows.append(("kernels.smoke.rwkv6_wkv.32tok", us, "interpret"))

    # mamba2 ssd: 64 tokens
    x2 = jax.random.normal(key, (1, 64, 1, 16)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 8),
                                           (1, 64, 1)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 9), (1,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 10), (1, 64, 1, 8)) * 0.4
    Cm = jax.random.normal(jax.random.fold_in(key, 11), (1, 64, 1, 8)) * 0.4
    us = time_us(lambda: jax.block_until_ready(
        ops.mamba2_ssd(x2, dt, A, Bm, Cm, chunk=32)), repeats=1)
    rows.append(("kernels.smoke.mamba2_ssd.64tok", us, "interpret"))
    return rows


def run_all(smoke: bool = False) -> list[tuple]:
    if smoke:
        return run_smoke()
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention: seq 512, hd 128 (MXU-aligned)
    B, S, H, KV, hd = 1, 512, 4, 2, 128
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd),
                          jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd),
                          jnp.float32) * 0.3
    us = time_us(lambda: jax.block_until_ready(
        ops.flash_attention_gqa(q, k, v)), repeats=3)
    flops = 2 * B * H * S * S * hd * 2 / 2        # causal halves the work
    rows.append(("kernels.flash_attention.512x128", us,
                 f"analytic_v5e_us={flops / V5E_PEAK_FLOPS_BF16 * 1e6:.2f}"))

    # reference attention for the same shape (oracle cost)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = jnp.repeat(k, H // KV, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vf = jnp.repeat(v, H // KV, 2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    jref = jax.jit(lambda a, b, c: ref.attention_ref(a, b, c))
    us = time_us(lambda: jax.block_until_ready(jref(qf, kf, vf)), repeats=3)
    rows.append(("kernels.attention_ref.512x128", us, "xla_dense"))

    # conv2d: AlexNet conv2 shape
    x = jax.random.normal(key, (1, 64, 27, 27), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 3), (192, 64, 5, 5),
                          jnp.float32) * 0.1
    us = time_us(lambda: jax.block_until_ready(
        ops.conv2d(x, w, stride=1, pad=2)), repeats=3)
    flops = 2 * 25 * 64 * 192 * 27 * 27
    rows.append(("kernels.conv2d.alexnet_conv2", us,
                 f"analytic_v5e_us={flops / V5E_PEAK_FLOPS_BF16 * 1e6:.2f}"))
    jconv = jax.jit(lambda a, b: ref.conv2d_ref(a, b, stride=1, pad=2))
    us = time_us(lambda: jax.block_until_ready(jconv(x, w)), repeats=3)
    rows.append(("kernels.conv2d_ref.alexnet_conv2", us, "xla_conv"))

    # fused conv+bias+relu: one tiled-kernel launch where the seed path
    # needed three ops (conv kernel, XLA bias broadcast, XLA relu)
    bias = jax.random.normal(jax.random.fold_in(key, 12), (192,)) * 0.1
    us = time_us(lambda: jax.block_until_ready(
        ops.conv2d(x, w, stride=1, pad=2, bias=bias, activation="relu")),
        repeats=3)
    rows.append(("kernels.conv2d_fused.alexnet_conv2", us,
                 "1_launch_vs_seed_3_ops"))
    jseed = jax.jit(lambda a, b, c: jax.nn.relu(
        ref.conv2d_ref(a, b, stride=1, pad=2) + c[None, :, None, None]))
    us = time_us(lambda: jax.block_until_ready(jseed(x, w, bias)), repeats=3)
    rows.append(("kernels.conv2d_unfused3.alexnet_conv2", us,
                 "xla_conv+bias+relu"))

    # the VMEM-busting shapes the seed kernel (whole-image staging) could
    # not hold in a 16 MB core: VGG16 conv1-conv3 + MobileNetV2 dw convs
    conv_shapes = [  # name, cin, hw, cout, K, stride, pad, groups
        ("vgg16_conv1", 3, 224, 64, 3, 1, 1, 1),
        ("vgg16_conv2", 64, 224, 64, 3, 1, 1, 1),
        ("vgg16_conv3", 64, 112, 128, 3, 1, 1, 1),
        ("mbv2_dw_s2_96", 96, 112, 96, 3, 2, 1, 96),
        ("mbv2_dw_s1_384", 384, 14, 384, 3, 1, 1, 384),
    ]
    for name, cin, hw, cout, K, s, p, g in conv_shapes:
        xc = jax.random.normal(key, (1, cin, hw, hw), jnp.float32) * 0.3
        wc = jax.random.normal(jax.random.fold_in(key, 13),
                               (cout, cin // g, K, K), jnp.float32) * 0.1
        bc = jax.random.normal(jax.random.fold_in(key, 14),
                               (cout,), jnp.float32) * 0.1
        plan = plan_conv(xc.shape, wc.shape, stride=s, pad=p, groups=g)
        us = time_us(lambda: jax.block_until_ready(
            ops.conv2d(xc, wc, stride=s, pad=p, bias=bc,
                       activation="relu", groups=g)), repeats=3)
        h_out = (hw + 2 * p - K) // s + 1
        flops = 2 * K * K * (cin // g) * cout * h_out * h_out
        rows.append((f"kernels.conv2d_tiled.{name}", us,
                     f"tile_h={plan.tile_h} vmem_bytes={plan.vmem_bytes} "
                     f"analytic_v5e_us="
                     f"{flops / V5E_PEAK_FLOPS_BF16 * 1e6:.2f}"))
        jc = jax.jit(functools.partial(ref.conv2d_ref, stride=s, pad=p,
                                       bias=bc, activation="relu", groups=g))
        us = time_us(lambda: jax.block_until_ready(jc(xc, wc)), repeats=3)
        rows.append((f"kernels.conv2d_ref.{name}", us, "xla_conv"))

    # fused conv+relu+maxpool triples (AlexNet/VGG16) + BENCH_conv_fusion
    rows += conv_fusion_report()

    # fp32 vs bf16 storage sweep (planner + parity) + BENCH_dtype_sweep
    rows += dtype_sweep_report()

    # greedy-vs-search tiling + wide-input sweep + BENCH_tiling_search
    rows += tiling_search_report()

    # boundary quantize at the paper splits: AlexNet pool5 (flat
    # scale-heavy boundary) and VGG16 pool4 (bulk 512-channel map)
    from repro.kernels.quant import quantize_boundary
    for qname, qshape in (("alexnet_pool5", (1, 256, 6, 6)),
                          ("vgg16_pool4", (1, 512, 28, 28))):
        xq = jax.random.normal(key, qshape, jnp.float32)
        us = time_us(lambda: jax.block_until_ready(quantize_boundary(xq)),
                     repeats=3)
        rows.append((f"kernels.quantize_boundary.{qname}", us,
                     "per-channel int8 + fp32 scales"))

    # rwkv6 wkv: 64 tokens x 2 heads
    b, t, h, hd2 = 1, 64, 2, 64
    r = jax.random.normal(key, (b, t, h, hd2)) * 0.3
    kk = jax.random.normal(jax.random.fold_in(key, 4), (b, t, h, hd2)) * 0.3
    vv = jax.random.normal(jax.random.fold_in(key, 5), (b, t, h, hd2)) * 0.3
    ww = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 6),
                                          (b, t, h, hd2))) * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(key, 7), (h, hd2)) * 0.1
    us = time_us(lambda: jax.block_until_ready(
        ops.rwkv6_wkv(r, kk, vv, ww, u, block_t=32)), repeats=3)
    rows.append(("kernels.rwkv6_wkv.64tok", us, "interpret"))

    # mamba2 ssd: 128 tokens
    x2 = jax.random.normal(key, (1, 128, 2, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 8),
                                           (1, 128, 2)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 9), (2,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 10), (1, 128, 2, 16)) * 0.4
    Cm = jax.random.normal(jax.random.fold_in(key, 11), (1, 128, 2, 16)) * 0.4
    us = time_us(lambda: jax.block_until_ready(
        ops.mamba2_ssd(x2, dt, A, Bm, Cm, chunk=64)), repeats=3)
    rows.append(("kernels.mamba2_ssd.128tok", us, "interpret"))
    return rows

"""Bring-up smoke test of the split-serving path on one TPU chip.

Run from the root of a checkout: ``python chip_smoke.py``.  It needs a TPU:
when JAX's first device is not one it exits 1 without printing a result.
Every phase runs in this one process (a chip belongs to one process):

  (a) serve   -- ``repro.launch.serve.main`` in-process: VGG16 over a
      3-tier chain (batch 4, 4 requests, ``ChainRuntime``), then AlexNet
      as 16 concurrent requests through ``CnnServingEngine``, on the
      default conv backend.  Logits are checked against a monolithic
      ``apply_cnn`` of the same inputs.
  (b) pallas  -- ``apply_cnn(..., backend="pallas")`` for AlexNet, VGG16
      and MobileNetV2 at 224 px, fp32 and bf16 storage, against a plain
      float32 ``backend="xla"`` reference; both run at the highest matmul
      precision, so only the conv kernels differ.  One conv is lowered
      and must contain a Mosaic kernel (``tpu_custom_call``): interpret
      mode cannot pass unseen.
  (c) runtime -- ``ChainRuntime`` on a clean 2-tier chain with the int8
      wire on the pallas backend: logits bit-identical to
      ``apply_split(..., wire="int8")`` on the same device.

Earlier lines give shapes, compile seconds and per-request wall seconds
(host clock around work that ends in ``block_until_ready``); numbers from
the virtual clock are labelled modelled.  The last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MODELS = ("alexnet", "vgg16", "mobilenetv2")
# max |got - ref| / max |ref| on the logits of phases (a) and (b)
SERVE_TOL = 1e-2     # default matmul precision, request batching may differ
# pallas vs the float32 reference: bf16 storage rounds every activation of
# a ~50-conv walk (MobileNetV2 has no batch norm), so XLA's own bf16 path
# is 3e-2..6e-2 off the reference; against that bf16 path the kernel is
# held to BF16_VS_XLA_TOL
PALLAS_TOL = {"fp32": 1e-4, "bf16": 1e-1}
BF16_VS_XLA_TOL = 2e-2


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _check(name: str, got, want, tol: float) -> None:
    import numpy as np
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(np.asarray(got, np.float32))):
        raise AssertionError(f"{name}: non-finite logits")
    err = _rel_err(got, want)
    print(f"  {name}: logits {tuple(got.shape)} rel err {err:.3e} "
          f"(tol {tol:g})", flush=True)
    if not err <= tol:
        raise AssertionError(f"{name}: rel err {err:.3e} > {tol:g}")


def phase_serve() -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.models import cnn

    out = serve.main(["--cnn", "vgg16", "--tiers", "3", "--batch", "4",
                      "--requests", "4"])
    if out["stats"]["requests"] != 4:
        raise AssertionError(f"vgg16 chain served {out['stats']}")
    want = cnn.apply_cnn(cnn.CNN_MODELS["vgg16"], out["params"], out["x"])
    _check("vgg16 3-tier ChainRuntime vs apply_cnn", out["logits"],
           jax.block_until_ready(want), SERVE_TOL)

    out = serve.main(["--cnn", "alexnet", "--tiers", "3",
                      "--concurrency", "16"])
    s = out["stats"]
    if s["served"] != 16 or out["logits"] is None:
        raise AssertionError(f"engine served {s['served']}/16")
    want = cnn.apply_cnn(cnn.CNN_MODELS["alexnet"], out["params"],
                         jnp.asarray(out["x"]))
    _check("alexnet CnnServingEngine x16 vs apply_cnn", out["logits"],
           jax.block_until_ready(want), SERVE_TOL)


def check_mosaic_kernel() -> None:
    """The pallas conv path must lower to a compiled Mosaic kernel."""
    import jax
    from repro.kernels import ops
    x = jax.ShapeDtypeStruct((1, 64, 56, 56), "float32")
    w = jax.ShapeDtypeStruct((64, 64, 3, 3), "float32")
    hlo = jax.jit(lambda a, b: ops.conv2d(a, b, stride=1, pad=1)) \
        .lower(x, w).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("lowered conv holds no Mosaic kernel: the "
                             "pallas backend is not compiled")
    print("  conv 64->64 3x3 on (1, 64, 56, 56) lowers to a Mosaic kernel "
          "(tpu_custom_call)", flush=True)


def phase_pallas(in_shape=None, models=MODELS) -> None:
    import jax
    from repro.models import cnn

    check_mosaic_kernel()
    in_shape = in_shape or cnn.INPUT_SHAPE
    x = jax.random.normal(jax.random.PRNGKey(0), (1,) + in_shape) * 0.5
    with jax.default_matmul_precision("highest"):
        for model in models:
            layers = cnn.CNN_MODELS[model]
            params = cnn.init_cnn(jax.random.PRNGKey(1), layers, in_shape)
            t0 = time.perf_counter()
            want = jax.block_until_ready(
                cnn.apply_cnn(layers, params, x, backend="xla",
                              dtype="fp32"))
            print(f"  {model} xla fp32 reference: input {tuple(x.shape)} "
                  f"first call {time.perf_counter() - t0:.3f}s wall",
                  flush=True)
            for dtype in ("fp32", "bf16"):
                walls = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    got = jax.block_until_ready(
                        cnn.apply_cnn(layers, params, x, backend="pallas",
                                      dtype=dtype))
                    walls.append(time.perf_counter() - t0)
                warm = min(walls[1:])
                print(f"  {model} pallas {dtype}: first call {walls[0]:.3f}s"
                      f" wall (~{walls[0] - warm:.3f}s compiling), then "
                      f"{warm:.4f}s per request (batch 1, compiled walk)",
                      flush=True)
                _check(f"{model} pallas {dtype} vs xla fp32", got, want,
                       PALLAS_TOL[dtype])
                if dtype == "bf16":
                    _check(f"{model} pallas bf16 vs xla bf16", got,
                           jax.block_until_ready(cnn.apply_cnn(
                               layers, params, x, backend="xla",
                               dtype="bf16")), BF16_VS_XLA_TOL)


def phase_runtime(in_shape=None, model: str = "alexnet") -> None:
    import jax
    import numpy as np
    from repro.core import paper_chain, smartsplit_chain
    from repro.models import cnn
    from repro.models.profiles import cnn_profile
    from repro.runtime import ChainRuntime

    in_shape = in_shape or cnn.INPUT_SHAPE
    layers = cnn.CNN_MODELS[model]
    params = cnn.init_cnn(jax.random.PRNGKey(2), layers, in_shape)
    x = jax.random.normal(jax.random.PRNGKey(3), (2,) + in_shape)
    hw = paper_chain(2)
    prof = cnn_profile(model, batch=2, in_shape=in_shape, dtype="fp32")
    plan = smartsplit_chain(prof, hw, wire="int8")
    rt = ChainRuntime(model, params, plan, prof, hw, backend="pallas",
                      dtype="fp32", wire="int8")
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        r = rt.infer(x)
        jax.block_until_ready(r.logits)
        walls.append(time.perf_counter() - t0)
    want, boundary = cnn.apply_split(layers, params, x, plan.cuts[0],
                                     backend="pallas", dtype="fp32",
                                     wire="int8")
    hop = rt.stats()["hops"][0]
    print(f"  {model} 2-tier int8 wire, cut {plan.cuts[0]}/{len(layers)}: "
          f"boundary {tuple(boundary.shape)} sent {hop['wire_bytes']}B "
          f"(raw {hop['raw_bytes']}B); first request {walls[0]:.3f}s wall "
          f"(compiles included), then {min(walls[1:]):.4f}s", flush=True)
    if hop["wire_dtype"] != "int8" or hop["attempts"] != 3:
        raise AssertionError(f"hop did not ship int8 cleanly: {hop}")
    if not np.array_equal(np.asarray(r.logits), np.asarray(want)):
        raise AssertionError(
            f"ChainRuntime int8 logits differ from apply_split: rel err "
            f"{_rel_err(r.logits, want):.3e}")
    print("  ChainRuntime logits bit-identical to apply_split(wire=int8)",
          flush=True)


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}",
          flush=True)
    t_all = time.perf_counter()
    for name, phase in (("a: serve entry point", phase_serve),
                        ("b: pallas backend, compiled", phase_pallas),
                        ("c: runtime, pallas + int8 wire", phase_runtime)):
        print(f"phase {name}", flush=True)
        t0 = time.perf_counter()
        phase()
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s",
              flush=True)
    print(f"all phases ok in {time.perf_counter() - t_all:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""A whole run on the CPU at a tiny size, with the chip check skipped: the
served logits pass the comparison, and with the timed path broken
underneath ``correct`` comes out false.  Also the control (the reference
at three bf16 passes in the program's place) reads above the limit."""
import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, spec

CONV = {"kind": "conv", "cout": 8, "ksize": 3, "pad": 1}
# tiny stand-ins of the two configurations: the same kinds of layers,
# chain, wire and checks, at 16 px and a few channels
TINY_LAYERS = {
    "vgg16-fp32-chain3": [
        CONV, {"kind": "relu"}, CONV, {"kind": "relu"},
        {"kind": "maxpool", "ksize": 2, "stride": 2},
        dict(CONV, cout=16), {"kind": "relu"},
        {"kind": "maxpool", "ksize": 2, "stride": 2},
        {"kind": "avgpool", "out_hw": 2}, {"kind": "linear", "features": 32},
        {"kind": "relu"}, {"kind": "dropout"},
        {"kind": "linear", "features": 10}],
    "mbv2-fp32-chain3-int8": [
        {"kind": "conv", "cout": 8, "ksize": 3, "stride": 2, "pad": 1},
        {"kind": "relu6"}, {"kind": "invres", "cout": 8, "expand": 1},
        {"kind": "invres", "cout": 12, "stride": 2, "expand": 6},
        {"kind": "invres", "cout": 12, "expand": 6},
        {"kind": "conv", "cout": 32, "ksize": 1}, {"kind": "relu6"},
        {"kind": "dropout"},
        {"kind": "gap_linear", "features": 10}],
}


MIXES = {"open": {"loop": "open", "rate_rps": 40.0, "images": 16},
         "closed": {"loop": "closed", "clients": 4, "images": 16}}


def tiny_cell(config: str, backend: str = "pallas", rate: float = 40.0,
              samples: int = 8, loop: str = "open"):
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    cfg.update(in_shape=[3, 16, 16], layers=TINY_LAYERS[config],
               backend=backend, check=dict(cfg["check"], samples=samples))
    mix = dict(MIXES[loop], **({"rate_rps": rate} if loop == "open" else {}))
    bench = spec.load_benchmark()
    e2e = tuple(spec._metric(m) for m in bench["end_to_end"])
    return spec.Cell(f"{config}.tiny", cfg, mix, 1, e2e, ())


@pytest.fixture
def cpu_run(tmp_path, monkeypatch):
    """Runs a cell on the CPU; JAX's process-wide settings come back
    afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    names = ("jax_default_matmul_precision", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}

    def run(cell, seed=2**31 + 5, seconds=0.6):
        return harness.run_cell(cell, seed, seconds, False,
                                time.perf_counter(), platform="cpu",
                                root=str(tmp_path))
    yield run
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("loop", sorted(MIXES))
@pytest.mark.parametrize("config", sorted(TINY_LAYERS))
def test_clean_run_is_correct(cpu_run, config, loop):
    out = cpu_run(tiny_cell(config, loop=loop))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    # open: rate x window requests; closed: at least one round per client
    assert out["attempted"] == 24 if loop == "open" else \
        out["attempted"] >= 8
    assert list(out)[-1] == "checks"
    for name in tiny_cell(config).config["check"]["limits"]:
        assert out["checks"][name]["value"] < 1e-5
    names = {m["name"] for m in spec.load_benchmark()["end_to_end"]}
    assert {"p50_ms", "throughput_rps", "setup_s"} <= names == \
        set(out["metrics"])


def test_a_reader_sees_the_engine_counters(cpu_run, monkeypatch):
    """``ctx["engine_stats"]`` holds the engine's whole ``stats()`` at the
    window's start and after its drain, beside ``engine_window``."""
    seen = {}

    def reader(name):
        def read(ctx):
            seen[name] = ctx["engine_stats"], ctx["engine_window"]
            return 1.0
        return read
    monkeypatch.setattr(spec, "load_reader", reader)
    served = spec.Metric("served_rps", "req/s")
    cell = dataclasses.replace(tiny_cell("vgg16-fp32-chain3", "xla"),
                               end_to_end=(served,))
    out = cpu_run(cell)
    stats, window = seen["served_rps"]
    assert set(stats) == {"before", "after"}
    assert set(stats["before"]) == set(stats["after"]) >= {
        "served", "batches", "hops", "buckets", "walk_traces"}
    for k in ("served", "batches"):
        assert stats["after"][k] - stats["before"][k] == window[k]
    assert window["served"] == out["attempted"] - out["failed"] > 0


def _altered_answer(monkeypatch):
    """Every answer has its first logit moved where it is produced, by 1%
    of its largest logit."""
    from repro.runtime import runtime
    infer = runtime.ChainRuntime.infer

    def broken(self, x, **kw):
        res = infer(self, x, **kw)
        bump = 1e-2 * np.max(np.abs(np.asarray(res.logits)))
        return dataclasses.replace(res, logits=res.logits.at[:, 0].add(bump))
    monkeypatch.setattr(runtime.ChainRuntime, "infer", broken)


def _half_batch(monkeypatch):
    """Only the first half of each batch is computed; the rest of the
    answers are copies of it."""
    from repro.runtime import runtime
    infer = runtime.ChainRuntime.infer

    def broken(self, x, **kw):
        n = int(x.shape[0])
        res = infer(self, x[:(n + 1) // 2], **kw)
        idx = np.arange(n) % ((n + 1) // 2)
        return dataclasses.replace(
            res, logits=res.logits[idx],
            microbatch_finish_s=tuple(res.microbatch_finish_s[i]
                                      for i in idx))
    monkeypatch.setattr(runtime.ChainRuntime, "infer", broken)


@pytest.mark.parametrize("config", sorted(TINY_LAYERS))
@pytest.mark.parametrize("fault", [_altered_answer, _half_batch])
def test_broken_path_is_not_correct(cpu_run, monkeypatch, fault, config):
    fault(monkeypatch)
    # arrivals faster than the steps, so that batches of several form, and
    # every served request compared
    out = cpu_run(tiny_cell(config, "xla", rate=200.0, samples=1000))
    assert out["attempted"] > 8
    assert not out["correct"], out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("config", sorted(TINY_LAYERS))
def test_conv_launches_match_the_walk(monkeypatch, config):
    """The benchmark's count of conv kernel calls per request, which the
    roofline reader holds the trace's calls against, is the number of
    calls one served request makes through the engine."""
    import jax
    from repro.kernels import ops
    from bench import flops, weights
    cfg = tiny_cell(config).config
    shape = tuple(cfg["in_shape"])
    params = weights.make_params(7, cfg["layers"], shape)
    images = weights.make_images(7, 2, shape)
    engine = harness.build_engine(cfg, params)
    calls = []
    conv = ops._conv2d

    def counted(*args, **kw):
        calls.append(kw.get("groups"))
        return conv(*args, **kw)
    monkeypatch.setattr(ops, "_conv2d", counted)
    req = engine.submit(images[0])
    engine.run_until_idle()
    jax.block_until_ready(req.logits)
    cuts = tuple(engine.stats()["buckets"][0]["cuts"])
    assert req.status == "served"
    assert len(calls) == len(flops.conv_launches(cfg["layers"], shape, 4,
                                                 cuts))


@pytest.mark.parametrize("config,cuts", [("vgg16-fp32-chain3", (17, 31)),
                                         ("mbv2-fp32-chain3-int8", (6, 16))])
def test_control_reads_above_the_limit(config, cuts):
    """The control, the reference at three bf16 passes per contraction in
    the program's place, fails the configuration's limit on every seed:
    the configuration's own layers and cuts, at 64 px to fit a test."""
    import jax.numpy as jnp
    from bench import reference, weights
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    layers, shape = cfg["layers"], (3, 64, 64)
    kw = dict(cuts=cuts, wires=harness.wire_formats(cfg, 2))
    for seed in (3, 4, 2**31 + 17):
        params = weights.make_params(seed, layers, shape)
        x = jnp.stack(weights.make_images(seed, 8, shape))
        want = np.asarray(reference.forward(layers, params, x, **kw))
        ctrl = np.asarray(reference.forward(layers, params, x, mode="bf16x3",
                                            **kw))
        checks = harness.gap_checks(cfg, harness.rel_err(ctrl, want))
        assert any(c["value"] > c["limit"] for c in checks.values()), checks


def test_run_py_without_a_chip_prints_no_result():
    root = spec.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "vgg16-fp32-chain3.steady", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "needs a tpu" in proc.stderr

"""The benchmark's yardstick on the CPU: traffic generator, FLOP counter,
peaks table and the files ``BENCHMARK.json`` names."""
import collections
import json
import math
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import flops, peaks, spec, traffic

CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


@pytest.mark.parametrize("rate,seconds", [(80.0, 20.0), (43.5, 7.0)])
def test_open_schedule_repeats_per_seed_and_differs_across_seeds(rate,
                                                                 seconds):
    a = traffic.open_schedule(2**31 + 11, rate, seconds)
    b = traffic.open_schedule(2**31 + 11, rate, seconds)
    c = traffic.open_schedule(12, rate, seconds)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every seed offers the same work: same count, same multiset of gaps
    # (the last one runs to the window's end)
    assert len(a) == len(c) == round(rate * seconds)
    # every seed offers the same arrivals: the same ring of gaps (the last
    # one runs to the window's end), rotated
    ga, gc = np.diff(np.append(a, seconds)), np.diff(np.append(c, seconds))
    shift = [k for k in range(len(ga))
             if np.allclose(np.roll(ga, k), gc, rtol=1e-9, atol=1e-12)]
    assert len(shift) == 1 and shift[0] != 0
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < seconds


def test_open_schedule_gaps_are_exponential():
    due = traffic.open_schedule(5, 100.0, 200.0)
    gaps = np.diff(due)
    assert abs(np.mean(gaps) - 0.01) < 0.0005
    # exponential: the median gap is ln 2 times the mean
    assert abs(np.median(gaps) / np.mean(gaps) - math.log(2)) < 0.03


def test_traffic_files_are_refused_when_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"loop": "open", "images": 4}))
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.load(str(bad))
    bad.write_text(json.dumps({"loop": "sideways", "images": 4}))
    with pytest.raises(ValueError, match="loop"):
        traffic.load(str(bad))
    bad.write_text(json.dumps({"loop": "closed", "images": 4}))
    with pytest.raises(ValueError, match="clients"):
        traffic.load(str(bad))


def test_knee_is_highest_rate_that_keeps_up():
    knee = traffic.knee_rate([40, 60, 80, 90, 100],
                             [400, 600, 795, 900, 900],
                             [400, 600, 800, 910, 1000],
                             [0.1, 0.5, 1.2, 1.5, 20.0],
                             [0.1, 0.4, 2.1, 2.7, 40.0])
    assert knee == 80     # 90 answered under 99%; at 100 the queue grew


@pytest.mark.parametrize("config", CONFIGS)
def test_flop_counter_matches_program_at_every_layer(config):
    """At the configuration's own input size, layer by layer; the layers
    its data file lists under ``program_lacks`` are the file's only
    departures from the program's model (torchvision's MobileNetV2 has a
    ReLU6 after the stem and the last conv, the program's has not)."""
    from repro.models import cnn
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    layers = cfg["layers"]
    lacks = set(spec.load_checks(config)["program_lacks"])
    program = [cnn.Layer(**mine) for i, mine in enumerate(layers)
               if i not in lacks]
    assert program == cnn.CNN_MODELS[cfg["model"]]
    shape = tuple(cfg["in_shape"])
    for mine in layers:
        theirs = cnn.Layer(**mine)
        assert flops.layer_flops_params(mine, shape) == \
            cnn.layer_flops_params(theirs, shape)
        nxt = flops.out_shape(mine, shape)
        assert nxt == tuple(cnn.layer_out_shape(theirs, shape))
        shape = nxt


@pytest.mark.parametrize("config", CONFIGS)
def test_model_totals(config):
    """FLOPs per image at the configuration's input size, and the conv
    kernel calls of one request at the stated cuts, as its data file
    gives them."""
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    totals = spec.load_checks(config)["totals"]
    shape = tuple(cfg["in_shape"])
    assert flops.model_flops(cfg["layers"], shape) == \
        pytest.approx(totals["flops"], rel=totals["flops_rel"])
    assert len(flops.conv_launches(cfg["layers"], shape, 4,
                                   tuple(totals["cuts"]))) == \
        totals["conv_calls"]


def test_conv_launches_split_fusion_at_a_cut():
    layers = [{"kind": "conv", "cout": 4, "ksize": 3, "pad": 1},
              {"kind": "relu"}, {"kind": "maxpool", "ksize": 2, "stride": 2}]
    fused = flops.conv_launches(layers, (2, 8, 8), 4)
    cut = flops.conv_launches(layers, (2, 8, 8), 4, cuts=(2,))
    assert len(fused) == len(cut) == 1
    assert fused[0]["flops"] == cut[0]["flops"] == 2 * 9 * 2 * 4 * 64
    # the pooled output (4x4x4) vs the unpooled one (4x8x8)
    assert fused[0]["bytes"] == 4 * (2 * 64 + 9 * 2 * 4 + 4 + 4 * 16)
    assert cut[0]["bytes"] == 4 * (2 * 64 + 9 * 2 * 4 + 4 + 4 * 64)
    assert fused[0]["groups"] == cut[0]["groups"] == 1
    # an inverted residual block: expand, depthwise (one group a channel),
    # project
    block = [{"kind": "invres", "cout": 4, "stride": 2, "expand": 6}]
    assert [c["groups"] for c in flops.conv_launches(block, (2, 8, 8), 4)] \
        == [1, 12, 1]


def test_peaks_refuse_an_unknown_device():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["peak_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_every_cell_resolves_to_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert os.path.isfile(spec.traffic_path(w["name"]))
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m.name for m in cell.end_to_end}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_reader(m.name))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"]))
    with pytest.raises(KeyError, match="unknown workload"):
        spec.resolve("no-such-model.steady", bench)
    with pytest.raises(FileNotFoundError):
        spec.reader_path("no_such_metric.lat")


def test_a_configuration_without_its_data_file_is_named(tmp_path):
    want = os.path.join("bench", "tests", "data", "configs",
                        "no-such-model.json")
    with pytest.raises(FileNotFoundError, match="no-such-model") as e:
        spec.load_checks("no-such-model")
    assert want in str(e.value)
    with pytest.raises(FileNotFoundError, match="has no CPU checks"):
        spec.load_checks(CONFIGS[0], str(tmp_path))


# the program's MobileNetV2 (the configuration's layers but the two ReLU6
# that torchvision adds) at 96 px, with the configuration's tiny stand-in
PROBE = "mbv2-probe96"
PER_CONFIG_TESTS = {
    "test_clean_run_is_correct": 2, "test_broken_path_is_not_correct": 2,
    "test_conv_launches_match_the_walk": 1,
    "test_control_reads_above_the_limit": 1,
    "test_weights_and_reference_are_pinned": 1, "test_model_totals": 1,
    "test_flop_counter_matches_program_at_every_layer": 1}


def test_a_new_configuration_is_new_files(tmp_path):
    """A configuration placed in a copy of ``bench/`` and
    ``BENCHMARK.json`` as its file, its data file and an entry in
    ``configs`` gets every per-configuration check, with no other file of
    the copy touched, and passes them."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = "mbv2-fp32-chain3-int8"
    with open(spec.config_path(base)) as f:
        cfg = json.load(f)
    lacks = spec.load_checks(base)["program_lacks"]
    cfg.update(name=PROBE, in_shape=[3, 96, 96],
               layers=[layer for i, layer in enumerate(cfg["layers"])
                       if i not in lacks])
    (tmp_path / "bench" / "configs" / f"{PROBE}.json").write_text(
        json.dumps(cfg))
    checks = dict(spec.load_checks(base), program_lacks=[],
                  control_cuts=[5, 15],
                  totals={"flops": 1.1367e8, "flops_rel": 1e-3,
                          "cuts": [5, 15], "conv_calls": 52})
    (tmp_path / "bench" / "tests" / "data" / "configs" /
     f"{PROBE}.json").write_text(json.dumps(checks))
    bench = spec.load_benchmark()
    bench["configs"].append(dict(bench["configs"][-1], name=PROBE,
                                 file=f"bench/configs/{PROBE}.json"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(tmp_path), os.path.join(spec.ROOT, "src")]))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", PROBE, "bench/tests"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    passed = re.findall(r"::(\w+)\[[^\]]*" + PROBE + r"[^\]]*\] PASSED",
                        proc.stdout)
    assert collections.Counter(passed) == PER_CONFIG_TESTS, proc.stdout[-4000:]

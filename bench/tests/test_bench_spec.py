"""The benchmark's yardstick on the CPU: traffic generator, FLOP counter,
peaks table and the files ``BENCHMARK.json`` names."""
import json
import math
import os

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
    from repro.models import cnn
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    layers = cfg["layers"]
    # the program's paper layers, and the file's only departure from them:
    # torchvision's MobileNetV2 has a ReLU6 after the stem and the last conv
    program = [cnn.Layer(**mine) for mine in layers
               if not (config.startswith("mbv2") and mine["kind"] == "relu6")]
    assert program == cnn.CNN_MODELS[cfg["model"]]
    shape = tuple(cfg["in_shape"])
    assert shape == cnn.INPUT_SHAPE
    for mine in layers:
        theirs = cnn.Layer(**mine)
        assert flops.layer_flops_params(mine, shape) == \
            cnn.layer_flops_params(theirs, shape)
        nxt = flops.out_shape(mine, shape)
        assert nxt == tuple(cnn.layer_out_shape(theirs, shape))
        shape = nxt


def test_model_totals():
    with open(spec.config_path("vgg16-fp32-chain3")) as f:
        vgg = json.load(f)
    with open(spec.config_path("mbv2-fp32-chain3-int8")) as f:
        mbv2 = json.load(f)
    assert flops.model_flops(vgg["layers"], (3, 224, 224)) == \
        pytest.approx(30.96e9, rel=1e-3)
    assert flops.model_flops(mbv2["layers"], (3, 224, 224)) == \
        pytest.approx(0.61e9, rel=0.02)
    assert len(flops.conv_launches(vgg["layers"], (3, 224, 224), 4,
                                   (17, 31))) == 13
    assert len(flops.conv_launches(mbv2["layers"], (3, 224, 224), 4,
                                   (6, 16))) == 52


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

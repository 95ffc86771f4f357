"""Layer kinds as files (``bench/kinds/<kind>.py``): each kind file's
``EXAMPLE`` layer agrees with the program's own layer arithmetic, a new kind
is one new file, and the weights and reference logits the kinds make are
the ones pinned in each configuration's data file
(``data/configs/<config>.json``), recorded before the kinds moved into
files."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import flops, spec

IN_SHAPE = (4, 9, 9)
REQUIRED = ("out_shape", "flops_params", "init", "apply")
KINDS = sorted(name[:-3] for name in os.listdir(
    os.path.join(spec.BENCH_DIR, "kinds")) if name.endswith(".py"))
CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


def example(kind: str, bench_dir: str = spec.BENCH_DIR):
    """The kind file's ``EXAMPLE`` layer and its input shape
    (``EXAMPLE_IN_SHAPE``, else ``IN_SHAPE``)."""
    module = spec.load_kind(kind, bench_dir)
    if not hasattr(module, "EXAMPLE"):
        pytest.fail(f"{spec.kind_path(kind, bench_dir)} has no EXAMPLE "
                    f"layer to hold against the program")
    return module.EXAMPLE, tuple(getattr(module, "EXAMPLE_IN_SHAPE",
                                         IN_SHAPE))


@pytest.mark.parametrize("kind", KINDS)
def test_kind_file_agrees_with_the_program(kind):
    """On the kind file's ``EXAMPLE`` layer: shapes, FLOPs and parameters
    as ``models/cnn.py`` counts them, the weights' layout as the program's
    walk takes it, and the reference's output as the program's XLA path
    computes it."""
    import jax
    import jax.numpy as jnp
    from repro.models import cnn
    module = spec.load_kind(kind)
    for name in REQUIRED:
        assert callable(getattr(module, name)), name
    layer, shape = example(kind)
    assert layer["kind"] == kind
    theirs = cnn.Layer(**layer)
    assert module.out_shape(layer, shape) == \
        tuple(cnn.layer_out_shape(theirs, shape))
    assert module.flops_params(layer, shape) == \
        cnn.layer_flops_params(theirs, shape)
    key = jax.random.PRNGKey(3)
    mine = module.init(key, layer, shape)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(cnn.init_layer(key, theirs, shape))
    assert [leaf.shape for leaf in jax.tree_util.tree_leaves(mine)] == \
        [leaf.shape for leaf in jax.tree_util.tree_leaves(
            cnn.init_layer(key, theirs, shape))]
    x = jax.random.normal(jax.random.PRNGKey(4), (2,) + shape, jnp.float32)
    want = cnn.apply_layer(theirs, mine, x, backend="xla", dtype="fp32")
    got = module.apply(layer, mine, x, "highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_an_unknown_kind_names_its_missing_file():
    layers = [{"kind": "conv", "cout": 4, "ksize": 3, "pad": 1},
              {"kind": "scale"}]
    want = os.path.join("bench", "kinds", "scale.py")
    with pytest.raises(ValueError, match="unknown layer kind 'scale'") as e:
        flops.model_flops(layers, (2, 8, 8))
    assert want in str(e.value)
    from bench import weights
    with pytest.raises(ValueError, match="scale.py"):
        weights.make_params(0, layers, (2, 8, 8))
    with pytest.raises(ValueError, match="unknown layer kind"):
        spec.kind_path("../kinds/conv")


SCALE_KIND = '''
"""Per-channel scale: x * g[c]."""
import math

import jax
import jax.numpy as jnp


def out_shape(layer, in_shape):
    return tuple(in_shape)


def flops_params(layer, in_shape):
    return float(math.prod(in_shape)), in_shape[0]


def init(key, layer, in_shape):
    return {"g": jax.random.normal(key, (in_shape[0],), jnp.float32)}


def apply(layer, p, x, mode):
    return x * p["g"][None, :, None, None]
'''

SCALE_RUN = '''
import json

import jax.numpy as jnp
import numpy as np

from bench import flops, reference, weights

layers = [{"kind": "conv", "cout": 4, "ksize": 3, "pad": 1},
          {"kind": "scale"}, {"kind": "relu"},
          {"kind": "gap_linear", "features": 3}]
shape = (2, 8, 8)
params = weights.make_params(5, layers, shape)
x = jnp.stack(weights.make_images(5, 2, shape))
got = np.asarray(reference.forward(layers, params, x))
y = reference.conv(x, params[0], 1, 1, "highest")
y = jnp.maximum(y * params[1]["g"][None, :, None, None], 0.0)
want = np.asarray(reference.linear(y.mean(axis=(2, 3)), params[3],
                                   "highest"))
print(json.dumps({
    "flops": flops.model_flops(layers, shape),
    "launches": len(flops.conv_launches(layers, shape, 4)),
    "g": list(params[1]["g"].shape),
    "gap": float(np.max(np.abs(got - want)))}))
'''


def test_a_new_kind_is_one_new_file(tmp_path):
    """A ``scale`` layer placed in a copy of ``bench/`` as
    ``kinds/scale.py`` runs through the FLOP counter, the weights and the
    reference with no other file of the copy touched.  It does not fold
    into the conv before it, so the relu after it is not folded either."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench" / "kinds" / "scale.py").write_text(SCALE_KIND)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(SCALE_RUN)],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    conv = 2 * 9 * 2 * 4 * 64
    # conv, scale and relu, then gap_linear's mean and its matmul
    assert out["flops"] == conv + 256 + 256 + (256 + 2 * 4 * 3)
    assert out["launches"] == 1
    assert out["g"] == [4]
    assert out["gap"] < 1e-6


def test_a_kind_file_without_an_example_is_named(tmp_path):
    """The kind test fails on a kind file that has no ``EXAMPLE``, and
    says which file."""
    kinds = tmp_path / "kinds"
    kinds.mkdir()
    (kinds / "scale.py").write_text(SCALE_KIND)
    want = os.path.join(str(kinds), "scale.py")
    with pytest.raises(pytest.fail.Exception, match="has no EXAMPLE") as e:
        example("scale", str(tmp_path))
    assert want in str(e.value)
    shutil.copy(spec.kind_path("relu"), kinds / "relu.py")
    assert example("relu", str(tmp_path)) == ({"kind": "relu"}, IN_SHAPE)


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_and_reference_are_pinned(config):
    """Per-layer weight sums of seed 0 and the reference logits of two
    images of seed 0 (both modes, the wire at the pinned cuts) for the
    tiny stand-in of the configuration's data file."""
    import jax
    import jax.numpy as jnp
    from bench import reference, weights
    checks = spec.load_checks(config)
    pins = checks["pins"]
    layers = checks["tiny"]["layers"]
    shape = tuple(checks["tiny"]["in_shape"])
    params = weights.make_params(0, layers, shape)
    sums = [sum(float(jnp.sum(leaf)) for leaf in jax.tree_util.tree_leaves(p))
            for p in params]
    np.testing.assert_allclose(sums, pins["sums"], rtol=1e-6)
    x = jnp.stack(weights.make_images(0, 2, shape))
    cuts, wires = tuple(pins["cuts"]), tuple(pins["wires"])
    for mode in reference.MODES:
        got = reference.forward(layers, params, x, cuts=cuts, wires=wires,
                                mode=mode)
        np.testing.assert_allclose(np.asarray(got), pins[mode], rtol=1e-6)

"""``apply_cnn`` as one compiled program per stage: partitions of a walk
are bit-identical to the whole compiled walk (xla; within a few ulps on
pallas in interpret mode), the program cache traces once per (range,
knobs, shape), no weight is baked into a program, geometry errors still
name the layer, and a warmed serving engine traces nothing.

Everything runs on the CPU (the pallas backend in interpret mode)."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paper_chain
from repro.models import cnn
from repro.models.cnn import avgpool, conv, linear, maxpool, relu
from repro.serving.cnn_engine import CnnServingEngine

TINY_LAYERS = [conv(8, 3, 1, 1), relu(), maxpool(2, 2),
               conv(16, 3, 1, 1), relu(), avgpool(2), linear(10)]
TINY_SHAPE = (3, 16, 16)
SMALL_SHAPE = (3, 64, 64)
# three fixed 3-stage cuts per paper model: a cut between a conv and its
# activation, one between the activation and its maxpool (inside a fused
# triple on pallas), and the benchmark's own cuts
MODEL_CUTS = {"vgg16": [(1, 38), (2, 20), (17, 31)],
              "mobilenetv2": [(1, 11), (3, 20), (6, 16)]}

_CASES = [("tiny", cuts)
          for cuts in itertools.combinations(range(1, len(TINY_LAYERS)), 2)]
_CASES += [(model, cuts) for model, cut_list in MODEL_CUTS.items()
           for cuts in cut_list]
_IDS = [f"{n}-{c[0]}-{c[1]}" for n, c in _CASES]
# On pallas, XLA fuses the ops around the kernels differently inside
# different programs, so a stage boundary can move the logits by an ulp or
# two (cuts through a fused triple did so eagerly too; MobileNetV2 at
# (3, 20) does so on the chip as well).  A fixed few ulps of the largest
# logit, from float32's epsilon.
PALLAS_ULPS = 8


def _model(name):
    if name == "tiny":
        return TINY_LAYERS, TINY_SHAPE
    return cnn.CNN_MODELS[name], SMALL_SHAPE


_WHOLE: dict[tuple, tuple] = {}


def _logits(y):
    return np.asarray(y.astype(jnp.float32))


def _whole(name, backend, dtype):
    """(params, x, whole-walk logits) per model, backend and dtype,
    computed once."""
    if (name, backend, dtype) not in _WHOLE:
        layers, shape = _model(name)
        params = cnn.init_cnn(jax.random.PRNGKey(0), layers, shape)
        x = jax.random.normal(jax.random.PRNGKey(1), (1,) + shape) * 0.5
        _WHOLE[name, backend, dtype] = (params, x, _logits(cnn.apply_cnn(
            layers, params, x, backend=backend, dtype=dtype)))
    return _WHOLE[name, backend, dtype]


def _partition(name, backend, cuts, dtype="fp32"):
    layers, _ = _model(name)
    params, x, want = _whole(name, backend, dtype)
    bounds = (0, *cuts, len(layers))
    y = x
    for start, stop in zip(bounds, bounds[1:]):
        y = cnn.apply_cnn(layers, params, y, start=start, stop=stop,
                          backend=backend, dtype=dtype)
    return _logits(y), want


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("name,cuts", _CASES, ids=_IDS)
def test_partition_bit_identical_to_whole_walk(name, cuts, dtype):
    """Three stage programs in a row give the whole program's logits bit
    for bit on the xla backend: a cut never changes the arithmetic.  Under
    bf16 this holds only because no program keeps excess precision."""
    got, want = _partition(name, "xla", cuts, dtype)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name,cuts", _CASES, ids=_IDS)
def test_pallas_partition_matches_whole_walk(name, cuts):
    """The same partitions on the pallas backend (interpret mode here):
    equal to the whole program's logits within ``PALLAS_ULPS`` ulps of the
    largest."""
    got, want = _partition(name, "pallas", cuts)
    atol = PALLAS_ULPS * np.finfo(np.float32).eps * np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_cache_hits_and_retraces_on_knob(monkeypatch):
    """A second call with the same range and shape is a hit, not a trace;
    flipping ``REPRO_CONV_TILE_W`` selects (and traces) another program."""
    monkeypatch.delenv("REPRO_CONV_TILE_W", raising=False)
    params = cnn.init_cnn(jax.random.PRNGKey(2), TINY_LAYERS, TINY_SHAPE)
    # a batch no other test uses, so the first call must trace
    x = jax.random.normal(jax.random.PRNGKey(3), (3,) + TINY_SHAPE)

    def call():
        before = cnn.walk_cache_stats()
        y = cnn.apply_cnn(TINY_LAYERS, params, x, stop=5, backend="pallas")
        after = cnn.walk_cache_stats()
        return np.asarray(y), {k: after[k] - before[k] for k in after}

    first, d = call()
    assert d == {"traces": 1, "hits": 0}
    again, d = call()
    assert d == {"traces": 0, "hits": 1}
    np.testing.assert_array_equal(again, first)
    monkeypatch.setenv("REPRO_CONV_TILE_W", "4")
    pinned, d = call()
    assert d == {"traces": 1, "hits": 0}
    np.testing.assert_allclose(pinned, first, rtol=1e-5, atol=1e-5)
    _, d = call()
    assert d == {"traces": 0, "hits": 1}


def _consts(closed):
    """Every constant of a closed jaxpr and of the jaxprs inside it."""
    out = list(closed.consts)
    for eqn in closed.jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _consts(sub)
    return out


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_stage_program_captures_no_weights(backend):
    """The stage's weights are arguments of its program, not constants: a
    walk that closed over them (the control) does show them as consts."""
    layers, shape = cnn.CNN_MODELS["vgg16"], SMALL_SHAPE
    params = cnn.init_cnn(jax.random.PRNGKey(0), layers, shape)
    start, stop = 31, len(layers)           # the classifier: 98% of weights
    x = jax.ShapeDtypeStruct((1, 512, 2, 2), jnp.float32)
    weights = {leaf.shape for leaf in jax.tree.leaves(params[start:stop])
               if leaf.size >= 1024}
    assert weights

    key = cnn._walk_key(layers, start, stop, backend, "fp32")
    traced = cnn._compiled_walk(key).trace(params[start:stop], x)
    assert not [c.shape for c in _consts(traced.jaxpr)
                if np.shape(c) in weights]

    control = jax.jit(lambda y: cnn._walk(
        tuple(layers[start:stop]), backend, "fp32", params[start:stop], y))
    assert [c for c in _consts(control.trace(x).jaxpr)
            if np.shape(c) in weights]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_degenerate_geometry_names_the_layer(backend):
    """Tracing the walk still raises the layer-naming ValueError, and a
    walk that failed to trace counts no hit."""
    params = cnn.init_cnn(jax.random.PRNGKey(0), TINY_LAYERS, TINY_SHAPE)
    x = jnp.zeros((1, 3, 1, 1))             # the 2x2 maxpool sees 1x1
    before = cnn.walk_cache_stats()["hits"]
    with pytest.raises(ValueError, match="'maxpool'.*empty output"):
        cnn.apply_cnn(TINY_LAYERS, params, x, backend=backend)
    assert cnn.walk_cache_stats()["hits"] == before


def test_walk_inside_a_callers_trace():
    """Inside a caller's jit or vmap the walk is traced into the caller's
    program (a nested jit may not take the stage program's compiler
    options) and gives the stage program's answer."""
    params = cnn.init_cnn(jax.random.PRNGKey(5), TINY_LAYERS, TINY_SHAPE)
    x = jax.random.normal(jax.random.PRNGKey(6), (2,) + TINY_SHAPE)
    want = np.asarray(cnn.apply_cnn(TINY_LAYERS, params, x, stop=5))
    hits = cnn.walk_cache_stats()["hits"]
    got = jax.jit(lambda p, y: cnn.apply_cnn(TINY_LAYERS, p, y, stop=5))(
        params, x)
    np.testing.assert_array_equal(np.asarray(got), want)
    got = jax.vmap(lambda y: cnn.apply_cnn(TINY_LAYERS, params, y[None],
                                           stop=5)[0])(x)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert cnn.walk_cache_stats()["hits"] == hits


@pytest.mark.parametrize("pipelined", [True, False])
def test_engine_window_after_warm_up_adds_no_trace(pipelined):
    """``CnnServingEngine.stats()`` reports the walk cache; once every
    batch size 1..4 has been served, serving more traces nothing."""
    params = cnn.init_cnn(jax.random.PRNGKey(4), TINY_LAYERS, TINY_SHAPE)
    rng = np.random.default_rng(4)
    xs = [np.asarray(rng.normal(size=TINY_SHAPE), np.float32)
          for _ in range(12)]
    eng = CnnServingEngine({"tiny": (TINY_LAYERS, params)},
                           hw=paper_chain(3), max_batch=4,
                           pipelined=pipelined)
    for n in range(1, 5):
        reqs = [eng.submit(x, at=0.0) for x in xs[:n]]
        eng.run_until_idle()
        assert all(r.status == "served" for r in reqs)
    warm = eng.stats()
    assert {"walk_traces", "walk_hits"} <= set(warm)
    reqs = [eng.submit(x, at=0.0) for x in xs]
    eng.run_until_idle()
    jax.block_until_ready([r.logits for r in reqs])
    after = eng.stats()
    assert after["served"] - warm["served"] == len(xs)
    assert after["walk_traces"] == warm["walk_traces"]
    # three stages per batch; pipelined: per request (batch-1 microbatches)
    per = len(xs) if pipelined else after["batches"] - warm["batches"]
    assert after["walk_hits"] - warm["walk_hits"] == 3 * per

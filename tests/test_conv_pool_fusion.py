"""Fused conv->relu->maxpool triple: kernel parity, VMEM planning, the
apply_cnn fusion walk (launch counts, split-boundary semantics), and the
pool-geometry corner cases (overlapping AlexNet-style windows, remainder
pooled tiles).

Everything runs in interpret mode on CPU; full-resolution triples whose
conv exceeds ~2e8 MACs are marked ``slow`` (tier-1 runs ``-m "not slow"``)
but still pass under a plain ``pytest`` run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.conv2d import (DEFAULT_VMEM_BUDGET, conv2d, plan_conv)
from repro.models import cnn

KEY = jax.random.PRNGKey(0)

POOL_MODELS = ("alexnet", "vgg11", "vgg13", "vgg16")


def _inputs(n, cin, hw, cout, k, scale=0.3):
    x = jax.random.normal(KEY, (n, cin, hw, hw)) * scale
    w = jax.random.normal(jax.random.fold_in(KEY, 1),
                          (cout, cin, k, k)) * 0.2
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (cout,)) * 0.1
    return x, w, b


def _ref_triple(x, w, b, *, stride, pad, act, pool_k, pool_s):
    y = ref.conv2d_ref(x, w, stride=stride, pad=pad, bias=b, activation=act)
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                 (1, 1, pool_k, pool_k),
                                 (1, 1, pool_s, pool_s), "VALID")


def _model_pool_triples(name):
    """(cin, hw, cout, k, stride, pad, act, pool_k, pool_s) for every
    conv->relu->maxpool triple the model executes, deduplicated.  The
    enumeration itself is cnn.conv_pool_triples -- the same source the
    fusion benchmarks use, mirroring apply_cnn's fusion condition."""
    seen, out = set(), []
    for spec in cnn.conv_pool_triples(cnn.CNN_MODELS[name]):
        spec = spec[1:]                 # drop the layer index
        if spec not in seen:
            seen.add(spec)
            out.append(spec)
    return out


def _triple_params():
    params, seen = [], set()
    for model in POOL_MODELS:
        for spec in _model_pool_triples(model):
            if spec in seen:
                continue            # VGG variants share most triples
            seen.add(spec)
            cin, hw, cout, k, stride, pad, act, pk, ps = spec
            macs = k * k * cin * cout * hw * hw
            marks = [pytest.mark.slow] if macs > 2e8 else []
            params.append(pytest.param(
                spec, marks=marks,
                id=f"{model}-{cin}x{hw}-{cout}c{k}s{stride}p{pk}_{ps}"))
    return params


# ---------------------------------------------------------------------------
# Kernel-level parity: every AlexNet/VGG triple shape + geometry sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("spec", _triple_params())
def test_fused_triple_parity_model_shapes(spec):
    """Acceptance: fused kernel == XLA conv->act->reduce_window to 1e-5 on
    every conv->relu->maxpool triple of the paper's pooling models."""
    cin, hw, cout, k, stride, pad, act, pk, ps = spec
    x, w, b = _inputs(1, cin, hw, cout, k)
    got = conv2d(x, w, stride=stride, pad=pad, bias=b, activation=act,
                 pool_k=pk, pool_s=ps)
    want = _ref_triple(x, w, b, stride=stride, pad=pad, act=act,
                       pool_k=pk, pool_s=ps)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spec", _triple_params())
def test_fused_triple_vmem_within_budget(spec):
    """Acceptance: the fused plan fits the 12 MiB budget for all paper
    triples at full 224 resolution (planning only -- no execution)."""
    cin, hw, cout, k, stride, pad, act, pk, ps = spec
    plan = plan_conv((1, cin, hw, hw), (cout, cin, k, k), stride=stride,
                     pad=pad, pool_k=pk, pool_s=ps)
    assert plan.vmem_bytes <= DEFAULT_VMEM_BUDGET, plan
    assert plan.pool_k == pk and plan.pool_s == ps
    # pooled geometry must match the layer-shape contract
    h_out = (hw + 2 * pad - k) // stride + 1
    assert plan.p_out == (h_out - pk) // ps + 1
    assert plan.pw_out == (plan.w_out - pk) // ps + 1
    assert plan.n_h_blocks * plan.tile_h >= plan.p_out
    # each grid step spans the conv rows its pool windows need
    assert plan.tile_conv_h == (plan.tile_h - 1) * ps + pk


@pytest.mark.parametrize("k,stride,pad,pk,ps", sorted({
    (k, s, p, pk, ps)
    for m in POOL_MODELS
    for (_, _, _, k, s, p, _, pk, ps) in _model_pool_triples(m)}))
def test_fused_triple_geometry_sweep_small(k, stride, pad, pk, ps):
    """Every distinct (K, stride, pad, pool) geometry of the paper models,
    shrunk to small channels/resolution so tier-1 covers the halo/pool
    interaction cheaply."""
    hw = 31 if k > 5 else 23
    x, w, b = _inputs(2, 6, hw, 8, k, scale=0.4)
    got = conv2d(x, w, stride=stride, pad=pad, bias=b, activation="relu",
                 pool_k=pk, pool_s=ps)
    want = _ref_triple(x, w, b, stride=stride, pad=pad, act="relu",
                       pool_k=pk, pool_s=ps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile_h", [1, 2, 3, 5])
@pytest.mark.parametrize("pk,ps", [(2, 2), (3, 2)])
def test_fused_pool_remainder_tiles(tile_h, pk, ps):
    """p_out not a multiple of tile_h: the padded pooled rows (and the
    zero conv rows feeding only them) must not leak into the output --
    including the overlapping-window case pk > ps where neighbouring
    tiles recompute shared conv rows."""
    x, w, b = _inputs(2, 6, 17, 12, 3)
    got = conv2d(x, w, stride=1, pad=1, bias=b, activation="relu",
                 pool_k=pk, pool_s=ps, tile_h=tile_h)
    want = _ref_triple(x, w, b, stride=1, pad=1, act="relu",
                       pool_k=pk, pool_s=ps)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pool_s_defaults_to_pool_k():
    x, w, b = _inputs(1, 4, 12, 8, 3)
    got = conv2d(x, w, stride=1, pad=1, bias=b, pool_k=2)
    want = _ref_triple(x, w, b, stride=1, pad=1, act=None, pool_k=2,
                       pool_s=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_pool_degenerate_geometry_raises():
    """Pool window larger than the conv output must fail in the planner
    with a geometry error, not deep inside the kernel."""
    with pytest.raises(ValueError, match="geometry"):
        plan_conv((1, 4, 6, 6), (8, 4, 3, 3), stride=1, pad=0,
                  pool_k=5, pool_s=2)


# ---------------------------------------------------------------------------
# apply_cnn fusion walk: launch counts + split-boundary semantics
# ---------------------------------------------------------------------------
_TRIPLE = [cnn.conv(8, 3, 1, 1), cnn.relu(), cnn.maxpool(3, 2),
           cnn.conv(16, 3, 1, 1), cnn.relu(), cnn.maxpool(2, 2),
           cnn.conv(16, 1, 1, 0), cnn.relu(),   # pair, no pool follows
           cnn.linear(10)]
_TRIPLE_IN = (3, 17, 17)


def _spy_counts(monkeypatch):
    """Count fused-kernel launches and separate reduce_window launches."""
    counts = {"conv": 0, "pool_k": [], "reduce_window": 0}
    real_conv = ops.conv2d
    real_rw = jax.lax.reduce_window

    def conv_spy(*a, **kw):
        counts["conv"] += 1
        counts["pool_k"].append(kw.get("pool_k", 0))
        return real_conv(*a, **kw)

    def rw_spy(*a, **kw):
        counts["reduce_window"] += 1
        return real_rw(*a, **kw)

    monkeypatch.setattr(ops, "conv2d", conv_spy)
    monkeypatch.setattr(jax.lax, "reduce_window", rw_spy)
    # the spies see the walk only while it is traced: start from no stage
    # program, and keep the spied ones out of the shared cache
    monkeypatch.setattr(cnn, "_WALKS", {})
    return counts


def test_triple_fuses_to_single_launch(monkeypatch):
    """Acceptance: a conv->relu->maxpool triple wholly on one side of the
    split is ONE kernel launch (ops.conv2d with pool_k set) and zero
    separate reduce_window launches."""
    params = cnn.init_cnn(jax.random.PRNGKey(3), _TRIPLE, _TRIPLE_IN)
    x = jax.random.normal(KEY, (1,) + _TRIPLE_IN) * 0.5
    counts = _spy_counts(monkeypatch)
    cnn.apply_cnn(_TRIPLE, params, x, backend="pallas")
    # 3 convs -> 3 launches: two fused triples + one fused pair
    assert counts["conv"] == 3
    assert counts["pool_k"] == [3, 2, 0]
    assert counts["reduce_window"] == 0


def test_split_inside_triple_does_not_fuse_across(monkeypatch):
    """A split landing inside a triple (conv|relu or relu|maxpool) must
    not fuse across the client/server boundary: the maxpool (and/or relu)
    runs unfused on the far side and the boundary payload is unchanged."""
    params = cnn.init_cnn(jax.random.PRNGKey(3), _TRIPLE, _TRIPLE_IN)
    x = jax.random.normal(KEY, (1,) + _TRIPLE_IN) * 0.5
    for split in (1, 2):            # conv|relu..., conv,relu|maxpool...
        lx, bx = cnn.apply_split(_TRIPLE, params, x, split, backend="xla")
        counts = _spy_counts(monkeypatch)
        lp, bp = cnn.apply_split(_TRIPLE, params, x, split,
                                 backend="pallas")
        assert bp.shape == bx.shape          # payload bytes unchanged
        np.testing.assert_allclose(np.asarray(bp), np.asarray(bx),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                                   rtol=1e-5, atol=1e-5)
        # the split triple's maxpool must have launched separately
        assert counts["reduce_window"] == 1
        assert counts["pool_k"][0] == 0      # first conv: no fused pool
        monkeypatch.undo()


@pytest.mark.parametrize("split", range(1, len(_TRIPLE)))
def test_triple_model_split_parity_all_indices(split):
    params = cnn.init_cnn(jax.random.PRNGKey(3), _TRIPLE, _TRIPLE_IN)
    x = jax.random.normal(KEY, (1,) + _TRIPLE_IN) * 0.5
    lx, bx = cnn.apply_split(_TRIPLE, params, x, split, backend="xla")
    lp, bp = cnn.apply_split(_TRIPLE, params, x, split, backend="pallas")
    np.testing.assert_allclose(np.asarray(bp), np.asarray(bx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model", POOL_MODELS)
def test_full_model_walk_fuses_every_triple(model, monkeypatch):
    """Acceptance: walking the whole model at 224 px on the pallas backend,
    every conv->relu->maxpool triple goes through ONE fused launch (pool_k
    set) and no separate reduce_window ever runs.  The conv kernel is
    stubbed with a shape-faithful zeros output so the full-resolution walk
    stays cheap -- this checks the *fusion decisions*, the parity tests
    above check the kernel itself."""
    layers = cnn.CNN_MODELS[model]
    calls = []

    def fake_conv2d(x, w, b, stride, pad, groups=1, activation=None,
                    pool_k=0, pool_s=0, backend=None, dtype=None):
        calls.append((activation, pool_k, pool_s))
        n, _, h, wd = x.shape
        cout, _, k, _ = w.shape
        oh = (h + 2 * pad - k) // stride + 1
        ow = (wd + 2 * pad - k) // stride + 1
        if pool_k:
            oh = (oh - pool_k) // pool_s + 1
            ow = (ow - pool_k) // pool_s + 1
        return jnp.zeros((n, cout, oh, ow), x.dtype)

    rw_calls = []
    real_rw = jax.lax.reduce_window
    # trace anew, and keep the stubbed program out of the shared cache
    monkeypatch.setattr(cnn, "_WALKS", {})
    monkeypatch.setattr(cnn, "_conv2d", fake_conv2d)
    monkeypatch.setattr(jax.lax, "reduce_window",
                        lambda *a, **kw: (rw_calls.append(1),
                                          real_rw(*a, **kw))[1])
    params = cnn.init_cnn(jax.random.PRNGKey(0), layers)
    out = cnn.apply_cnn(layers, params, jnp.zeros((1,) + cnn.INPUT_SHAPE),
                        backend="pallas")
    assert out.shape == (1, 1000)
    n_triples = len(_model_pool_triples(model))
    n_convs = sum(l.kind == "conv" for l in layers)
    assert sum(pk > 0 for _, pk, _ in calls) == n_triples
    assert len(calls) == n_convs
    assert rw_calls == []              # no maxpool launched separately


@pytest.mark.slow
@pytest.mark.parametrize("model", ["alexnet", "vgg11"])
def test_pool_model_end_to_end_backend_parity_224(model):
    """Full 224 forward with triple fusion active, pallas vs xla."""
    layers = cnn.CNN_MODELS[model]
    params = cnn.init_cnn(jax.random.PRNGKey(1), layers)
    x = jax.random.normal(KEY, (1,) + cnn.INPUT_SHAPE) * 0.5
    want = cnn.apply_cnn(layers, params, x, backend="xla")
    got = cnn.apply_cnn(layers, params, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)

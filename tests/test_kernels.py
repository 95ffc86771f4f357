"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
asserting allclose against the pure-jnp oracles in kernels/ref.py, plus the
integration paths in kernels/ops.py (GQA wrapper, padding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.conv2d import conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_ssd import mamba2_ssd
from repro.kernels.rwkv6_wkv import rwkv6_wkv

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,sq,sk,hd,causal,bq,bk", [
    (2, 128, 128, 64, True, 64, 64),
    (1, 128, 128, 128, True, 128, 128),   # full-tile blocks, MXU head dim
    (2, 128, 256, 64, False, 64, 64),     # cross-attention style
    (1, 64, 256, 32, True, 64, 128),      # decode-ish: fewer q than k
    (2, 128, 128, 80, True, 64, 64),      # non-128 head dim (phi3's 96 kin)
    # original oversized variants: multi-q-block at hd=128, deeper decode
    # k-span, and non-power-of-two extents -- slow, not deleted
    pytest.param(1, 256, 256, 128, True, 128, 128,
                 marks=pytest.mark.slow),
    pytest.param(1, 64, 384, 32, True, 64, 128, marks=pytest.mark.slow),
    pytest.param(3, 192, 192, 80, True, 64, 64, marks=pytest.mark.slow),
])
def test_flash_attention_sweep(bh, sq, sk, hd, causal, bq, bk, dtype):
    q = (jax.random.normal(KEY, (bh, sq, hd)) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(KEY, 1), (bh, sk, hd))
         * 0.3).astype(dtype)
    v = (jax.random.normal(jax.random.fold_in(KEY, 2), (bh, sk, hd))
         * 0.3).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_gqa_wrapper_matches_layer_attention():
    """ops.flash_attention_gqa == the model's einsum attention (no cache)."""
    B, S, H, KV, hd = 2, 128, 8, 2, 64
    q = jax.random.normal(KEY, (B, S, H, hd)) * 0.3
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, hd)) * 0.3
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd)) * 0.3
    out = ops.flash_attention_gqa(q, k, v, causal=True, block_q=64,
                                  block_k=64)
    # reference via repeat + dense attention
    g = H // KV
    kb = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vb = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    want = ref.attention_ref(qf, kb, vb, causal=True)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Conv2d
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,cin,cout,hw,k,stride,pad", [
    (1, 3, 16, 32, 3, 1, 1),
    (2, 8, 32, 28, 5, 1, 2),
    (1, 3, 64, 19, 11, 4, 2),     # AlexNet conv1 geometry (shrunk H/W:
                                  # parity is shape-independent, K=11 is
                                  # the expensive unrolled part)
    (2, 16, 16, 16, 1, 1, 0),     # pointwise
    (1, 4, 8, 20, 3, 2, 1),       # strided
    (1, 8, 256, 8, 3, 1, 1),      # two 128-lane output-channel blocks
])
def test_conv2d_sweep(n, cin, cout, hw, k, stride, pad, dtype):
    x = (jax.random.normal(KEY, (n, cin, hw, hw)) * 0.5).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(KEY, 1), (cout, cin, k, k))
         * 0.2).astype(dtype)
    # channel blocks are the whole extent or multiples of 128 lanes
    out = conv2d(x, w, stride=stride, pad=pad, block_co=min(cout, 128))
    want = ref.conv2d_ref(x, w, stride=stride, pad=pad)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_conv2d_matches_cnn_layer():
    """Kernel == the model's lax conv on a real AlexNet layer shape."""
    from repro.models import cnn
    layer = cnn.ALEXNET[3]            # conv(192, 5, 1, 2)
    params = cnn.init_layer(jax.random.PRNGKey(0), layer, (64, 27, 27))
    x = jax.random.normal(KEY, (1, 64, 27, 27)) * 0.3
    want = cnn.apply_layer(layer, params, x)
    got = ops.conv2d(x, params["w"], stride=1, pad=2) \
        + params["b"][None, :, None, None]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas execution mode: follows the platform, resolved at call time
# ---------------------------------------------------------------------------
def test_pallas_compile_env_resolved_at_call_time(monkeypatch):
    """Kernels interpret exactly when the platform is the CPU and compile
    on any other, and the platform is read at *call* time, so the next
    kernel call after a platform change requests the other mode.  The spy
    forces interpret execution so the test runs on CPU while still
    observing what the wrapper asked for."""
    requested = []
    real = ops._conv.conv2d

    def spy(*args, **kw):
        requested.append(kw["interpret"])
        kw["interpret"] = True
        return real(*args, **kw)

    monkeypatch.setattr(ops._conv, "conv2d", spy)
    # distinctive shape so no earlier test's jit cache entry can absorb the
    # first (interpret=True) trace
    x = jax.random.normal(KEY, (1, 5, 9, 9)) * 0.3
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (7, 5, 3, 3)) * 0.2

    assert jax.default_backend() == "cpu"
    assert ops.interpret_mode() is True
    ops.conv2d(x, w, stride=1, pad=1)
    for platform in ("tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert ops.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops.conv2d(x, w, stride=1, pad=1)   # same shapes: must still retrace
    # interpret is a static jit arg, so the compile-mode call cannot have
    # silently reused the interpret-mode executable
    assert requested == [True, False]


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,hd,bt", [
    (2, 128, 2, 32, 32),
    (1, 96, 4, 64, 32),
    (3, 64, 1, 16, 64),
])
def test_rwkv6_wkv_sweep(b, t, h, hd, bt, dtype):
    r = (jax.random.normal(KEY, (b, t, h, hd)) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, h, hd))
         * 0.3).astype(dtype)
    v = (jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, h, hd))
         * 0.3).astype(dtype)
    w = (jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 3),
                                          (b, t, h, hd))) * 0.5
         + 0.45).astype(dtype)
    u = (jax.random.normal(jax.random.fold_in(KEY, 4), (h, hd))
         * 0.1).astype(dtype)
    out = rwkv6_wkv(r, k, v, w, u, block_t=bt)
    want, _ = ref.rwkv6_wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_rwkv6_ops_padding():
    """T not a block multiple: ops pads with identity decay."""
    b, t, h, hd = 1, 50, 2, 16
    mk = lambda i: jax.random.normal(jax.random.fold_in(KEY, i),
                                     (b, t, h, hd)) * 0.3
    w = jax.nn.sigmoid(mk(3)) * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(KEY, 4), (h, hd)) * 0.1
    out = ops.rwkv6_wkv(mk(0), mk(1), mk(2), w, u, block_t=32)
    want, _ = ref.rwkv6_wkv_ref(mk(0), mk(1), mk(2), w, u)
    assert out.shape == (b, t, h, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,hp,ds,chunk", [
    (2, 128, 2, 16, 8, 32),
    (1, 64, 4, 32, 16, 64),
    (2, 96, 1, 64, 64, 32),       # zamba2-like head/state dims
])
def test_mamba2_ssd_sweep(b, t, h, hp, ds, chunk, dtype):
    x = (jax.random.normal(KEY, (b, t, h, hp)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    B = (jax.random.normal(jax.random.fold_in(KEY, 3), (b, t, h, ds))
         * 0.4).astype(dtype)
    C = (jax.random.normal(jax.random.fold_in(KEY, 4), (b, t, h, ds))
         * 0.4).astype(dtype)
    out = mamba2_ssd(x, dt.astype(dtype), A, B, C, chunk=chunk)
    want, _ = ref.mamba2_ssd_ref(x, dt, A, B, C)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_mamba2_layer_matches_kernel_path():
    """The model's chunked-jnp Mamba2 inner scan and the Pallas SSD kernel
    agree on the same (x, dt, A, B, C) inputs."""
    b, t, h, hp, ds = 1, 64, 2, 16, 8
    x = jax.random.normal(KEY, (b, t, h, hp)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (b, t, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (h,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, t, h, ds)) * 0.4
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, t, h, ds)) * 0.4
    got = ops.mamba2_ssd(x, dt, A, B, C, chunk=32)
    want, _ = ref.mamba2_ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

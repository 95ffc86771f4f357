"""Weights and images made by the benchmark from ``--seed``.

Both come from one jitted call each, on the device, in float32 (the
storage dtype of every configuration so far).  The layout is the one the
program's layer walk takes: a list with one entry per layer, a conv as
``{"w": (cout, cin/groups, k, k), "b": (cout,)}``, a linear as
``{"w": (fin, fout), "b": (fout,)}``, an inverted residual block as
``{"expand"?, "dw", "project"}``; each kind's ``init`` in
``bench/kinds/<kind>.py`` makes its own from the key it is given.  The
plain reference reads the same arrays; neither side makes weights of its
own.

Weights are He-normal; biases are small and non-zero (a deployed network
has batch norm folded into them), so the bias path is exercised."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, spec

BIAS_STD = 0.01


def key_of(seed: int, stream: int):
    """A threefry key from any whole seed, 64 bits of it kept."""
    words = np.random.SeedSequence(
        [int(seed) & (2**64 - 1), int(stream)]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def conv_params(key, cin_pg, cout, k):
    """A conv's He-normal weights (cout, cin/groups, k, k) and bias."""
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (cout, cin_pg, k, k), jnp.float32) \
        * math.sqrt(2.0 / (cin_pg * k * k))
    return {"w": w, "b": BIAS_STD * jax.random.normal(kb, (cout,),
                                                      jnp.float32)}


def linear_params(key, fin, fout):
    """A linear layer's He-normal weights (fin, fout) and bias."""
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (fin, fout), jnp.float32) * math.sqrt(2.0 / fin)
    return {"w": w, "b": BIAS_STD * jax.random.normal(kb, (fout,),
                                                      jnp.float32)}


@functools.lru_cache(maxsize=None)
def _params_fn(layers: tuple, in_shape: tuple):
    layer_dicts = [dict(items) for items in layers]
    shapes = [in_shape] + flops.shapes_through(layer_dicts, in_shape)[:-1]

    def build(key):
        keys = jax.random.split(key, len(layer_dicts))
        return [spec.load_kind(layer["kind"]).init(keys[i], layer, shapes[i])
                for i, layer in enumerate(layer_dicts)]
    return jax.jit(build)


@functools.lru_cache(maxsize=None)
def _images_fn(n: int, in_shape: tuple):
    def build(key):
        keys = jax.random.split(key, n)
        return [jax.random.normal(keys[i], in_shape, jnp.float32)
                for i in range(n)]
    return jax.jit(build)


def make_params(seed: int, layers: list[dict], in_shape: tuple):
    """All layer parameters in one jitted call on the default device."""
    key = tuple(tuple(sorted(layer.items())) for layer in layers)
    return _params_fn(key, tuple(in_shape))(key_of(seed, 0))


def make_images(seed: int, n: int, in_shape: tuple) -> list:
    """``n`` distinct standard-normal images, one device array each."""
    return _images_fn(int(n), tuple(in_shape))(key_of(seed, 1))

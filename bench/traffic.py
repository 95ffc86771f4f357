"""The one traffic generator; each mix is a data file under ``traffic/``.

A mix file is JSON.  ``"loop": "open"``: independent phones sending
single images at ``rate_rps``.  ``"loop": "closed"``: ``clients`` phones,
each sending its next image when its reply arrives (``harness.
drive_closed``), so the engine's queue always holds work.  Inter-arrival gaps are exponential
(Poisson arrivals), as in the program's serving benchmark
(``benchmarks/serving_bench.py``), but stratified and shared: the
``rate_rps * seconds`` gaps sit at the exponential's quantiles, in one
fixed shuffled order, and the seed only picks where in that ring of gaps
the window starts.  So every seed offers the same arrivals, rotated.  (A
shuffle per seed changed how the requests bunch: at 0.8x the knee the
p95 of three seeds spread by 14-17% on the chip, as much as a kernel PR
could gain.)

``images`` distinct images are made from the seed; request ``i`` sends
image ``i % images``."""
from __future__ import annotations

import json

import numpy as np

LOOPS = ("open", "closed")


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    loop = mix.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"{path}: loop must be one of {LOOPS}, got {loop!r}")
    if int(mix.get("images", 0)) < 1:
        raise ValueError(f"{path}: images must be >= 1")
    if loop == "open" and not float(mix.get("rate_rps", 0)) > 0:
        raise ValueError(f"{path}: an open loop needs rate_rps > 0")
    if loop == "closed" and int(mix.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    return mix


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1), int(stream)])


ORDER_SEED = 0          # the one shuffle of the gaps that every seed shares


def open_schedule(seed: int, rate_rps: float, seconds: float) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: stratified
    exponential gaps in the shared order, rotated by the seed, scaled to
    span ``seconds``."""
    n = max(1, round(rate_rps * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng(ORDER_SEED, 1).permutation(-np.log1p(-q) / rate_rps)
    gaps = np.roll(gaps, -int(rng(seed, 1).integers(n)))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / float(np.sum(gaps)))


def image_of(request_index: int, images: int) -> int:
    return request_index % images


def knee_rate(rates: list[float], served: list[int], offered: list[int],
              depth_first: list[float],
              depth_second: list[float]) -> float | None:
    """Highest offered rate whose window served at least 99% of what was
    offered with a queue that did not grow: its mean depth over the
    window's second half at most one request above that over the first.
    (A single reading of the depth at the middle and at the end swings by
    a batch or more from step to step, even well below the knee.)"""
    ok = [r for r, s, o, a, b in zip(rates, served, offered, depth_first,
                                     depth_second)
          if o and s / o >= 0.99 and b <= a + 1.0]
    return max(ok) if ok else None


def describe(mix: dict, seconds: float) -> str:
    if mix["loop"] == "closed":
        return (f"closed loop, {mix['clients']} clients, "
                f"{mix['images']} distinct images")
    n = max(1, round(float(mix["rate_rps"]) * seconds))
    return (f"open loop, {n} requests at {mix['rate_rps']} req/s "
            f"(mean gap {1e3 / float(mix['rate_rps']):.2f} ms), "
            f"{mix['images']} distinct images")

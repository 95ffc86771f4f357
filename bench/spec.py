"""``BENCHMARK.json`` and the files it names, found by name.

* a configuration ``<config>`` is ``bench/configs/<config>.json``;
* a layer kind ``<kind>`` is ``bench/kinds/<kind>.py``: its shapes,
  FLOPs, weights, plain reference and conv kernel calls (``load_kind``);
* a configuration's CPU checks are ``bench/tests/data/configs/<config>.json``
  (``load_checks``), read by the tests and never by a run;
* a cell's traffic mix is ``bench/traffic/<cell>.json``;
* a metric ``<name>`` is read by ``bench/metrics/<name>.py``, or, where
  that file does not exist, by ``bench/metrics/<base>.py`` with ``<base>``
  the name up to its first dot (``ops_per_req.lat`` and
  ``ops_per_req.tput`` share ``ops_per_req.py``).  A reader module defines
  ``read(ctx) -> float | None``.

A later configuration, cell, mix, layer kind or metric is new files and a
new entry; no file that is there needs an edit."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import re

from bench import traffic as traffic_lib

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _metric(m: dict) -> Metric:
    return Metric(m["name"], m["unit"])


def config_path(name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "configs", f"{name}.json")


def load_checks(config: str, bench_dir: str = BENCH_DIR) -> dict:
    """What the CPU tests hold configuration ``config`` to:

    * ``tiny``: ``in_shape`` and ``layers`` of a small stand-in with the
      configuration's kinds of layers, chain, wire and checks, which a
      whole run on the CPU serves;
    * ``pins``: ``cuts`` and ``wires`` of the stand-in, its per-layer
      weight sums (``sums``) of seed 0, and the reference logits of two
      images of seed 0 under each of ``reference.MODES``;
    * ``control_cuts``: the cuts at which the control is read, over the
      configuration's own layers at 64 px;
    * ``totals``: ``flops`` per image at ``in_shape`` within ``flops_rel``,
      and ``conv_calls``, the conv kernel calls of one request at ``cuts``;
    * ``program_lacks``: indices of the configuration's layers that the
      program's model (its ``model`` key) does not have."""
    path = os.path.join(bench_dir, "tests", "data", "configs",
                        f"{config}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {config!r} has no CPU "
                                f"checks: no file {path}")
    with open(path) as f:
        return json.load(f)


def traffic_path(cell: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{cell}.json")


def resolve(name: str, bench: dict | None = None,
            bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics loaded;
    an unknown name or a missing file is an error."""
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise KeyError(f"workload {name!r} names unknown config "
                       f"{w['config']!r}")
    with open(config_path(w["config"], bench_dir)) as f:
        config = json.load(f)
    for layer in config["layers"]:
        kind_path(layer["kind"], bench_dir)     # fail early on a missing one
    mix = traffic_lib.load(traffic_path(name, bench_dir))
    e2e = tuple(_metric(m) for m in bench["end_to_end"] if _applies(m, name))
    per_layer = tuple(_metric(m) for m in bench["per_layer"]
                      if _applies(m, name))
    for m in e2e + per_layer:
        reader_path(m.name, bench_dir)          # fail early on a missing one
    return Cell(name, config, mix, int(w["chips"]), e2e, per_layer)


def reader_path(metric: str, bench_dir: str = BENCH_DIR) -> str:
    base = os.path.join(bench_dir, "metrics")
    for stem in (metric, metric.split(".", 1)[0]):
        path = os.path.join(base, f"{stem}.py")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no reader for metric {metric!r} under {base}")


def _exec(module_name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    path = reader_path(metric, bench_dir)
    module_name = "bench_metric_" + os.path.basename(path)[:-3].replace(
        ".", "_")
    return _exec(module_name, path).read


def kind_path(kind: str, bench_dir: str = BENCH_DIR) -> str:
    path = os.path.join(bench_dir, "kinds", f"{kind}.py")
    if not re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_]*", kind) or \
            not os.path.isfile(path):
        raise ValueError(f"unknown layer kind {kind!r}: no file {path}")
    return path


@functools.lru_cache(maxsize=None)
def load_kind(kind: str, bench_dir: str = BENCH_DIR):
    """The module of layer kind ``kind``, loaded once per process.

    A layer is the configuration file's plain dict (``{"kind": "conv",
    "cout": 64, ...}``) and a shape is (C, H, W) or (F,), for one image.
    The module defines:

    * ``out_shape(layer, in_shape)``;
    * ``flops_params(layer, in_shape)``: (FLOPs, parameter count);
    * ``init(key, layer, in_shape)``: the layer's float32 weights from
      ``key``, in the layout the program's layer walk takes (``{}`` for
      none);
    * ``apply(layer, p, x, mode)``: the plain reference on the batch ``x``,
      every contraction through ``reference.contract`` under ``mode``;
    * optionally ``launches(layers, i, in_shape, elem_bytes, fuses)``: the
      conv kernel calls that layer ``i`` starts, as ``flops.launch`` counts
      them, and the number of layers they consume; ``fuses(j)`` says
      whether layer ``j`` runs in the same stage as layer ``j - 1``.  A
      kind without it makes no call and consumes itself;
    * optionally ``FOLDS_INTO_CONV``: ``"epilogue"`` where a conv right
      before folds this layer into its call, ``"pool"`` where a conv and
      its epilogue right before do (``flops.folds_into_conv``);
    * ``EXAMPLE``: one layer of the kind, which the tests hold against the
      program's own layer at the input ``EXAMPLE_IN_SHAPE`` (optional,
      (4, 9, 9) where it is not set).  A run does not read it.

    Kind modules import nothing from the program."""
    return _exec(f"bench_kind_{kind}", kind_path(kind, bench_dir))

"""The ``stage_ahead`` reader on a whole closed-loop run on the CPU at a tiny
size: each batch of n requests is one request a microbatch, and the n - 1
after the first use the stage 0 queued ahead for them."""
import dataclasses

import pytest

from bench import spec
from bench.tests.test_bench_run import (  # noqa: F401 (cpu_run: fixture)
    CONFIGS, cpu_run, tiny_cell)


def test_stage_ahead_reads_the_batches_after_the_first(cpu_run, monkeypatch):
    """Every configuration in one test: a test per configuration would be
    one more than a new configuration is held to bring."""
    seen = {}
    load = spec.load_reader

    def reader(name):
        read = load(name)

        def spy(ctx):
            seen[name] = ctx["engine_window"]
            return read(ctx)
        return spy
    monkeypatch.setattr(spec, "load_reader", reader)
    metric = spec.Metric("stage_ahead.tput", "%")
    for config in CONFIGS:
        cell = dataclasses.replace(tiny_cell(config, "xla", loop="closed"),
                                   end_to_end=(metric,))
        out = cpu_run(cell)
        window = seen.pop(metric.name)
        served, batches = window["served"], window["batches"]
        assert served > batches > 0, config
        got = out["metrics"][metric.name]["value"]
        assert got == pytest.approx(100.0 * (served - batches) / served)
        if served == 4 * batches:
            assert got == 75.0

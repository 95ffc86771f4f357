"""N-tier chain runtime: pipeline scheduling, degradation ladder
(stage-merge -> Pareto re-pick -> unrecoverable), and bit-identity against
the single-device reference.

Deterministic like tests/test_runtime.py: outage windows + the shared
virtual clock force exact failure/recovery sequences per seed."""
import jax
import numpy as np
import pytest

from repro.core import (PAPER_ENV_J6, paper_chain, smartsplit_chain,
                        smartsplit_exhaustive)
from repro.models import cnn as cnn_lib
from repro.models.cnn import avgpool, conv, linear, maxpool, relu
from repro.models.profiles import cnn_profile
from repro.runtime import (ChainRuntime, FaultSpec, FaultyLink, RetryPolicy,
                           SplitRuntime, SplitUnrecoverable, VirtualClock,
                           chain_links_from_env, events, microbatch_slices)

TINY_LAYERS = [conv(8, 3, 1, 1), relu(), maxpool(2, 2),
               conv(16, 3, 1, 1), relu(), avgpool(2), linear(10)]
TINY_SHAPE = (3, 16, 16)


@pytest.fixture(scope="module")
def tiny():
    params = cnn_lib.init_cnn(jax.random.PRNGKey(0), TINY_LAYERS,
                              TINY_SHAPE)
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(4,) + TINY_SHAPE), np.float32)
    return params, x


def _chain_plan(K=3, dtype=None, microbatches=1):
    prof = cnn_profile("tiny", in_shape=TINY_SHAPE, dtype=dtype,
                       layers=TINY_LAYERS)
    hw = paper_chain(K)
    return prof, hw, smartsplit_chain(prof, hw, microbatches=microbatches)


def _links(hw, seed=0, fault_hop=None, spec=None):
    clock = VirtualClock()
    return [FaultyLink(link.bandwidth, clock=clock, seed=seed + k,
                       faults=spec if k == fault_hop else FaultSpec())
            for k, link in enumerate(hw.links)]


def _full_ref(params, x, dtype=None):
    return np.asarray(cnn_lib.apply_cnn(TINY_LAYERS, params, x,
                                        dtype=dtype))


# ---------------------------------------------------------------------------
# Clean path: chain == single device, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [None, "bf16"])
def test_three_tier_clean_bit_identical(tiny, dtype):
    params, x = tiny
    prof, hw, plan = _chain_plan(3, dtype=dtype)
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, dtype=dtype)
    r = rt.infer(x)
    assert not r.degraded and r.merged_hops == ()
    assert r.cuts == plan.cuts and len(r.cuts) == 2
    np.testing.assert_array_equal(np.asarray(r.logits),
                                  _full_ref(params, x, dtype))
    assert r.attempts == len(hw.links)      # one clean send per hop
    assert r.chain_elapsed_s > 0
    assert rt.stats()["recovered"] == 0


@pytest.mark.parametrize("dtype", [None, "bf16"])
def test_one_hop_chain_matches_split_runtime(tiny, dtype):
    """K=2 ChainRuntime == the paper's SplitRuntime on the clean path."""
    params, x = tiny
    prof, hw, plan = _chain_plan(2, dtype=dtype)
    two = smartsplit_exhaustive(prof, PAPER_ENV_J6)
    assert plan.cuts == (two.split_index,)
    crt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, dtype=dtype)
    srt = SplitRuntime(TINY_LAYERS, params, two, prof, PAPER_ENV_J6,
                       dtype=dtype)
    rc = crt.infer(x)
    rs = srt.infer(x)
    np.testing.assert_array_equal(np.asarray(rc.logits),
                                  np.asarray(rs.logits))
    assert rc.goodput_bytes == rs.goodput_bytes


def test_microbatching_bit_identical_and_faster(tiny):
    """M=4 overlaps hop transfers with downstream compute: the virtual
    makespan shrinks while logits stay bit-identical to a single-device
    run sliced at the same microbatch granularity."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    elapsed = {}
    for m in (1, 4):
        rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw,
                          microbatches=m)
        r = rt.infer(x)
        assert r.microbatches == m
        elapsed[m] = r.chain_elapsed_s
        ref = np.concatenate(
            [_full_ref(params, x[a:b]) for a, b in
             microbatch_slices(x.shape[0], m)], axis=0)
        np.testing.assert_array_equal(np.asarray(r.logits), ref)
    assert elapsed[4] < elapsed[1]
    # M=1 batched execution equals the plain batched reference
    # (microbatch_slices(batch, 1) is the whole batch)
    assert microbatch_slices(4, 1) == [(0, 4)]
    assert microbatch_slices(4, 4) == [(0, 1), (1, 2), (2, 3), (3, 4)]
    assert microbatch_slices(5, 2) == [(0, 3), (3, 5)]
    with pytest.raises(ValueError):
        microbatch_slices(0, 1)


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------
def test_mid_chain_outage_merges_stage(tiny):
    """A permanently dead hop 1 folds the downstream stage onto the
    upstream tier (the cut collapses) and the answer stays bit-exact."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    links = _links(hw, fault_hop=1,
                   spec=FaultSpec(outages=((0.0, 1e9),)))
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, links=links,
                      policy=RetryPolicy(max_attempts=2, timeout_s=0.01,
                                         backoff_base_s=0.005))
    r = rt.infer(x)
    assert r.degraded
    assert r.merged_hops == (1,)
    assert len(r.cuts) == 1                 # one cut collapsed
    np.testing.assert_array_equal(np.asarray(r.logits),
                                  _full_ref(params, x))
    s = rt.stats()
    assert s["merges"] == 1 and s["recovered"] == 1
    assert any(e.kind == events.STAGE_MERGE for e in r.events)
    assert s["hops"][1]["merges"] == 1
    assert s["hops"][1]["link"]["outage_hits"] >= 1


def test_transient_outage_recovers_via_repick(tiny):
    """With merges disabled and hop 1 down only for a window, the runtime
    re-picks a different cut vector from the cached front and finishes."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    assert len(plan.pareto_cuts) >= 2       # front has an alternative
    links = _links(hw, fault_hop=1,
                   spec=FaultSpec(outages=((0.0, 0.012),)))
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, links=links,
                      merge_fallback=False,
                      policy=RetryPolicy(max_attempts=1, timeout_s=0.01,
                                         backoff_base_s=0.005))
    r = rt.infer(x[:1])
    assert r.degraded and r.merged_hops == ()
    assert r.cuts != r.planned_cuts
    np.testing.assert_array_equal(np.asarray(r.logits),
                                  _full_ref(params, x[:1]))
    s = rt.stats()
    assert s["repicks"] == 1 and s["merges"] == 0
    assert any(e.kind == events.REPICK for e in r.events)


def test_permanent_outage_without_merge_is_unrecoverable(tiny):
    """Every cut vector of a K=3 chain crosses hop 1, so a dead hop with
    merges disabled exhausts the front and surfaces the outage."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    links = _links(hw, fault_hop=1,
                   spec=FaultSpec(outages=((0.0, 1e9),)))
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, links=links,
                      merge_fallback=False,
                      policy=RetryPolicy(max_attempts=1, timeout_s=0.01,
                                         backoff_base_s=0.005))
    with pytest.raises(SplitUnrecoverable):
        rt.infer(x[:1])
    assert rt.log.count(events.UNRECOVERABLE) == 1


# ---------------------------------------------------------------------------
# Stage 0 of the next microbatch, queued ahead of this one's last hop
# ---------------------------------------------------------------------------
def test_stage_ahead_clean_bit_identical(tiny):
    """Each microbatch's logits equal ``apply_split`` of its slice alone;
    microbatches 1-3 use the stage 0 queued ahead for them, and M=1
    queues none."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    assert plan.cuts[0] > 0
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, microbatches=4)
    for n in (1, 2):
        r = rt.infer(x)
        assert r.cuts == plan.cuts and not r.degraded
        for i, (a, b) in enumerate(microbatch_slices(x.shape[0], 4)):
            want, _ = cnn_lib.apply_split(TINY_LAYERS, params, x[a:b],
                                          plan.cuts[0])
            np.testing.assert_array_equal(np.asarray(r.logits[a:b]),
                                          np.asarray(want), err_msg=str(i))
        s = rt.stats()
        assert s["stage_ahead"] == s["stage_ahead_used"] == 3 * n
    one = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, microbatches=1)
    one.infer(x)
    assert one.stats()["stage_ahead"] == one.stats()["stage_ahead_used"] \
        == 0


def _lossy_run(tiny, outage, merge_fallback):
    """Two M=4 requests over a 3-tier chain whose hops drop and corrupt
    (seeded), hop 1 also down for ``outage``."""
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    clock = VirtualClock()
    specs = [FaultSpec(drop_rate=0.2, corrupt_rate=0.2),
             FaultSpec(drop_rate=0.2, corrupt_rate=0.2, outages=(outage,))]
    links = [FaultyLink(link.bandwidth, clock=clock, seed=7 + k, faults=sp)
             for k, (link, sp) in enumerate(zip(hw.links, specs))]
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw, links=links,
                      microbatches=4, merge_fallback=merge_fallback,
                      policy=RetryPolicy(max_attempts=3, timeout_s=0.01,
                                         backoff_base_s=0.005))
    return rt, [rt.infer(x) for _ in range(2)]


@pytest.mark.parametrize("case", ["merge", "repick"])
def test_stage_ahead_changes_nothing_under_faults(tiny, monkeypatch, case):
    """Lossy hops plus an outage on hop 1 from the third microbatch on:
    permanent, so hop 1 merges, or a window with merges off, so the chain
    re-picks onto another first cut.  Logits, cuts, events, virtual times
    and every counter equal those of the same run with nothing queued
    ahead; a stage 0 queued before the first cut moved is discarded."""
    outage, merge = {"merge": ((0.004, 1e9), None),
                     "repick": ((0.004, 0.045), False)}[case]
    rt, got = _lossy_run(tiny, outage, merge)
    with monkeypatch.context() as mp:
        mp.setattr(ChainRuntime, "_run_ahead", lambda self, x, stop: None)
        base_rt, want = _lossy_run(tiny, outage, merge)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.logits),
                                      np.asarray(w.logits))
        assert (g.cuts, g.events, g.chain_elapsed_s,
                g.microbatch_finish_s, g.merged_hops) == \
            (w.cuts, w.events, w.chain_elapsed_s, w.microbatch_finish_s,
             w.merged_hops)
    assert [link.counters() for link in rt.links] == \
        [link.counters() for link in base_rt.links]
    s, base = rt.stats(), base_rt.stats()
    ahead, used = s.pop("stage_ahead"), s.pop("stage_ahead_used")
    assert (base.pop("stage_ahead"), base.pop("stage_ahead_used")) == (0, 0)
    assert s == base
    assert ahead == 6                       # 3 a request
    if case == "merge":
        assert s["merges"] == 2 and s["repicks"] == 0
        assert got[0].cuts == got[1].cuts == (3,)
        assert used == ahead                # the first cut never moved
    else:
        assert s["merges"] == 0 and s["repicks"] == 1
        assert got[0].planned_cuts == (3, 6) and got[0].cuts == (1, 6)
        assert used == ahead - 1            # queued for cut 3, ran cut 1


# ---------------------------------------------------------------------------
# Observability: per-hop counters in both runtimes
# ---------------------------------------------------------------------------
def test_chain_stats_per_hop(tiny):
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw)
    rt.infer(x)
    s = rt.stats()
    assert len(s["hops"]) == 2
    for k, h in enumerate(s["hops"]):
        assert h["hop"] == k
        assert h["attempts"] == 1
        assert h["goodput_bytes"] > 0
        assert h["retransmitted_bytes"] == 0
        assert h["degradation"] > 0
    assert s["active_cuts"] == list(plan.cuts)


def test_split_runtime_stats_expose_hops(tiny):
    from repro.core import PAPER_ENV_J6
    params, x = tiny
    prof = cnn_profile("tiny", in_shape=TINY_SHAPE, layers=TINY_LAYERS)
    plan = smartsplit_exhaustive(prof, PAPER_ENV_J6)
    rt = SplitRuntime(TINY_LAYERS, params, plan, prof, PAPER_ENV_J6)
    rt.infer(x)
    s = rt.stats()
    assert len(s["hops"]) == 1
    h = s["hops"][0]
    assert h["hop"] == 0
    assert h["attempts"] == 1
    assert h["wire_bytes"] == h["goodput_bytes"] > 0
    assert "est_bandwidth" in h and "degradation" in h


# ---------------------------------------------------------------------------
# Shared virtual clock + per-hop env knobs
# ---------------------------------------------------------------------------
def test_virtual_clock_shared_across_hops():
    clock = VirtualClock()
    a = FaultyLink(100.0, clock=clock)
    b = FaultyLink(100.0, clock=clock)
    a.send(b"x" * 100, timeout_s=10.0)      # 1s of wire time
    assert b.clock == pytest.approx(1.0)    # b sees a's progress
    out, elapsed = b.send_at(5.0, b"y" * 50, timeout_s=10.0)
    assert out == b"y" * 50
    assert clock.now == pytest.approx(5.5)  # explicit start, not now
    clock.advance_to(2.0)                   # monotone: never rewinds
    assert clock.now == pytest.approx(5.5)


def test_chain_links_from_env_per_hop_override(monkeypatch):
    monkeypatch.setenv("REPRO_LINK_DROP", "0.1")
    monkeypatch.setenv("REPRO_LINK1_DROP", "0.5")
    monkeypatch.setenv("REPRO_LINK_SEED", "7")
    links = chain_links_from_env([1e6, 2e6, 3e6])
    assert [link.faults.drop_rate for link in links] == [0.1, 0.5, 0.1]
    assert [link.seed for link in links] == [7, 8, 9]   # base + hop
    assert links[0]._clock is links[1]._clock is links[2]._clock
    monkeypatch.setenv("REPRO_LINK2_SEED", "99")
    assert chain_links_from_env([1e6, 2e6, 3e6])[2].seed == 99


def test_chain_runtime_microbatch_env_default(tiny, monkeypatch):
    params, x = tiny
    prof, hw, plan = _chain_plan(3)
    monkeypatch.setenv("REPRO_CHAIN_MICROBATCH", "4")
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw)
    assert rt.infer(x).microbatches == 4


# ---------------------------------------------------------------------------
# Acceptance: 4-tier VGG16 at the paper's native input
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_four_tier_vgg16_end_to_end_bit_identical():
    in_shape = cnn_lib.INPUT_SHAPE
    layers = cnn_lib.CNN_MODELS["vgg16"]
    prof = cnn_profile("vgg16", batch=2, in_shape=in_shape)
    hw = paper_chain(4)
    plan = smartsplit_chain(prof, hw)
    assert len(plan.cuts) == 3
    params = cnn_lib.init_cnn(jax.random.PRNGKey(0), layers, in_shape)
    x = np.asarray(np.random.default_rng(0).normal(
        size=(2,) + in_shape), np.float32)
    rt = ChainRuntime("vgg16", params, plan, prof, hw, microbatches=1)
    r = rt.infer(x)
    assert not r.degraded
    ref = np.asarray(cnn_lib.apply_cnn(layers, params, x))
    np.testing.assert_array_equal(np.asarray(r.logits), ref)

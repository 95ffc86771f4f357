"""Hypothesis property tests for the conv tiling planner.

Kept separate from tests/test_conv2d_tiled.py so environments without
``hypothesis`` (dev-only dependency) still run the unit and parametrized
tests there -- same convention as the other ``*_properties.py`` modules.

Invariants (planning only -- no kernel execution, so hundreds of random
geometries stay cheap):

* where the planner finds no tiling, even a single-element tile at the
  smallest channel block overflows the VMEM budget; elsewhere
* the grid tiles exactly cover ``p_out x pw_out``: every output element
  falls in some tile, and no tile (in particular the remainder tile) is
  entirely padding;
* remainder tiles stay in-bounds: the last tile's haloed input read ends
  within the rows/cols the ``conv2d`` wrapper is committed to pad;
* the VMEM estimate is monotone in ``tile_h`` and ``tile_w`` and never
  falls below the bias + fp32-accumulator floor;
* searched plans respect the budget whenever any tiling does, and never
  need more grid launches than the legacy greedy planner."""
import pytest

pytest.importorskip("hypothesis")
from hypothesis import (example, given, settings,  # noqa: E402
                        strategies as st)

from repro.kernels.conv2d import (DEFAULT_VMEM_BUDGET,  # noqa: E402
                                  channel_blocks, conv_vmem_bytes,
                                  plan_conv)


@st.composite
def conv_geometries(draw):
    """Random but valid (x_shape, w_shape, stride, pad, pool) tuples."""
    cin = draw(st.sampled_from([1, 3, 8, 24, 64]))
    cout = draw(st.sampled_from([4, 16, 48, 64, 192]))
    K = draw(st.sampled_from([1, 3, 5, 11]))
    stride = draw(st.integers(1, 4))
    pad = draw(st.integers(0, 3))
    H = draw(st.integers(max(1, K - 2 * pad), 64))
    W = draw(st.integers(max(1, K - 2 * pad), 640))
    pool = draw(st.sampled_from([(0, 0), (2, 2), (3, 2)]))
    h_out = (H + 2 * pad - K) // stride + 1
    w_out = (W + 2 * pad - K) // stride + 1
    if h_out < 1 or w_out < 1 or (pool[0] and (
            h_out < pool[0] or w_out < pool[0])):
        pool = (0, 0)
    return ((1, cin, H, W), (cout, cin, K, K), stride, pad) + pool


def plan_or_none(geom):
    """``plan_conv``'s plan for ``geom``, or None where the planner finds
    no tiling within its VMEM budget -- a raise that must be right: even
    a single-element output tile at the smallest accepted channel block
    overflows the budget."""
    x_shape, w_shape, stride, pad, pk, ps = geom
    try:
        return plan_conv(x_shape, w_shape, stride=stride, pad=pad,
                         pool_k=pk, pool_s=ps)
    except ValueError as e:
        if "no feasible conv tiling" not in str(e):
            raise
    _, cin, _, W = x_shape
    cout, _, K, _ = w_shape
    floor = conv_vmem_bytes(
        tile_h=1, tile_w=1, cin_block=cin, block_co=channel_blocks(cout)[0],
        w_out=(W + 2 * pad - K) // stride + 1, K=K, stride=stride,
        cin_per_group=cin, pool_k=pk, pool_s=ps or 1)
    assert floor > DEFAULT_VMEM_BUDGET, (geom, floor)
    return None


# a geometry hypothesis has drawn, with no tiling in the budget: the
# (K*K, Cin, 192-channel) weight block alone overflows it
NO_TILING = ((1, 64, 11, 11), (192, 64, 11, 11), 1, 0, 0, 0)


@given(conv_geometries())
@example(NO_TILING)
@settings(max_examples=120, deadline=None)
def test_grid_tiles_exactly_cover_output(geom):
    x_shape, w_shape, stride, pad, pk, ps = geom
    plan = plan_or_none(geom)
    if plan is None:
        return
    # full cover: the padded grid reaches past the real output ...
    assert plan.n_h_blocks * plan.tile_h >= plan.p_out
    assert plan.n_w_blocks * plan.tile_w >= plan.pw_out
    # ... but the last tile still contains at least one real element
    assert (plan.n_h_blocks - 1) * plan.tile_h < plan.p_out
    assert (plan.n_w_blocks - 1) * plan.tile_w < plan.pw_out
    assert plan.launches == plan.n_h_blocks * plan.n_w_blocks * \
        (w_shape[0] // plan.block_co) * x_shape[0]
    # the plan's per-step tile never exceeds what it believes fits
    assert plan.vmem_bytes <= DEFAULT_VMEM_BUDGET


@given(conv_geometries())
@example(NO_TILING)
@settings(max_examples=120, deadline=None)
def test_remainder_tiles_read_in_bounds(geom):
    """The last tile's haloed read must end within the padded extents the
    conv2d wrapper allocates (rows_needed / cols_needed)."""
    x_shape, w_shape, stride, pad, pk, ps = geom
    plan = plan_or_none(geom)
    if plan is None:
        return
    K = w_shape[2]
    for n_blocks, tile, tile_in, full in (
            (plan.n_h_blocks, plan.tile_h, plan.tile_in_h,
             plan.n_h_blocks * plan.tile_h),
            (plan.n_w_blocks, plan.tile_w, plan.tile_in_w,
             plan.n_w_blocks * plan.tile_w)):
        step = tile * plan.pool_s * stride
        conv_ext = (full - 1) * plan.pool_s + plan.pool_k if plan.pool_k \
            else full
        needed = (conv_ext - 1) * stride + K
        assert (n_blocks - 1) * step + tile_in <= max(
            needed, tile_in)  # single full-width tile stages w_in as-is


@given(conv_geometries(), st.integers(1, 64), st.integers(1, 64))
@settings(max_examples=120, deadline=None)
def test_vmem_estimate_monotone_and_floored(geom, th, tw):
    x_shape, w_shape, stride, pad, pk, ps = geom
    _, cin, _, W = x_shape
    cout, _, K, _ = w_shape
    w_in = W + 2 * pad
    w_out = (w_in - K) // stride + 1
    kw = dict(cin_block=cin, block_co=cout, w_out=w_out, K=K,
              stride=stride, cin_per_group=cin, pool_k=pk,
              pool_s=ps or 1)
    est = conv_vmem_bytes(tile_h=th, tile_w=tw, **kw)
    # monotone in both tile axes
    assert conv_vmem_bytes(tile_h=th + 1, tile_w=tw, **kw) > est
    assert conv_vmem_bytes(tile_h=th, tile_w=tw + 1, **kw) >= est
    # never below the double-buffered bias column + fp32 accumulator floor
    tile_conv_h = (th - 1) * (ps or 1) + pk if pk else th
    tile_conv_w = min((tw - 1) * (ps or 1) + pk if pk else tw, w_out)
    assert est >= 2 * cout * 4 + cout * tile_conv_h * tile_conv_w * 4


@given(conv_geometries())
@settings(max_examples=60, deadline=None)
def test_search_never_beaten_by_greedy(geom):
    """The joint search subsumes the greedy point (same block_co ladder
    entry, full-width column tile, max-fit row tile), so whenever greedy
    finds a feasible tiling the search's cost-model bytes are <= greedy's.
    (On arbitrary geometry the cost optimum may trade a launch or two for
    less halo/lane-padded traffic; the launch-count <= guarantee asserted
    per paper shape lives in test_conv2d_tiled.py.)"""
    x_shape, w_shape, stride, pad, pk, ps = geom
    try:
        greedy = plan_conv(x_shape, w_shape, stride=stride, pad=pad,
                           pool_k=pk, pool_s=ps, search=False)
    except ValueError:
        return  # row-only planner infeasible; search-only territory
    searched = plan_conv(x_shape, w_shape, stride=stride, pad=pad,
                         pool_k=pk, pool_s=ps, search=True)
    assert searched.searched and not greedy.searched
    assert searched.cost_bytes <= greedy.cost_bytes
    assert searched.vmem_bytes <= DEFAULT_VMEM_BUDGET

"""Spatially-tiled direct convolution as a Pallas TPU kernel.

The paper's compute hot-spot is CNN convolution on the client device.  The
TPU-native formulation: a KxK conv is K^2 shifted ``(cols x Cin) @ (Cin x
Cout)`` matmuls -- pure MXU work with the image tile resident in VMEM,
instead of a GPU-style im2col gather.

Layout
------
Inside the kernel channels sit on the 128-wide lane axis and image columns
on the sublane axis; rows are a leading (untiled) axis.  The wrapper turns
the NCHW activation into *haloed tile windows* before the launch::

    (N, C, H, W) -> (B, n_h, n_w, tile_in_h, S, tile_in_wq, Cb)

* ``B = groups x N`` for grouped convs (each group is its own image with
  ``cin_per_group`` lanes); depthwise convs keep ``B = N`` and block the
  channel axis instead.
* Rows and columns are cut into overlapping windows -- the conv rows/cols a
  tile produces plus the K-1 halo shared with its neighbours -- so every
  block the pipeline streams is a whole trailing array extent and Mosaic's
  (8, 128) block rule never binds, whatever the tile sizes.
* Columns are split into ``S = stride * pool_s`` phases (column ``u*S + t``
  lands in phase ``t``, slot ``u``).  A tap of a stride-``s`` conv, and a
  window of a stride-``pool_s`` maxpool, then reads a *unit-stride*
  sublane slice: Mosaic has no strided sublane load for 16-bit data and
  none for lane extents other than 128.  Row strides need no such trick:
  rows are a leading axis, indexed by scalar.

Each grid step ``(b, c, h, w)`` loops over its conv rows (``fori_loop``)
and, per row and conv-column phase, accumulates the K^2 taps as 2-D
``(U x Cb) @ (Cb x block_co)`` dots on an fp32 accumulator -- no reshape
ever crosses the lane axis.  Depthwise convs (``cin_per_group == 1``)
multiply-add on the VPU instead of issuing 1-deep matmuls.

The epilogue (bias add + relu/relu6 + optional maxpool) runs on the fp32
accumulator before writeback, so a paper-layer conv+relu+maxpool *triple*
is one kernel launch with no intermediate activation round-tripping HBM:
with a pool fused, conv rows land in a VMEM scratch (one plane per column
phase) and each pooled row is the max of ``pool_k^2`` unit-stride reads.

VMEM budget model
-----------------
``conv_vmem_bytes`` counts every buffer as Mosaic lays it out: the last two
dims of each VMEM buffer occupy whole ``(sublanes, 128)`` tiles -- 8
sublanes for 4-byte, 16 for 2-byte elements -- so a 3-channel input row
costs 128 lanes and a ``(K*K, Cin, block_co)`` weight slice costs
``K*K * ceil(Cin/sublanes) * ceil(block_co/128)`` tiles.  Streamed blocks
(input window, weights, bias, output tile) are double-buffered by the
pipeline; the pooled-epilogue scratch and the per-row accumulator are not.
With a fused pool the streamed output shrinks to the pooled tile, which is
why fusion *shrinks* the client-side footprint the paper optimises.
``vmem_limit_bytes`` is set to the planner's budget plus
``MOSAIC_SCRATCH_BYTES`` for the compiler's own scratch.

Tiling search
-------------
``plan_conv`` picks ``(block_co, tile_h, tile_w)`` *jointly* by minimising
an explicit per-shape cost model over every channel block the compiler
accepts (the whole extent, or a divisor that is a multiple of 128 lanes)
and a dedup'd ladder of column splits (``plan_cost``: total HBM traffic
the grid streams -- input windows including halo re-reads, the weight
slice re-staged every grid step, padded output tiles -- plus a fixed
per-grid-step overhead of ``LAUNCH_COST_BYTES`` bytes-equivalent).  For
each candidate the largest ``tile_h`` whose VMEM estimate fits the budget
(default 12 MiB) is found by bisection -- the estimate is monotone in
``tile_h`` -- then shrunk to ``ceil(p_out / n_blocks)`` so the final grid
wastes as few padded rows as possible (columns get the same shrink).  The
search subsumes the legacy greedy choice (largest accepted ``block_co <=
128``, then largest ``tile_h``) as a candidate, so it never costs more than
greedy; ``REPRO_CONV_SEARCH=0`` falls back to greedy exactly, and
``REPRO_CONV_TILE_W`` pins the column tile (0 = automatic).

Column tiles open the wide-input workloads (1080p camera frames,
panoramic strips) where a *single output row* overflows VMEM; with a fused
pool the column tiles land on pool-window starts exactly as pooled rows
do.  ``h_out`` / ``pw_out`` need not be multiples of the tile: the wrapper
zero-pads so remainder tiles read in-bounds and slices the padded outputs
away.

Storage dtype: input windows, weights and the output tile move in
``x.dtype`` (fp32 or bf16 under the ``REPRO_CONV_DTYPE`` policy -- see
``kernels.ops.conv2d``).  bf16 operands feed the MXU directly with fp32
accumulation (exact products, fp32 sums); fp32 operands use the full-
precision matmul.  The accumulator, bias and every epilogue op are fp32,
and the result is cast back to ``x.dtype`` only at writeback.
Grouped convolution (``feature_group_count``) is supported: pointwise
(groups=1), group-aligned channel blocks (1 < groups < Cin), and the
depthwise case (cin_per_group == 1).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

VMEM_LIMIT_BYTES = 16 * 1024 * 1024     # one v5e core's default scoped VMEM
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024  # what the planner may fill
# Headroom the compiler gets on top of the planner's budget for its own
# scratch (spills, relayouts); budget + headroom is the kernel's VMEM limit.
MOSAIC_SCRATCH_BYTES = VMEM_LIMIT_BYTES - DEFAULT_VMEM_BUDGET

# Fixed bytes-equivalent charged per grid step by the tiling-search cost
# model (DMA descriptor setup + pipeline bubble; ~an HBM microsecond).
LAUNCH_COST_BYTES = 128 * 1024
# Fixed bytes-equivalent per tap op issued inside the row loop (a small
# dot pays its weight load and pipeline fill whatever its row count).
# Uncalibrated: a modelled ~0.1 us, not a chip measurement.
TAP_COST_BYTES = 64 * 1024
# VMEM lane width: channel extents occupy whole lanes.
LANE = 128
# Largest channel block the search considers when a smaller accepted one
# exists (wider trades a bigger weight slice for fewer grid steps).
MAX_BLOCK_CO = 512
# Column-split ladder: candidate n_w_blocks in 1..MAX_W_SPLITS (dedup'd by
# the tile width they imply), enough to shatter an 8K-wide panorama row.
MAX_W_SPLITS = 128

SEARCH_ENV = "REPRO_CONV_SEARCH"
TILE_W_ENV = "REPRO_CONV_TILE_W"


def search_enabled(search: bool | None = None) -> bool:
    """Resolve the tiling-search switch *now* (mirrors ``conv_backend``).

    Explicit argument wins, else ``REPRO_CONV_SEARCH`` (default on)."""
    if search is not None:
        return search
    v = os.environ.get(SEARCH_ENV, "1")
    if v not in ("0", "1"):
        raise ValueError(f"{SEARCH_ENV} must be '0' or '1', got {v!r}")
    return v == "1"


def tile_w_override(tile_w: int = 0) -> int:
    """Resolve the column-tile override: explicit argument wins, else
    ``REPRO_CONV_TILE_W`` (0 = let the planner decide)."""
    if tile_w:
        return tile_w
    v = os.environ.get(TILE_W_ENV, "0")
    try:
        n = int(v)
    except ValueError:
        raise ValueError(f"{TILE_W_ENV} must be an integer, got {v!r}") \
            from None
    if n < 0:
        raise ValueError(f"{TILE_W_ENV} must be >= 0, got {n}")
    return n


def _pool_out(n: int, pool_k: int, pool_s: int) -> int:
    """VALID-window pooled extent (matches lax.reduce_window)."""
    return (n - pool_k) // pool_s + 1


def _rup(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype_bytes: int) -> int:
    """Rows of one VMEM tile: (8, 128) for 4-byte, (16, 128) for 2-byte."""
    return 8 * 4 // dtype_bytes


def col_phases(tile_w: int, *, K: int, stride: int, pool_k: int = 0,
               pool_s: int = 1) -> tuple[int, int, int]:
    """Column geometry of one tile in the phase layout.

    Returns ``(S, U, wq)``: the phase count ``S = stride * pool_s``, the
    conv columns ``U`` computed per conv-column phase (``pool_s`` phases
    when a pool is fused, else one), and the staged window width ``wq`` in
    phase slots (``wq * S`` input columns)."""
    if not pool_k:
        pool_s = 1
    S = stride * pool_s
    U = tile_w + (pool_k - 1) // pool_s if pool_k else tile_w
    return S, U, U + ((pool_s - 1) * stride + K - 1) // S


def channel_blocks(limit: int) -> list[int]:
    """Channel blocks the compiler accepts for an extent of ``limit``
    lanes: the whole extent, or a divisor that is a multiple of 128."""
    return [d for d in range(LANE, limit, LANE) if limit % d == 0] + [limit]


def conv_vmem_bytes(*, cin_block: int, block_co: int, tile_h: int,
                    w_out: int, K: int, stride: int, cin_per_group: int,
                    dtype_bytes: int = 4, pool_k: int = 0, pool_s: int = 1,
                    tile_w: int = 0) -> int:
    """VMEM bytes one grid step of the tiled kernel occupies, counted in
    whole (sublanes, 128) tiles as Mosaic lays the buffers out.

    With ``pool_k > 0`` (fused maxpool epilogue) ``tile_h`` / ``tile_w``
    count pooled output rows/cols; the fp32 scratch still spans the conv
    rows/cols feeding those pool windows.  ``tile_w = 0`` means the tile
    spans the full output width."""
    if pool_k:
        tile_conv_h = (tile_h - 1) * pool_s + pool_k
        full_out_w = _pool_out(w_out, pool_k, pool_s)
    else:
        tile_conv_h, full_out_w, pool_s = tile_h, w_out, 1
    out_w = tile_w if tile_w and tile_w < full_out_w else full_out_w
    S, U, wq = col_phases(out_w, K=K, stride=stride, pool_k=pool_k,
                          pool_s=pool_s)
    tile_in_h = (tile_conv_h - 1) * stride + K
    sub = _sublanes(dtype_bytes)
    cin_l, co_l = _rup(cin_block, LANE), _rup(block_co, LANE)
    x_b = tile_in_h * S * _rup(wq, sub) * cin_l * dtype_bytes
    w_b = K * K * _rup(cin_per_group, sub) * co_l * dtype_bytes
    b_b = 8 * co_l * 4
    o_b = tile_h * _rup(out_w, sub) * co_l * dtype_bytes
    scratch = pool_s * tile_conv_h * _rup(U, 8) * co_l * 4 if pool_k else 0
    # one row's live values: fp32 accumulator + tap product, staged tap
    row = 2 * _rup(U, 8) * co_l * 4 + _rup(U, sub) * cin_l * dtype_bytes
    return 2 * (x_b + w_b + b_b + o_b) + scratch + row


def _max_fit_tile_h(est, h_cap: int, budget: int) -> int:
    """Largest ``tile_h in [1, h_cap]`` with ``est(tile_h) <= budget``
    (0 if even one row overflows).  Bisection is valid because the VMEM
    estimate is strictly monotone in ``tile_h``."""
    if est(tile_h=1) > budget:
        return 0
    lo, hi = 1, h_cap
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if est(tile_h=mid) <= budget:
            lo = mid
        else:
            hi = mid - 1
    return lo


def choose_tile_h(h_out: int, *, cin_block: int, block_co: int, w_out: int,
                  K: int, stride: int, cin_per_group: int,
                  dtype_bytes: int = 4, pool_k: int = 0, pool_s: int = 1,
                  tile_w: int = 0,
                  budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Largest output-row tile whose VMEM estimate fits ``budget`` (found
    by bisection -- the estimate is monotone in ``tile_h``), shrunk to the
    smallest tile with the same block count (minimal padded waste).

    ``h_out`` and the returned tile are in *kernel output rows*: conv rows
    normally, pooled rows when a maxpool epilogue is fused (``pool_k > 0``)
    -- tile boundaries then land on pool-window starts, i.e. ``tile_h`` is
    aligned to the pool stride by construction.  ``tile_w`` narrows the
    estimate to a column tile (0 = full width)."""
    if h_out < 1:
        raise ValueError(f"invalid conv geometry: h_out={h_out} "
                         f"(kernel/stride larger than padded input)")
    est = functools.partial(
        conv_vmem_bytes, cin_block=cin_block, block_co=block_co,
        w_out=w_out, K=K, stride=stride, cin_per_group=cin_per_group,
        dtype_bytes=dtype_bytes, pool_k=pool_k, pool_s=pool_s,
        tile_w=tile_w)
    tile_h = _max_fit_tile_h(est, min(h_out, 512), budget)
    if tile_h == 0:
        raise ValueError(
            f"conv tile of a single output row exceeds VMEM budget "
            f"({est(tile_h=1)} > {budget}); split columns with tile_w "
            f"(the tiling search, on by default in plan_conv, does this "
            f"automatically)")
    n_blocks = -(-h_out // tile_h)
    return -(-h_out // n_blocks)


def plan_cost(*, n_batch: int, n_c_blocks: int, n_h_blocks: int,
              n_w_blocks: int, cin_block: int, block_co: int, tile_h: int,
              tile_w: int, tile_in_h: int, tile_in_w: int, K: int,
              cin_per_group: int, dtype_bytes: int, p_out: int,
              pw_out: int, tile_conv_h: int, conv_phases: int) -> dict:
    """The tiling-search cost model for one candidate grid.

    ``hbm_bytes`` is everything the grid streams between HBM and VMEM:
    the input window (halo re-reads appear as overlapping ``tile_in_*``
    extents, and for groups == 1 every channel block re-reads the same
    window), the weight slice re-staged by every grid step, the fp32 bias
    row, and the (possibly padded) output tile.  ``waste_frac`` is the
    padded-output overshoot the remainder tiles compute and throw away.
    ``tap_issues`` counts the per-row tap ops the kernel issues (K^2 per
    conv row and conv-column phase, whatever the tile's width), so narrow
    column tiles pay for issuing more, smaller dots.  ``cost`` adds
    ``LAUNCH_COST_BYTES`` per grid step and ``TAP_COST_BYTES`` per tap
    issue as bytes-equivalents.  Channel extents are rounded up to whole
    lanes and column extents to whole sublanes, as the blocks occupy (and
    move) them on hardware."""
    launches = n_batch * n_c_blocks * n_h_blocks * n_w_blocks
    sub = _sublanes(dtype_bytes)
    co_l = _rup(block_co, LANE)
    x_tile = tile_in_h * _rup(tile_in_w, sub) * _rup(cin_block, LANE) \
        * dtype_bytes
    w_slice = K * K * _rup(cin_per_group, sub) * co_l * dtype_bytes
    b_row = co_l * 4
    o_tile = tile_h * _rup(tile_w, sub) * co_l * dtype_bytes
    hbm = launches * (x_tile + w_slice + b_row + o_tile)
    taps = launches * tile_conv_h * conv_phases * K * K
    waste = (n_h_blocks * tile_h * n_w_blocks * tile_w) \
        / (p_out * pw_out) - 1.0
    return {"launches": launches, "hbm_bytes": hbm, "tap_issues": taps,
            "waste_frac": waste,
            "cost": float(hbm + LAUNCH_COST_BYTES * launches
                          + TAP_COST_BYTES * taps)}


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """Static tiling decision + derived geometry for one conv shape
    (exposed for tests; ``conv2d`` consumes it so the block geometry and
    the VMEM estimate can never desynchronise).

    With a fused maxpool epilogue (``pool_k > 0``) the kernel's output
    rows/cols are *pooled*: ``tile_h x tile_w`` tiles ``p_out x pw_out``,
    and each grid step internally computes ``tile_conv_h x tile_conv_w``
    conv elements.  ``tile_in_h`` / ``tile_in_w`` are the input rows/cols
    a tile depends on (the staged window may round the columns up to whole
    phase slots)."""
    block_co: int
    cin_block: int
    tile_h: int
    tile_in_h: int
    n_h_blocks: int
    vmem_bytes: int
    h_out: int
    w_out: int
    g_out: int          # output channels per group
    depthwise: bool
    pool_k: int = 0     # fused maxpool window (0 = no pool epilogue)
    pool_s: int = 1     # fused maxpool stride
    p_out: int = 0      # pooled output rows (== h_out when no pool)
    pw_out: int = 0     # pooled output cols (== w_out when no pool)
    tile_conv_h: int = 0  # conv rows computed per grid step
    tile_w: int = 0       # output cols per grid step (pooled when fused)
    tile_in_w: int = 0    # input cols per grid step (with halo)
    n_w_blocks: int = 1   # column tiles (1 = full-width rows)
    tile_conv_w: int = 0  # conv cols computed per grid step
    launches: int = 0     # total grid steps (batch x channel x h x w)
    tap_issues: int = 0   # plan_cost()["tap_issues"] for this geometry
    cost_bytes: float = 0.0   # plan_cost()["cost"] for this geometry
    searched: bool = False    # True when the joint search picked the plan


def plan_conv(x_shape: tuple, w_shape: tuple, *, stride: int = 1,
              pad: int = 0, groups: int = 1, block_co: int = 0,
              tile_h: int = 0, tile_w: int = 0, dtype_bytes: int = 4,
              pool_k: int = 0, pool_s: int = 0,
              vmem_budget: int = DEFAULT_VMEM_BUDGET,
              search: bool | None = None) -> ConvPlan:
    """Pick ``(block_co, tile_h, tile_w)`` for the grid and estimate
    per-step VMEM.

    By default the joint cost-model search runs (``plan_cost`` over every
    accepted channel block and column-split candidate).  Explicit
    ``block_co`` / ``tile_h`` arguments pin those dimensions and bypass
    the search (test/debug overrides keep the legacy greedy semantics);
    ``tile_w`` (or ``REPRO_CONV_TILE_W``) pins the column tile while the
    search still picks ``block_co``/``tile_h``.  ``search=False`` (or
    ``REPRO_CONV_SEARCH=0``) is the legacy greedy planner: largest
    accepted ``block_co <= 128``, then the largest row tile -- and a
    ValueError when a single output row overflows the budget.

    A channel block is accepted when it is the whole channel extent (all
    output channels, or one group's) or a multiple of 128 lanes dividing
    it; an explicit ``block_co`` outside that set raises."""
    N, Cin, H, W = x_shape
    Cout, cin_pg, K, _ = w_shape
    if Cin != cin_pg * groups or Cout % groups:
        raise ValueError(f"bad grouping: x Cin={Cin}, w Cin/g={cin_pg}, "
                         f"groups={groups}, Cout={Cout}")
    g_out = Cout // groups
    depthwise = cin_pg == 1 and groups > 1
    if depthwise and g_out != 1:
        raise ValueError("depthwise with channel multiplier > 1 unsupported")
    limit = Cout if groups == 1 or depthwise else g_out
    blocks = channel_blocks(limit)
    if block_co and block_co not in blocks:
        raise ValueError(
            f"block_co={block_co} must divide the {limit} output channels "
            f"of a group and be the whole extent or a multiple of {LANE} "
            f"lanes (accepted: {blocks})")
    h_in, w_in = H + 2 * pad, W + 2 * pad
    h_out = (h_in - K) // stride + 1
    w_out = (w_in - K) // stride + 1
    if pool_k:
        pool_s = pool_s or pool_k
        if pool_s < 1:
            raise ValueError(f"pool_s={pool_s} must be >= 1")
        p_out = _pool_out(h_out, pool_k, pool_s)
        pw_out = _pool_out(w_out, pool_k, pool_s)
        if h_out < 1 or p_out < 1 or pw_out < 1:
            raise ValueError(
                f"invalid fused conv+pool geometry: conv out "
                f"{h_out}x{w_out}, pool(k={pool_k}, s={pool_s}) out "
                f"{p_out}x{pw_out}")
    else:
        pool_s = 1
        p_out, pw_out = h_out, w_out
    if h_out < 1 or w_out < 1:
        raise ValueError(f"invalid conv geometry: output {h_out}x{w_out} "
                         f"(kernel/stride larger than padded input)")
    tile_w = min(tile_w_override(tile_w), pw_out)

    def est_kw(bc):
        return dict(cin_block=cin_pg * (bc if depthwise else 1),
                    block_co=bc, w_out=w_out, K=K, stride=stride,
                    cin_per_group=cin_pg, dtype_bytes=dtype_bytes,
                    pool_k=pool_k, pool_s=pool_s)

    def finalize(bc, th, tw, searched):
        cin_block = cin_pg * (bc if depthwise else 1)
        th, tw = min(th, p_out), min(tw, pw_out)
        n_h, n_w = -(-p_out // th), -(-pw_out // tw)
        tile_conv_h = (th - 1) * pool_s + pool_k if pool_k else th
        if n_w == 1:
            # single column tile: full-width rows over the padded input
            tile_conv_w, tile_in_w, tw_est = w_out, w_in, 0
        else:
            tile_conv_w = (tw - 1) * pool_s + pool_k if pool_k else tw
            tile_in_w, tw_est = (tile_conv_w - 1) * stride + K, tw
        tile_in_h = (tile_conv_h - 1) * stride + K
        cost = plan_cost(
            n_batch=N, n_c_blocks=Cout // bc, n_h_blocks=n_h,
            n_w_blocks=n_w, cin_block=cin_block, block_co=bc, tile_h=th,
            tile_w=tw, tile_in_h=tile_in_h, tile_in_w=tile_in_w, K=K,
            cin_per_group=cin_pg, dtype_bytes=dtype_bytes, p_out=p_out,
            pw_out=pw_out, tile_conv_h=tile_conv_h,
            conv_phases=pool_s if pool_k else 1)
        return ConvPlan(
            block_co=bc, cin_block=cin_block, tile_h=th,
            tile_in_h=tile_in_h, n_h_blocks=n_h,
            vmem_bytes=conv_vmem_bytes(tile_h=th, tile_w=tw_est,
                                       **est_kw(bc)),
            h_out=h_out, w_out=w_out, g_out=g_out, depthwise=depthwise,
            pool_k=pool_k, pool_s=pool_s, p_out=p_out, pw_out=pw_out,
            tile_conv_h=tile_conv_h, tile_w=tw, tile_in_w=tile_in_w,
            n_w_blocks=n_w, tile_conv_w=tile_conv_w,
            launches=cost["launches"], tap_issues=cost["tap_issues"],
            cost_bytes=cost["cost"],
            searched=searched)

    do_search = search_enabled(search) and not block_co and not tile_h
    if not do_search:
        # legacy greedy: largest accepted channel block <= 128 (else the
        # smallest accepted), then the largest row tile that fits
        if not block_co:
            block_co = max((b for b in blocks if b <= 128),
                           default=blocks[0])
        if not tile_h:
            tile_h = choose_tile_h(p_out, budget=vmem_budget,
                                   tile_w=tile_w, **est_kw(block_co))
        return finalize(block_co, tile_h, tile_w or pw_out, False)

    # joint search: every accepted channel block x column-split candidate,
    # row tile maximised by bisection, scored by plan_cost
    bcs = [b for b in blocks if b <= MAX_BLOCK_CO] or blocks[:1]
    if tile_w:
        tws = [tile_w]
    else:
        tws = sorted({-(-pw_out // n)
                      for n in range(1, min(pw_out, MAX_W_SPLITS) + 1)},
                     reverse=True)
    best, best_key = None, None
    for bc in bcs:
        kw = est_kw(bc)
        for tw in tws:
            tw_est = 0 if tw >= pw_out else tw
            th = _max_fit_tile_h(
                functools.partial(conv_vmem_bytes, tile_w=tw_est, **kw),
                min(p_out, 512), vmem_budget)
            if th == 0:
                continue
            # shrink both tiles to the smallest with the same block count
            th = -(-p_out // -(-p_out // th))
            tw_s = -(-pw_out // -(-pw_out // tw))
            cand = finalize(bc, th, tw_s, True)
            key = (cand.cost_bytes, cand.launches, cand.n_w_blocks,
                   cand.n_h_blocks, -cand.block_co)
            if best is None or key < best_key:
                best, best_key = cand, key
    if best is None:
        one = conv_vmem_bytes(tile_h=1, tile_w=1, **est_kw(bcs[0]))
        raise ValueError(
            f"no feasible conv tiling: even a single-element output tile "
            f"at block_co={bcs[0]} needs {one} bytes > budget "
            f"{vmem_budget}")
    return best


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *scratch, K: int, stride: int,
                 phases: int, U: int, tile_h: int, tile_conv_h: int,
                 depthwise: bool, activation: str | None, pool_k: int,
                 pool_s: int, precision):
    # x_ref: (tile_in_h, phases, wq, cin_block) haloed window
    # w_ref: (K*K, block_co) depthwise | (K*K, cin_per_group, block_co)
    # o_ref: (tile_h, tile_w, block_co); scratch: pooled-epilogue planes
    bias = b_ref[...]                       # (1, block_co) fp32
    conv_phases = pool_s if pool_k else 1

    def conv_row(r, carry):
        for q in range(conv_phases):        # conv columns q, q+pool_s, ...
            acc = None
            for kh in range(K):
                for kw in range(K):
                    t = q * stride + kw
                    xs = x_ref[r * stride + kh, t % phases,
                               pl.ds(t // phases, U), :]    # (U, cin)
                    if depthwise:
                        wk = w_ref[pl.ds(kh * K + kw, 1), :]
                        y = xs.astype(jnp.float32) * wk.astype(jnp.float32)
                    else:
                        y = jax.lax.dot_general(
                            xs, w_ref[kh * K + kw], (((1,), (0,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
                    acc = y if acc is None else acc + y
            acc = acc + bias
            if activation == "relu":
                acc = jnp.maximum(acc, 0.0)
            elif activation == "relu6":
                acc = jnp.clip(acc, 0.0, 6.0)
            if pool_k:
                scratch[0][q, r] = acc
            else:
                o_ref[r] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tile_conv_h, conv_row, 0)
    if not pool_k:
        return
    # pooled epilogue: max over the pool_k x pool_k window, straight from
    # the fp32 scratch -- the conv rows never leave VMEM
    tile_w = o_ref.shape[1]

    def pool_row(p, carry):
        m = None
        for ph in range(pool_k):
            for pw in range(pool_k):
                v = scratch[0][pw % pool_s, p * pool_s + ph,
                               pl.ds(pw // pool_s, tile_w), :]
                m = v if m is None else jnp.maximum(m, v)
        o_ref[p] = m.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, tile_h, pool_row, 0)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
           pad: int = 0, bias: jnp.ndarray | None = None,
           activation: str | None = None, groups: int = 1,
           pool_k: int = 0, pool_s: int = 0,
           block_co: int = 0, tile_h: int = 0, tile_w: int = 0,
           vmem_budget: int = DEFAULT_VMEM_BUDGET,
           search: bool | None = None,
           interpret: bool | None = None) -> jnp.ndarray:
    """x: (N, Cin, H, W); w: (Cout, Cin/groups, K, K) -> (N, Cout, Ho, Wo).

    ``bias`` (Cout,) and ``activation`` in {None, "relu", "relu6"} fuse into
    the kernel epilogue; ``groups`` follows lax ``feature_group_count``.
    ``pool_k > 0`` additionally fuses a VALID ``maxpool(pool_k, pool_s)``
    (``pool_s`` defaults to ``pool_k``) after the activation, returning the
    pooled (N, Cout, Po, Pw) tensor from the same launch.  Tiling comes
    from ``plan_conv`` (joint cost-model search by default; ``block_co`` /
    ``tile_h`` / ``tile_w`` / ``search`` are overrides).  ``interpret``
    runs the kernel body in the Pallas interpreter instead of compiling
    it; None follows the platform (``kernels.interpret_mode``)."""
    if activation not in (None, "relu", "relu6"):
        raise ValueError(f"unknown activation {activation!r}")
    if interpret is None:
        interpret = interpret_mode()
    N, Cin, H, W = x.shape
    Cout, cin_pg, K, _ = w.shape
    plan = plan_conv(x.shape, w.shape, stride=stride, pad=pad, groups=groups,
                     block_co=block_co, tile_h=tile_h, tile_w=tile_w,
                     pool_k=pool_k, pool_s=pool_s,
                     dtype_bytes=x.dtype.itemsize, vmem_budget=vmem_budget,
                     search=search)
    bc, th, tw = plan.block_co, plan.tile_h, plan.tile_w
    pool_k, pool_s = plan.pool_k, plan.pool_s
    n_h, n_w = plan.n_h_blocks, plan.n_w_blocks
    S, U, wq = col_phases(tw, K=K, stride=stride, pool_k=pool_k,
                          pool_s=pool_s)
    G = 1 if plan.depthwise else groups
    cg, g_out = Cin // G, Cout // G

    # haloed windows: consecutive tiles advance by tile * pool_s conv
    # elements = tile * pool_s * stride input elements, so pooled tiles
    # land on pool-window starts; in phase slots a column tile is tw wide
    row_step = th * pool_s * stride
    rows = (n_h - 1) * row_step + plan.tile_in_h
    cols = ((n_w - 1) * tw + wq) * S
    x = jnp.pad(x, ((0, 0), (0, 0), (pad, max(0, rows - H - pad)),
                    (pad, max(0, cols - W - pad))))[:, :, :rows, :cols]
    x = x.reshape(N, G, cg, rows, cols // S, S) \
        .transpose(1, 0, 3, 5, 4, 2).reshape(G * N, rows, S, cols // S, cg)
    x = jnp.stack([x[:, h * row_step:h * row_step + plan.tile_in_h]
                   for h in range(n_h)], axis=1)
    x = jnp.stack([x[..., v * tw:v * tw + wq, :] for v in range(n_w)],
                  axis=2)       # (G*N, n_h, n_w, tile_in_h, S, wq, cg)

    if bias is None:
        bias = jnp.zeros((Cout,), jnp.float32)
    bias = bias.astype(jnp.float32).reshape(G, 1, g_out)
    if plan.depthwise:
        wk = w.reshape(Cout, K * K).T                       # (K*K, C)
        x_spec = pl.BlockSpec(
            (None, None, None, plan.tile_in_h, S, wq, bc),
            lambda b, c, h, v: (b, h, v, 0, 0, 0, c))
        w_spec = pl.BlockSpec((K * K, bc), lambda b, c, h, v: (0, c))
    else:
        wk = w.reshape(G, g_out, cin_pg, K * K).transpose(0, 3, 2, 1)
        x_spec = pl.BlockSpec(
            (None, None, None, plan.tile_in_h, S, wq, cg),
            lambda b, c, h, v: (b, h, v, 0, 0, 0, 0))
        w_spec = pl.BlockSpec((None, K * K, cin_pg, bc),
                              lambda b, c, h, v: (b // N, 0, 0, c))
    # explicit either way: a caller's default_matmul_precision("highest")
    # would otherwise ask Mosaic for an fp32 contraction of bf16 operands,
    # which it refuses
    kernel = functools.partial(
        _conv_kernel, K=K, stride=stride, phases=S, U=U, tile_h=th,
        tile_conv_h=plan.tile_conv_h, depthwise=plan.depthwise,
        activation=activation, pool_k=pool_k, pool_s=pool_s,
        precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))
    scratch = [pltpu.VMEM((pool_s, plan.tile_conv_h, U, bc), jnp.float32)] \
        if pool_k else []
    out = pl.pallas_call(
        kernel,
        grid=(G * N, g_out // bc, n_h, n_w),
        in_specs=[x_spec, w_spec,
                  pl.BlockSpec((None, 1, bc),
                               lambda b, c, h, v: (b // N, 0, c))],
        out_specs=pl.BlockSpec((None, None, None, th, tw, bc),
                               lambda b, c, h, v: (b, h, v, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((G * N, n_h, n_w, th, tw, g_out),
                                       x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4,
            vmem_limit_bytes=vmem_budget + MOSAIC_SCRATCH_BYTES),
        interpret=interpret,
    )(x, wk, bias)
    out = out.reshape(G, N, n_h, n_w, th, tw, g_out) \
        .transpose(1, 0, 6, 2, 4, 3, 5).reshape(N, Cout, n_h * th, n_w * tw)
    return out[:, :, :plan.p_out, :plan.pw_out]

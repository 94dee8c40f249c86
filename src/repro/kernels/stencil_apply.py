"""Pallas TPU kernel backend for ``stencil.apply`` (DESIGN.md §2).

The paper lowers stencil kernels to GPU (CUDA via MLIR) and FPGA (HLS);
the TPU-native analogue is a Pallas kernel with explicit BlockSpec VMEM
tiling.  Rather than hand-writing one kernel per stencil, the apply op's
*point function is code-generated into the kernel body*: each grid step
fetches one overlapping window per operand into VMEM, accesses become
static slices of the resident window, and the arithmetic DAG is emitted
verbatim — the same "domain information drives the lowering" story the
paper tells for GPUs, retargeted at the TPU memory hierarchy:

    HBM --(aligned overlapping window, double-buffered)--> VMEM --(slices)--> VPU

Windows obey the TPU's (8, 128) block rule for f32: a window is the
output tile grown by the operand's access extent and rounded up to whole
(sublane, lane) tiles along the last two dims, and every window starts
at a multiple of the tile, so each element-indexed block (``pl.Element``)
has an aligned shape and offset.  A tile need not divide the result: the
grid rounds up and Pallas drops the last tile's overhang (epoch-grown
applies have extents like 16384 + 24 that no aligned tile divides).
Every window must lie inside the operand array, so the last one needs
``high_slack`` points past the operand's high bound: rounding slack and
the last tile's dropped overhang, never read into a kept output.  An
operand that comes from a ``comm.halo_pad`` gets that slack from the pad
itself (the lowering plans it at compile time) and is read in place;
any other is re-based and zero-padded in XLA first (``window_source``,
counted in ``dispatch_stats().window_copies``).  The tile is chosen
for the fewest HBM bytes fetched per output point whose *whole-kernel*
VMEM footprint — every window and output block double-buffered, plus
value temporaries — fits ``VMEM_BUDGET_BYTES``.
"""
from __future__ import annotations

import itertools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dialects import stencil
from repro.kernels import _DISPATCH, KernelPlanError

SUBLANES, LANES = 8, 128  # f32 (sublane, lane) tile of the last two dims
# Whole-kernel working set the tile chooser targets, and the scoped VMEM
# limit handed to Mosaic (a v5e core has 128 MiB of VMEM; the limit
# leaves the compiler room beyond the estimate).
VMEM_BUDGET_BYTES = 24 * 2**20
VMEM_LIMIT_BYTES = 96 * 2**20
# value temporaries a kernel body keeps live, counted in output tiles
TEMP_TILES = 4
# Mosaic unrolls a kernel body over the vector registers of its tile, so
# compile time grows with tile area: 128K points (128 f32 vregs) keeps a
# per-apply kernel near a second and a fused epoch near ten.
MAX_TILE_POINTS = 128 * 1024


def _align(rank: int, d: int) -> int:
    if d == rank - 1:
        return LANES
    if d == rank - 2:
        return SUBLANES
    return 1


def _round_up(n: int, a: int) -> int:
    return -(-n // a) * a


def _vmem_numel(shape: Sequence[int]) -> int:
    """Elements a VMEM buffer of ``shape`` occupies (the last two dims
    padded to the (8, 128) tile)."""
    rank = len(shape)
    return math.prod(_round_up(s, _align(rank, d)) for d, s in enumerate(shape))


def _legal_extent(n: int, t: int, align: int) -> bool:
    return t == n or (0 < t < n and t % align == 0)


def tile_extents(n: int, align: int) -> list:
    """The tile extents the chooser tries along a dim of extent ``n``:
    ``align * 2**j`` below ``n``, and ``n`` itself."""
    out, t = [], align
    while t < n:
        out.append(t)
        t *= 2
    return out + [n]


def is_legal_tile(shape: Sequence[int], tile: Sequence[int]) -> bool:
    """Every extent is the dim's whole extent or a multiple of its
    alignment below it."""
    rank = len(shape)
    return len(tile) == rank and all(
        _legal_extent(n, t, _align(rank, d))
        for d, (n, t) in enumerate(zip(shape, tile))
    )


def grid_shape(shape: Sequence[int], tile: Sequence[int]) -> tuple:
    return tuple(-(-n // t) for n, t in zip(shape, tile))


def check_tile(shape: Sequence[int], tile: Sequence[int]) -> tuple:
    """``tile`` as a tuple, or a ``KernelPlanError`` naming the rule it
    breaks."""
    tile = tuple(int(t) for t in tile)
    rank = len(shape)
    if len(tile) != rank:
        raise KernelPlanError(
            f"tile {tile} has {len(tile)} dims, shape {tuple(shape)} has {rank}"
        )
    for d, (n, t) in enumerate(zip(shape, tile)):
        if not _legal_extent(n, t, _align(rank, d)):
            raise KernelPlanError(
                f"tile {tile} is illegal for shape {tuple(shape)}: dim {d} "
                f"extent {t} must be a multiple of {_align(rank, d)} below "
                f"{n}, or {n} itself"
            )
    return tile


def window_shape(tile: Sequence[int], span: Sequence[int]) -> tuple:
    """VMEM window of an operand read ``span`` points beyond the tile."""
    rank = len(tile)
    return tuple(
        _round_up(t + s, _align(rank, d))
        for d, (t, s) in enumerate(zip(tile, span))
    )


def vmem_bytes(tile, spans, n_out: int, double_buffered: bool = True) -> int:
    """Whole-kernel VMEM estimate for one grid step: every operand window
    and output block (twice when Pallas double-buffers them) plus
    ``TEMP_TILES`` tile-sized value temporaries, in f32 bytes."""
    blocks = sum(_vmem_numel(window_shape(tile, s)) for s in spans)
    blocks += n_out * _vmem_numel(tile)
    copies = 2 if double_buffered else 1
    return 4 * (copies * blocks + TEMP_TILES * _vmem_numel(tile))


def choose_tile(
    shape: Sequence[int],
    spans: Sequence[Sequence[int]],
    n_out: int = 1,
    budget: int = VMEM_BUDGET_BYTES,
) -> tuple:
    """The legal tile of ``shape`` fetching the fewest window bytes per
    output point whose whole-kernel VMEM fits ``budget`` and whose area is
    at most ``MAX_TILE_POINTS`` (ties: the larger tile, i.e. fewer grid
    steps).  ``spans[k]`` is how far operand ``k`` reads beyond the tile
    along each dim."""
    shape = tuple(shape)
    rank = len(shape)
    options = [tile_extents(n, _align(rank, d)) for d, n in enumerate(shape)]
    best, best_key = None, None
    for tile in itertools.product(*options):
        if (
            math.prod(tile) > MAX_TILE_POINTS
            or vmem_bytes(tile, spans, n_out) > budget
        ):
            continue
        fetched = sum(math.prod(window_shape(tile, s)) for s in spans)
        fetched *= math.prod(grid_shape(shape, tile))
        key = (fetched / math.prod(shape), -math.prod(tile))
        if best_key is None or key < best_key:
            best, best_key = tile, key
    if best is None:
        smallest = tuple(o[0] for o in options)
        raise KernelPlanError(
            f"no tile of shape {shape} fits the {budget} B VMEM budget (or "
            f"{MAX_TILE_POINTS} points): the "
            f"smallest legal tile {smallest} needs "
            f"{vmem_bytes(smallest, spans, n_out)} B (windows "
            f"{[window_shape(smallest, s) for s in spans]}, "
            f"{n_out} output(s))"
        )
    return best


def window_reach(shape, tile, window) -> tuple:
    """Points from the first window's origin to the last window's end
    along each dim, over an output of ``shape``."""
    return tuple(
        (g - 1) * t + w
        for g, t, w in zip(grid_shape(shape, tile), tile, window)
    )


def window_source(arr, base, shape, tile, window):
    """``arr`` re-based for element-indexed windows: window ``i`` along
    each dim starts at ``i * tile`` and the last one (for an output of
    ``shape``) ends inside the array.  Read in place when ``base`` is 0
    and the array reaches far enough; otherwise slices off ``base``
    leading points and zero-pads the high end (a copy, counted in
    ``dispatch_stats().window_copies``)."""
    need = window_reach(shape, tile, window)
    copied = False
    if any(base):
        avail = tuple(min(s - b, m) for s, b, m in zip(arr.shape, base, need))
        arr = lax.slice(
            arr, tuple(base), tuple(b + a for b, a in zip(base, avail))
        )
        copied = True
    pad = [(0, max(0, m - s)) for m, s in zip(need, arr.shape)]
    if any(hi for _, hi in pad):
        arr = jnp.pad(arr, pad)
        copied = True
    if copied:
        _DISPATCH.window_copies += 1
    return arr


def window_spec(
    grid: Sequence[int], tile: Sequence[int], window: Sequence[int]
) -> pl.BlockSpec:
    """Element-indexed block of shape ``window`` starting at tile ``i``'s
    origin: an aligned offset, or the constant 0 along a dim the grid does
    not split (Mosaic must prove each offset aligned, and a whole-extent
    tile such as 2054 is not)."""
    steps = tuple((t if g > 1 else 0) for g, t in zip(grid, tile))

    def index_map(*ids):
        return tuple(i * s for i, s in zip(ids, steps))

    return pl.BlockSpec(tuple(pl.Element(w) for w in window), index_map)


def compiler_params(rank: int) -> pltpu.CompilerParams:
    # every grid step writes its own output tile: steps are independent
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * rank,
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


def apply_spans(apply_op: stencil.ApplyOp) -> list:
    """(lo, hi) access extent of every operand (zeros if never read)."""
    rank = apply_op.result_bounds.rank
    exts = apply_op.access_extents()
    zero = (tuple([0] * rank), tuple([0] * rank))
    return [exts.get(k, zero) for k in range(len(apply_op.operands))]


def plan_apply(
    apply_op: stencil.ApplyOp,
    result_bounds: stencil.Bounds,
    tile: Optional[Sequence[int]] = None,
) -> tuple:
    """The tile one apply kernel runs with: ``tile`` checked against the
    (8, 128) rule, or the chooser's pick.  Raises ``KernelPlanError``."""
    shape = result_bounds.shape
    spans = [
        tuple(h - l for l, h in zip(lo, hi)) for lo, hi in apply_spans(apply_op)
    ]
    if tile is not None:
        return check_tile(shape, tile)
    return choose_tile(shape, spans, n_out=len(apply_op.results))


def high_slack(
    apply_op: stencil.ApplyOp, result_bounds: stencil.Bounds, tile: tuple
) -> list:
    """Per operand, the points its last window reaches past the high
    bound of the operand's type along each dim (0 where it ends inside):
    how much longer an array must be for ``window_source`` to read it in
    place.  ``None`` for an operand whose first window starts past its low
    bound: ``window_source`` re-bases (copies) it whatever its length."""
    rb = result_bounds
    out = []
    for v, (lo, hi) in zip(apply_op.operands, apply_spans(apply_op)):
        ob = v.type.bounds
        if any(r + l != o for r, l, o in zip(rb.lb, lo, ob.lb)):
            out.append(None)
            continue
        window = window_shape(tile, [h - l for l, h in zip(lo, hi)])
        reach = window_reach(rb.shape, tile, window)
        out.append(tuple(
            max(0, o + m - u) for o, m, u in zip(ob.lb, reach, ob.ub)
        ))
    return out


def build_apply_kernel(
    apply_op: stencil.ApplyOp,
    result_bounds: stencil.Bounds,
    tile: tuple,
    *,
    interpret: bool,
    name: Optional[str] = None,
):
    """Code-generate a pallas_call for one stencil.apply over ``tile``.

    The call takes one re-based window source per operand
    (``window_source``) and returns the result arrays."""
    from repro.core.lowering import eval_apply_body  # shared evaluator

    shape = result_bounds.shape
    rank = len(shape)
    spans = apply_spans(apply_op)
    grid = grid_shape(shape, tile)
    in_specs = [
        window_spec(
            grid, tile, window_shape(tile, [h - l for l, h in zip(lo, hi)])
        )
        for lo, hi in spans
    ]
    # window k's point 0 sits at tile-relative coordinate lo_k
    window_origins = [tuple(lo) for lo, _ in spans]
    tile_bounds = stencil.Bounds.from_shape(tile)
    n_in = len(spans)

    def kernel(*refs):
        blocks = [r[...] for r in refs[:n_in]]
        outs = eval_apply_body(apply_op, blocks, window_origins, tile_bounds)
        for o_ref, val in zip(refs[n_in:], outs):
            o_ref[...] = val

    n_out = len(apply_op.results)
    out_specs = [pl.BlockSpec(tile, lambda *ids: ids) for _ in range(n_out)]
    out_shape = [jax.ShapeDtypeStruct(shape, jnp.float32) for _ in range(n_out)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if n_out > 1 else out_specs[0],
        out_shape=out_shape if n_out > 1 else out_shape[0],
        compiler_params=compiler_params(rank),
        interpret=interpret,
        name=name,
    )


def run_apply_pallas(
    apply_op: stencil.ApplyOp,
    arrays: Sequence,
    origins: Sequence[tuple],
    result_bounds: stencil.Bounds,
    tile: Optional[tuple] = None,
    *,
    interpret: bool,
    name: Optional[str] = None,
) -> list:
    """Entry point used by the lowering's pallas backend: ``arrays[k]``
    holds logical points from ``origins[k]`` on (and may run past the
    operand's high bound: see ``high_slack``).  Each call is one traced
    pallas_call (counted in ``kernels.dispatch_stats``), named ``name``."""
    tile = plan_apply(apply_op, result_bounds, tile)
    rb = result_bounds
    sources = []
    for arr, og, (lo, hi) in zip(arrays, origins, apply_spans(apply_op)):
        base = tuple(r + l - o for r, l, o in zip(rb.lb, lo, og))
        if any(b < 0 for b in base):
            raise ValueError(
                f"operand window starts at {base} before the array "
                "origin (halo missing — run the decompose pass first)"
            )
        window = window_shape(tile, [h - l for l, h in zip(lo, hi)])
        sources.append(
            window_source(arr.astype(jnp.float32), base, rb.shape, tile,
                          window)
        )
    call = build_apply_kernel(apply_op, rb, tile, interpret=interpret,
                              name=name)
    _DISPATCH.apply_calls += 1
    out = call(*sources)
    return list(out) if isinstance(out, (tuple, list)) else [out]

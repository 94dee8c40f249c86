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
at an aligned offset, so each element-indexed block (``pl.Element``) has
an aligned shape and offset.  A tile need not divide the result: the
grid rounds up and Pallas drops the last tile's overhang (epoch-grown
applies have extents like 16384 + 24 that no aligned tile divides).
The rounding slack and that overhang are never read into a kept output,
so the last window may run past the operand array's end: those points
are Mosaic high padding (``pl.Element(w, (0, hi))``), and nothing is
copied to make room for them.

An operand may read as zero outside its array (``zero``: the lowering
passes the unpadded operand of a ``comm.halo_pad`` whose halo can only
hold zeros).  Its windows reach the halo's depth ``A`` before each tile,
rounded up to the dim's alignment where the grid splits the dim: window
``i`` starts at ``max(i * tile - A, 0)``.  Interior tiles run the plain
body.  Edge tiles (a clamped start, or reads past the array's end) take
a second branch that shifts the first tile's window back by ``A`` (by
the halo itself along a dim the grid does not split) and zeroes every
point outside the array with an iota mask: the halo is built in VMEM.
``stencil.index`` in a kernel body reads the tile's origin in the grid
(``program_id`` times the tile, plus the result's low bound).  The tile
is chosen for the fewest HBM bytes fetched per output point whose
*whole-kernel* VMEM footprint — every window and output block
double-buffered, plus value temporaries — fits ``VMEM_BUDGET_BYTES``.
"""
from __future__ import annotations

import itertools
import math
from typing import NamedTuple, Optional, Sequence

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


def window_shape(tile, span, lead=None, shape=None) -> tuple:
    """VMEM window of an operand read ``span`` points beyond the tile's
    end and ``lead`` points before its start (none by default).  Where
    the grid splits a dim of ``shape``, the lead rounds up to the dim's
    alignment, so that every window but the first starts aligned."""
    rank = len(tile)
    lead = lead or (0,) * rank
    shape = shape or tile
    out = []
    for d, (t, s, l, n) in enumerate(zip(tile, span, lead, shape)):
        a = _align(rank, d)
        out.append(_round_up(t + s + (_round_up(l, a) if t < n else l), a))
    return tuple(out)


def _windows(tile, spans, leads, shape) -> list:
    leads = leads or [None] * len(spans)
    return [window_shape(tile, s, l, shape) for s, l in zip(spans, leads)]


def vmem_bytes(
    tile, spans, n_out: int, double_buffered: bool = True, leads=None,
    shape=None, halos=None,
) -> int:
    """Whole-kernel VMEM estimate for one grid step: every operand window
    and output block (twice when Pallas double-buffers them), one more
    copy of each window ``halos`` marks (the buffer its zero halo is
    built in), and ``TEMP_TILES`` tile-sized value temporaries, in f32
    bytes."""
    windows = _windows(tile, spans, leads, shape)
    blocks = sum(_vmem_numel(w) for w in windows) + n_out * _vmem_numel(tile)
    copies = 2 if double_buffered else 1
    built = sum(_vmem_numel(w) for w, h in zip(windows, halos or ()) if h)
    return 4 * (copies * blocks + built + TEMP_TILES * _vmem_numel(tile))


def choose_tile(
    shape: Sequence[int],
    spans: Sequence[Sequence[int]],
    n_out: int = 1,
    budget: int = VMEM_BUDGET_BYTES,
    leads=None,
    halos=None,
) -> tuple:
    """The legal tile of ``shape`` fetching the fewest window bytes per
    output point whose whole-kernel VMEM (``vmem_bytes``) fits
    ``budget`` and whose area is at most ``MAX_TILE_POINTS`` (ties: the
    larger tile, i.e. fewer grid steps).  ``spans[k]`` is how far operand
    ``k``'s window reaches beyond the tile along each dim, ``leads[k]``
    how far before it (``window_shape``)."""
    shape = tuple(shape)
    rank = len(shape)
    options = [tile_extents(n, _align(rank, d)) for d, n in enumerate(shape)]

    def need(tile):
        return vmem_bytes(tile, spans, n_out, leads=leads, shape=shape,
                          halos=halos)

    best, best_key = None, None
    for tile in itertools.product(*options):
        if math.prod(tile) > MAX_TILE_POINTS or need(tile) > budget:
            continue
        fetched = sum(math.prod(w) for w in _windows(tile, spans, leads, shape))
        fetched *= math.prod(grid_shape(shape, tile))
        key = (fetched / math.prod(shape), -math.prod(tile))
        if best_key is None or key < best_key:
            best, best_key = tile, key
    if best is None:
        smallest = tuple(o[0] for o in options)
        raise KernelPlanError(
            f"no tile of shape {shape} fits the {budget} B VMEM budget (or "
            f"{MAX_TILE_POINTS} points): the "
            f"smallest legal tile {smallest} needs {need(smallest)} B "
            f"(windows {_windows(smallest, spans, leads, shape)}, "
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
    """``arr`` re-based for the fused-epoch kernel's element-indexed
    windows (``window_spec``): window ``i`` along
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
    origin (the fused-epoch kernel's windows): an aligned offset, or the constant 0 along a dim the grid does
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


def apply_sources(apply_op: stencil.ApplyOp) -> list:
    """Every operand read from an array covering its type's bounds, with
    no zero halo: the sources ``plan_apply`` assumes by default."""
    return [(v.type.bounds, False) for v in apply_op.operands]


def _reads(apply_op, result_bounds, sources) -> list:
    """Per operand, ``None`` where it is fetched as the output's own
    block (read only at the point, from an array that starts at the
    result's origin and covers it), else ``(lead, span)``: how many
    points tile 0 reads before the array's first point (only an operand
    with a zero halo may), and how far a window reaches past its tile's
    end, counted from the array's origin.  ``sources[k]`` is the bounds
    of operand ``k``'s array and whether it reads as zero outside them.
    Raises ``ValueError`` where any other operand is read before its
    array's origin."""
    rb = result_bounds
    out = []
    for (lo, hi), (ab, zero) in zip(apply_spans(apply_op), sources):
        off = tuple(r - a for r, a in zip(rb.lb, ab.lb))
        need = tuple(o + l for o, l in zip(off, lo))
        if not zero and any(n < 0 for n in need):
            raise ValueError(
                f"operand read from {tuple(r + l for r, l in zip(rb.lb, lo))}"
                f" but its array starts at {ab.lb} (halo missing — run the "
                "decompose pass first)"
            )
        if (not any(lo) and not any(hi) and not any(off)
                and all(u >= r for u, r in zip(ab.ub, rb.ub))):
            out.append(None)
        else:
            out.append((tuple(max(0, -n) for n in need),
                        tuple(o + h for o, h in zip(off, hi))))
    return out


class Window(NamedTuple):
    """How one operand is fetched for each tile, per dim, in the points
    of the operand's array: window ``i`` holds the array from ``i *
    tile + start`` on (from ``start`` along a dim the grid does not
    split), ``shape`` long; its point 0 sits at tile-relative coordinate
    ``origin``; ``pad`` is the Mosaic high padding the windows need past
    the array's ``extent``.  A negative ``start`` reaches into a zero
    halo (``zero``): tiles below ``low`` find their start clamped to 0,
    and tiles from ``high`` on read past the array's end."""

    start: tuple
    shape: tuple
    origin: tuple
    pad: tuple
    extent: tuple
    zero: bool
    low: tuple
    high: tuple


def plan_windows(
    apply_op: stencil.ApplyOp,
    result_bounds: stencil.Bounds,
    tile: tuple,
    sources: Optional[Sequence] = None,
) -> list:
    """Every operand's ``Window`` over ``tile`` (``None`` for an operand
    fetched as the output's own block); ``sources`` as ``_reads``."""
    rb = result_bounds
    shape, rank = rb.shape, rb.rank
    grid = grid_shape(shape, tile)
    sources = sources or apply_sources(apply_op)
    out = []
    for (_, hi), (ab, zero), read in zip(
        apply_spans(apply_op), sources, _reads(apply_op, rb, sources)
    ):
        if read is None:
            out.append(None)
            continue
        lead, span = read
        window = window_shape(tile, span, lead, shape)
        start, origin, pad, low, high = [], [], [], [], []
        for d in range(rank):
            t, g, n, w = tile[d], grid[d], ab.shape[d], window[d]
            c0 = -(_round_up(lead[d], _align(rank, d)) if g > 1 else lead[d])
            off = rb.lb[d] - ab.lb[d]
            start.append(c0)
            origin.append(c0 - off)
            pad.append(max(0, max(0, (g - 1) * t + c0) + w - n))
            low.append(-(c0 // t))
            # the first of the last tiles whose kept outputs read past
            # the array's end (only an operand with a zero halo may)
            first = g
            while zero and first > 0:
                i = first - 1
                if i * t + off + min(t, shape[d] - i * t) + hi[d] <= n:
                    break
                first = i
            high.append(first)
        out.append(Window(tuple(start), window, tuple(origin), tuple(pad),
                          tuple(ab.shape), zero, tuple(low), tuple(high)))
    return out


def _window_spec(grid, tile, w: Window) -> pl.BlockSpec:
    """Element-indexed block of window ``w``: tile ``i`` fetches from
    ``max(i * tile + start, 0)``, proved aligned for Mosaic, or from the
    constant 0 along a dim the grid does not split (a whole-extent tile
    such as 2054 is not aligned)."""
    rank = len(tile)

    def index_map(*ids):
        out = []
        for d, (i, t, g, c0) in enumerate(zip(ids, tile, grid, w.start)):
            if g == 1:
                out.append(0)
            elif c0 == 0:
                out.append(i * t)
            else:
                out.append(pl.multiple_of(jnp.maximum(i * t + c0, 0),
                                          _align(rank, d)))
        return tuple(out)

    return pl.BlockSpec(
        tuple(pl.Element(s, (0, p)) for s, p in zip(w.shape, w.pad)),
        index_map,
    )


def _edge_tiles(windows, grid):
    """Which tiles take the edge branch, per dim ``(low, high)``: tiles
    below ``low`` or from ``high`` on; ``None`` where no tile does."""
    zero = [w for w in windows if w is not None and w.zero]
    edges = [
        (max((w.low[d] for w in zero), default=0),
         min((w.high[d] for w in zero), default=g))
        for d, g in enumerate(grid)
    ]
    if all(lo == 0 and hi == g for (lo, hi), g in zip(edges, grid)):
        return None
    return edges


def _is_edge(ids, edges, grid):
    """Whether the tile at program ids ``ids`` is an edge tile."""
    cond = None
    for i, (lo, hi), g in zip(ids, edges, grid):
        for c in ([i < lo] if lo else []) + ([i >= hi] if hi < g else []):
            cond = c if cond is None else cond | c
    return cond


def _with_zero_halo(x, w: Window, ids, tile, grid):
    """A zero-halo operand's block at an edge tile: the window's points
    put where the body expects them (rolled back by ``-start`` where the
    start was clamped to 0, an aligned roll where the grid splits the
    dim) and every point outside the array zeroed, as ``comm.halo_pad``
    fills its zero halo."""
    keep = None
    for d in range(x.ndim):
        t, g, c0 = tile[d], grid[d], w.start[d]
        if c0 < 0 and g == 1:
            x = pltpu.roll(x, -c0, d)
        elif c0 < 0:
            clamped = x
            for i in range(w.low[d]):
                x = jnp.where(ids[d] == i, pltpu.roll(clamped, -(i * t + c0), d), x)
        if c0 < 0 or w.high[d] < g:
            first = ids[d] * t + c0 if g > 1 else c0
            pos = lax.broadcasted_iota(jnp.int32, x.shape, d) + first
            k = (pos >= 0) & (pos < w.extent[d])
            keep = k if keep is None else keep & k
    return x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))


def uses_index(apply_op: stencil.ApplyOp) -> bool:
    return any(isinstance(op, stencil.IndexOp) for op in apply_op.body.ops)


def plan_apply(
    apply_op: stencil.ApplyOp,
    result_bounds: stencil.Bounds,
    tile: Optional[Sequence[int]] = None,
    sources: Optional[Sequence] = None,
) -> tuple:
    """The tile one apply kernel runs with: ``tile`` checked against the
    (8, 128) rule, or the chooser's pick for the windows its operands'
    ``sources`` (``_reads``) need.  Raises ``KernelPlanError``."""
    shape = result_bounds.shape
    zeros = (0,) * len(shape)
    sources = sources or apply_sources(apply_op)
    reads = [r or (zeros, zeros)
             for r in _reads(apply_op, result_bounds, sources)]
    if tile is not None:
        return check_tile(shape, tile)
    return choose_tile(shape, [s for _, s in reads], n_out=len(apply_op.results),
                       leads=[l for l, _ in reads],
                       halos=[z for _, z in sources])


def build_apply_kernel(
    apply_op: stencil.ApplyOp,
    result_bounds: stencil.Bounds,
    tile: tuple,
    *,
    interpret: bool,
    name: Optional[str] = None,
    windows: Optional[list] = None,
):
    """Code-generate a pallas_call for one stencil.apply over ``tile``.

    The call takes one array per operand, whose windows ``windows``
    gives (``plan_windows``), and returns the result arrays.  Interior
    tiles run the body on the windows as fetched.  Edge tiles
    (``_edge_tiles``) first build each zero-halo window in a VMEM
    scratch buffer, under its own ``pl.when``, and then run a second
    variant of the body that reads those buffers (where a dim the grid
    does not split has a halo to build, every tile is an edge tile and
    the body reads only them).  Keeping the halo out of the body's
    branch keeps the body's arithmetic as the interior's: on the CPU,
    XLA fuses a select into the multiplies after it and rounds
    differently."""
    from repro.core.lowering import eval_apply_body  # shared evaluator

    shape = result_bounds.shape
    rank = len(shape)
    windows = windows or plan_windows(apply_op, result_bounds, tile)
    grid = grid_shape(shape, tile)
    in_specs = [
        pl.BlockSpec(tile, lambda *ids: ids) if w is None
        else _window_spec(grid, tile, w)
        for w in windows
    ]
    # window k's point 0 sits at tile-relative coordinate origins[k]
    origins = [(0,) * rank if w is None else w.origin for w in windows]
    tile_bounds = stencil.Bounds.from_shape(tile)
    n_in = len(windows)
    n_out = len(apply_op.results)
    index = uses_index(apply_op)
    edges = _edge_tiles(windows, grid)
    zero = [k for k, w in enumerate(windows) if w is not None and w.zero]
    scratch_shapes = [
        pltpu.VMEM(windows[k].shape, jnp.float32) for k in zero
    ] if edges else []
    every_tile = edges is not None and any(
        g == 1 and (lo, hi) != (0, 1) for (lo, hi), g in zip(edges, grid)
    )

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
        halos = dict(zip(zero, refs[n_in + n_out:]))
        ids = [pl.program_id(d) for d in range(rank)]

        def run(srcs):
            origin = None
            if index:  # the tile's first point in the result's coordinates
                origin = [
                    r + (i * t if g > 1 else 0)
                    for r, i, t, g in zip(result_bounds.lb, ids, tile, grid)
                ]
            vals = eval_apply_body(apply_op, [r[...] for r in srcs], origins,
                                   tile_bounds, index_origin=origin)
            for o_ref, val in zip(outs, vals):
                o_ref[...] = val

        if edges is None:
            run(ins)
            return
        edge = _is_edge(ids, edges, grid)
        if interpret:
            # with one grid step the interpreter would fold the predicate
            # and fuse the halo into the body after all
            edge = lax.optimization_barrier(edge)

        @pl.when(edge)
        def _():
            for k, h in halos.items():
                h[...] = _with_zero_halo(ins[k][...], windows[k], ids, tile, grid)

        at_edge = [halos.get(k, r) for k, r in enumerate(ins)]
        if every_tile:
            run(at_edge)
        else:
            lax.cond(edge, lambda: run(at_edge), lambda: run(ins))

    out_specs = [pl.BlockSpec(tile, lambda *ids: ids) for _ in range(n_out)]
    out_shape = [jax.ShapeDtypeStruct(shape, jnp.float32) for _ in range(n_out)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if n_out > 1 else out_specs[0],
        out_shape=out_shape if n_out > 1 else out_shape[0],
        scratch_shapes=scratch_shapes,
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
    zero: Optional[Sequence[bool]] = None,
) -> list:
    """Entry point used by the lowering's pallas backend: ``arrays[k]``
    holds operand ``k``'s logical points from ``origins[k]`` on; where
    ``zero[k]``, the operand reads as zero outside that array (the kernel
    builds the halo, so nothing is copied).  Each call is one traced
    pallas_call (counted in ``kernels.dispatch_stats``), named ``name``."""
    rb = result_bounds
    zero = zero or [False] * len(arrays)
    sources = [
        (stencil.Bounds(tuple(og), tuple(o + n for o, n in zip(og, a.shape))), z)
        for a, og, z in zip(arrays, origins, zero)
    ]
    tile = plan_apply(apply_op, rb, tile, sources)
    call = build_apply_kernel(
        apply_op, rb, tile, interpret=interpret, name=name,
        windows=plan_windows(apply_op, rb, tile, sources),
    )
    _DISPATCH.apply_calls += 1
    out = call(*[a.astype(jnp.float32) for a in arrays])
    return list(out) if isinstance(out, (tuple, list)) else [out]

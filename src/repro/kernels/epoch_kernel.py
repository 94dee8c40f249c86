"""Pallas epoch megakernel: ONE ``pl.pallas_call`` per deep-halo epoch.

Code-generates the whole region of a :class:`stencil.FusedEpochOp` — the
k-times-unrolled apply chain ``temporal-tile{k}`` produces, plus its
``comm.boundary_mask`` re-zeroing — into a single Pallas kernel body
(DESIGN.md §10).  Where ``kernels/stencil_apply.py`` dispatches one
kernel per apply (k HBM round-trips per epoch), here the k sub-steps'
intermediates are values *inside* the kernel: Mosaic keeps them in
VMEM/registers, time-buffer rotation is value rebinding, and the
shrinking redundant-boundary frames are just each sub-step's (smaller)
result bounds.

Two kernel modes, chosen from the region:

- **tiled** (whenever every escaping value shares one core bounds ``C``
  and no apply reads ``stencil.index``): a grid over ``C`` whose input
  windows carry the *accumulated* epoch halo, laid out by the same
  aligned-window rules as the per-apply kernel
  (``stencil_apply.window_shape``/``window_source``); each tile
  redundantly recomputes its neighbours' frame overlap — the standard
  overlapped-tiling time-tile trade.
- **whole-shard**: a grid-free call whose blocks are the full shard
  arrays.  Used only where that fits the VMEM budget; otherwise planning
  raises a ``KernelPlanError`` naming the sizes.

Boundary masks are computed *inside* the kernel from iotas, the tile
origin and the rank's grid coordinates, which are read outside the
kernel (``lax.axis_index`` is unavailable in a kernel body) and passed
as a small int32 vector in SMEM.  Masking is the interpreter's own
``boundary_keep`` + ``jnp.where``, so masked points match it exactly.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dialects import comm, stencil
from repro.kernels import _DISPATCH, KernelPlanError
from repro.kernels.stencil_apply import (
    VMEM_BUDGET_BYTES,
    check_tile,
    choose_tile,
    compiler_params,
    grid_shape,
    window_shape,
    window_source,
    window_spec,
)


def _region_values(fused_op: stencil.FusedEpochOp) -> list:
    """Every SSA value live in the region: block args + member results."""
    vals = list(fused_op.body.args)
    for op in fused_op.body.ops:
        vals.extend(op.results)
    return vals


def _uses_index(fused_op: stencil.FusedEpochOp) -> bool:
    return any(
        isinstance(inner, stencil.IndexOp)
        for op in fused_op.body.ops
        if isinstance(op, stencil.ApplyOp)
        for inner in op.body.ops
    )


def _emit_region(fused_op, inputs, bounds_of, keep_of) -> list:
    """Evaluate the fused region over VMEM values.  ``bounds_of`` maps a
    region value to the bounds its array covers — actual logical bounds in
    whole-shard mode, tile-relative bounds in tiled mode; ``keep_of(op,
    shape)`` is a boundary_mask op's keep-mask (``None``: keep all)."""
    from repro.core.lowering import eval_apply_body

    env = dict(zip(fused_op.body.args, inputs))
    for op in fused_op.body.ops:
        if isinstance(op, stencil.ApplyOp):
            arrays = [env[o] for o in op.operands]
            origins = [bounds_of(o).lb for o in op.operands]
            outs = eval_apply_body(op, arrays, origins, bounds_of(op.results[0]))
            for res, val in zip(op.results, outs):
                env[res] = val
        elif isinstance(op, comm.BoundaryMaskOp):
            x = env[op.temp]
            keep = keep_of(op, tuple(x.shape))
            env[op.results[0]] = (
                x if keep is None else jnp.where(keep, x, jnp.zeros_like(x))
            )
        elif isinstance(op, stencil.FusedYieldOp):
            return [env[o] for o in op.operands]
        else:  # pragma: no cover - FusedEpochOp.verify_ rejects these
            raise NotImplementedError(f"fused region op {op.name}")
    raise AssertionError("fused_epoch region missing stencil.fused_yield")


def _rel_bounds(b: stencil.Bounds, core: stencil.Bounds, tile: tuple):
    """Tile-relative bounds: where value ``b`` sits around one core tile.
    The window a tile reads/computes of ``b`` is the tile grown by the
    value's overhang beyond the core: shape = tile + (b.shape - core.shape),
    starting ``core.lb - b.lb`` before the tile origin."""
    return stencil.Bounds(
        tuple(bl - cl for bl, cl in zip(b.lb, core.lb)),
        tuple(t + (bu - cu) for t, bu, cu in zip(tile, b.ub, core.ub)),
    )


def _span(b: stencil.Bounds, core: stencil.Bounds) -> tuple:
    return tuple(bs - cs for bs, cs in zip(b.shape, core.shape))


def plan_epoch(
    fused_op: stencil.FusedEpochOp, tile: Optional[Sequence[int]] = None
) -> Optional[tuple]:
    """The tile of the tiled mode, or ``None`` for whole-shard mode (only
    when it fits VMEM).  Raises ``KernelPlanError`` naming the sizes."""
    escapes = [r.type.bounds for r in fused_op.results]
    core = escapes[0]
    if all(b == core for b in escapes) and not _uses_index(fused_op):
        # windows: the externals, double-buffered; the intermediates
        # (shrinking frames) count among the chooser's tile temporaries
        spans = [_span(a.type.bounds, core) for a in fused_op.body.args]
        if tile is not None:
            return check_tile(core.shape, tile)
        return choose_tile(core.shape, spans, n_out=len(escapes))
    # whole-shard: every region value resident at once, no double-buffering
    numel = sum(
        math.prod(v.type.bounds.shape)
        for v in _region_values(fused_op)
        if isinstance(v.type, stencil.TempType)
    )
    need = 4 * numel
    if need > VMEM_BUDGET_BYTES:
        shapes = [tuple(a.type.bounds.shape) for a in fused_op.body.args]
        raise KernelPlanError(
            f"fused epoch cannot be tiled (escapes {[tuple(b.shape) for b in escapes]} "
            f"differ or it reads stencil.index) and its whole shard needs "
            f"{need} B of VMEM over the {VMEM_BUDGET_BYTES} B budget "
            f"(inputs {shapes})"
        )
    return None


def build_epoch_kernel(
    fused_op: stencil.FusedEpochOp,
    keep_fn,
    tile: Optional[tuple],
    *,
    interpret: bool,
    name: Optional[str] = None,
):
    """Code-generate one pallas_call for a whole fused epoch, named
    ``name``.

    Returns a callable taking ``(coords, *sources)``: the rank's int32
    grid coordinate per dim, then one array per op operand (re-based with
    ``window_source`` in tiled mode).  ``keep_fn(op, shape, coords,
    shift)`` builds a boundary_mask keep-mask for a value whose first
    point sits ``shift`` points past its own bounds' origin."""
    escape_bounds = [r.type.bounds for r in fused_op.results]
    rank = escape_bounds[0].rank
    n_in = len(fused_op.operands)
    n_out = len(escape_bounds)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    if tile is None:
        # -- whole-shard mode: grid-free, blocks are the full arrays -----
        def kernel(coords_ref, *refs):
            coords = [coords_ref[d] for d in range(rank)]
            inputs = [r[...] for r in refs[:n_in]]
            outs = _emit_region(
                fused_op, inputs, lambda v: v.type.bounds,
                lambda op, shape: keep_fn(op, shape, coords, (0,) * rank),
            )
            for o_ref, val in zip(refs[n_in:], outs):
                o_ref[...] = val

        out_shape = [
            jax.ShapeDtypeStruct(b.shape, jnp.float32) for b in escape_bounds
        ]
        vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kernel,
            in_specs=[smem] + [vmem] * n_in,
            out_specs=[vmem] * n_out if n_out > 1 else vmem,
            out_shape=out_shape if n_out > 1 else out_shape[0],
            compiler_params=compiler_params(0),
            interpret=interpret,
            name=name,
        )

    # -- tiled mode: grid over core, aligned epoch-halo windows -------------
    core = escape_bounds[0]
    grid = grid_shape(core.shape, tile)
    rel = {
        v: _rel_bounds(v.type.bounds, core, tile)
        for v in _region_values(fused_op)
        if isinstance(v.type, stencil.TempType)
    }
    in_specs = [smem] + [
        window_spec(grid, tile, window_shape(tile, _span(a.type.bounds, core)))
        for a in fused_op.body.args
    ]

    def kernel(coords_ref, *refs):
        coords = [coords_ref[d] for d in range(rank)]
        origin = [pl.program_id(d) * t for d, t in enumerate(tile)]
        inputs = [r[...] for r in refs[:n_in]]
        # escapes all have bounds == core, so rel(escape) == [0, tile):
        # each yielded value IS exactly this tile's output block
        outs = _emit_region(
            fused_op, inputs, lambda v: rel[v],
            lambda op, shape: keep_fn(op, shape, coords, origin),
        )
        for o_ref, val in zip(refs[n_in:], outs):
            o_ref[...] = val

    out_specs = [pl.BlockSpec(tile, lambda *ids: ids) for _ in range(n_out)]
    out_shape = [
        jax.ShapeDtypeStruct(core.shape, jnp.float32) for _ in range(n_out)
    ]
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


def run_epoch_pallas(
    fused_op: stencil.FusedEpochOp,
    arrays: Sequence,
    coords,
    keep_fn,
    tile: Optional[tuple] = None,
    *,
    interpret: bool,
    name: Optional[str] = None,
) -> list:
    """Entry point used by the lowering's pallas backend: one traced
    pallas_call per fused epoch (counted in ``kernels.dispatch_stats``),
    named ``name``.  ``arrays[k]`` covers operand ``k``'s bounds;
    ``coords`` is the rank's int32 grid coordinate per dim."""
    if not fused_op.results:
        return []
    tile = plan_epoch(fused_op, tile)
    sources = [a.astype(jnp.float32) for a in arrays]
    if tile is not None:
        core = fused_op.results[0].type.bounds
        sources = [
            window_source(
                s, (0,) * core.rank, core.shape, tile,
                window_shape(tile, _span(a.type.bounds, core)),
            )
            for s, a in zip(sources, fused_op.body.args)
        ]
    call = build_epoch_kernel(fused_op, keep_fn, tile, interpret=interpret,
                              name=name)
    _DISPATCH.fused_epoch_calls += 1
    out = call(jnp.asarray(coords, jnp.int32), *sources)
    return list(out) if isinstance(out, (tuple, list)) else [out]

"""Executing the comm-level IR as JAX (paper secs. 4.3 & 5).

The paper lowers ``stencil`` → ``dmp`` → ``mpi`` → LLVM calls.  Here the
final target is XLA: the rank-local function — **after** the canonical
dmp→comm lowering (``core/passes/lower_comm.py``), so it contains comm
ops, never ``dmp.swap`` — is *interpreted into a JAX trace* (every IR op
becomes jnp/lax primitives), the exchanges become ``lax.ppermute`` inside
``jax.shard_map``, and XLA compiles the result.  Two compute backends
share the interpreter's body evaluator:

- ``jnp``    — shifted ``lax.slice`` reads, fused by XLA (the reference);
- ``pallas`` — each ``stencil.apply`` is code-generated into a Pallas TPU
  kernel with explicit BlockSpec VMEM tiling (``repro.kernels``), the TPU
  analogue of the paper's GPU/FPGA backends.

Halo-exchange execution model (DESIGN.md §2) — one op-dispatch level,
one path:

- ``comm.halo_pad``       → boundary-condition pad (zeros, or wrap for
                            periodic dims that are not decomposed); a pad
                            whose halo can only hold zeros and that only
                            Pallas kernels read lowers to nothing
                            (``pad_folds``): the kernels build the halo;
- ``comm.exchange_start`` → extract the send rectangle, ``lax.ppermute``
                            it toward ``-shift`` (pairs built by the
                            shared ``comm.permute_pairs``);
- ``comm.wait``           → insert received patches
                            (``lax.dynamic_update_slice``);
- ``stencil.combine``     → reassemble split (overlapped) applies.

Comm/compute overlap is *not* a runtime special case: the
``split_overlapped_applies`` pass expresses it in the IR, and the
interpreter just executes what it sees.  Grid axes of size 1 run a local
emulation (self-exchange for periodic wrap, no-op for zero BC), so the
single-device reference path runs the same comm-level program unchanged.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ir
from repro.core.dialects import comm, dmp, stencil

# --------------------------------------------------------------------------
# Shared point-function evaluator
# --------------------------------------------------------------------------


def eval_apply_body(
    apply_op: stencil.ApplyOp,
    operand_arrays: Sequence[Any],
    operand_origins: Sequence[tuple],
    result_bounds: stencil.Bounds,
    index_origin: Optional[Sequence] = None,
) -> list:
    """Evaluate an apply's point function vectorized over ``result_bounds``.

    ``operand_arrays[k]`` covers logical coords starting at
    ``operand_origins[k]``; an access at offset ``o`` of operand ``k``
    becomes a static slice — identical code runs on jnp arrays (XLA
    backend) and on VMEM blocks inside a Pallas kernel.  ``stencil.index``
    along ``d`` counts from ``index_origin[d]`` (default: the low bound of
    ``result_bounds``); a kernel over one tile passes the tile's origin
    in the grid, which may be traced.
    """
    rb = result_bounds
    shape = rb.shape
    env: dict[ir.SSAValue, Any] = {}

    def operand_slice(k: int, offset: tuple):
        start = tuple(
            rl + o - og for rl, o, og in zip(rb.lb, offset, operand_origins[k])
        )
        arr = operand_arrays[k]
        return lax.slice(arr, start, tuple(s + n for s, n in zip(start, shape)))

    for op in apply_op.body.ops:
        if isinstance(op, stencil.AccessOp):
            env[op.results[0]] = operand_slice(op.temp.index, op.offset)
        elif isinstance(op, stencil.IndexOp):
            d = op.dim
            first = rb.lb[d] if index_origin is None else index_origin[d]
            io = lax.broadcasted_iota(jnp.int32, shape, d) + first
            env[op.results[0]] = io.astype(jnp.float32)
        elif isinstance(op, ir.ConstantOp):
            env[op.results[0]] = jnp.float32(op.value)
        elif isinstance(op, ir.AddOp):
            env[op.results[0]] = env[op.operands[0]] + env[op.operands[1]]
        elif isinstance(op, ir.SubOp):
            env[op.results[0]] = env[op.operands[0]] - env[op.operands[1]]
        elif isinstance(op, ir.MulOp):
            env[op.results[0]] = env[op.operands[0]] * env[op.operands[1]]
        elif isinstance(op, ir.DivOp):
            env[op.results[0]] = env[op.operands[0]] / env[op.operands[1]]
        elif isinstance(op, ir.NegOp):
            env[op.results[0]] = -env[op.operands[0]]
        elif isinstance(op, ir.AbsOp):
            env[op.results[0]] = jnp.abs(env[op.operands[0]])
        elif isinstance(op, ir.SqrtOp):
            env[op.results[0]] = jnp.sqrt(env[op.operands[0]])
        elif isinstance(op, ir.ExpOp):
            env[op.results[0]] = jnp.exp(env[op.operands[0]])
        elif isinstance(op, ir.SelectGeZeroOp):
            p, a, b = (env[o] for o in op.operands)
            env[op.results[0]] = jnp.where(p >= 0, a, b)
        elif isinstance(op, stencil.StencilReturnOp):
            return [
                jnp.broadcast_to(env[o], shape)
                for o in op.operands
            ]
        else:
            raise NotImplementedError(f"apply body op {op.name}")
    raise AssertionError("apply body missing stencil.return")


# --------------------------------------------------------------------------
# Boundary-condition fill
# --------------------------------------------------------------------------


def _pad_with_bc(x, lo: tuple, hi: tuple, grid: dmp.GridAttr, boundary: str):
    """Grow ``x`` by halo widths; wrap-fill periodic *undecomposed* dims
    locally, everything else zeros (decomposed dims are filled by
    exchanges; zero-BC edges stay zero because non-cyclic permutes leave
    non-receivers untouched)."""
    rank = x.ndim
    if boundary == "periodic":
        wrap_dims = [
            d
            for d in range(rank)
            if grid.axis_of_dim(d) is None and (lo[d] or hi[d])
        ]
        if wrap_dims:
            pad_widths = [
                (lo[d], hi[d]) if d in wrap_dims else (0, 0) for d in range(rank)
            ]
            x = jnp.pad(x, pad_widths, mode="wrap")
        zero_widths = [
            (0, 0) if d in wrap_dims else (lo[d], hi[d]) for d in range(rank)
        ]
        if any(w != (0, 0) for w in zero_widths):
            x = jnp.pad(x, zero_widths)
        return x
    pad_widths = [(lo[d], hi[d]) for d in range(rank)]
    if any(w != (0, 0) for w in pad_widths):
        x = jnp.pad(x, pad_widths)
    return x


# --------------------------------------------------------------------------
# Function interpreter — one op-dispatch level, comm ops only
# --------------------------------------------------------------------------

def scope(op: ir.Operation) -> str:
    """The ``jax.named_scope`` an IR op executes under: its own IR name,
    and for a split apply its part after a dot
    (``stencil.apply.interior``).  Every device op the op emits carries
    it in its ``op_name`` metadata, and the profiler in its ``tf_op``
    stat; no ``:``, which ``tf_op`` uses as a separator."""
    part = op.attributes.get("part")
    return op.name if part is None else f"{op.name}.{part.value}"


def runs_pallas(op: stencil.ApplyOp, backend: str) -> bool:
    """Whether an apply lowers to the Pallas kernel: every apply of the
    pallas backend but a split's thin boundary frames, which go through
    the jnp evaluator (identical elementwise arithmetic, no per-slab
    kernel launch)."""
    part = op.attributes.get("part")
    return backend == "pallas" and (part is None or part.value == "interior")


def halo_source(value: ir.SSAValue) -> Optional[ir.SSAValue]:
    """The ``comm.halo_pad`` result ``value`` is, through any
    ``comm.wait``s, or ``None``."""
    while isinstance(value, ir.OpResult) and isinstance(value.op, comm.WaitOp):
        value = value.op.temp
    if isinstance(value, ir.OpResult) and isinstance(value.op, comm.HaloPadOp):
        return value
    return None


def pad_folds(op: comm.HaloPadOp, backend: str) -> bool:
    """Whether a ``comm.halo_pad`` lowers to nothing, its halo built by
    the kernels that read it: the boundary is zero; every dim it pads is
    undecomposed or on a grid axis of size 1, so its exchanges deliver
    only zeros; and every reader of it, through ``comm.wait``s, is a
    Pallas apply, a wait or an exchange start.  A periodic halo holds
    wrapped data, and a decomposed dim's halo received rows: those keep
    the copy.  ``CompiledStencil.kernel_dispatches["halo_pad_copies"]``
    counts the pads this leaves."""
    if op.boundary != "zero":
        return False
    grid: dmp.GridAttr = op.attributes["grid"]
    ib, ob = op.temp.type.bounds, op.results[0].type.bounds
    for d in range(ib.rank):
        gax = grid.axis_of_dim(d)
        padded = ib.lb[d] != ob.lb[d] or ib.ub[d] != ob.ub[d]
        if padded and gax is not None and grid.shape[gax] > 1:
            return False
    pending = [op.results[0]]
    while pending:
        for use in pending.pop().uses:
            reader = use.operation
            if isinstance(reader, comm.WaitOp):
                pending.append(reader.results[0])
            elif isinstance(reader, stencil.ApplyOp):
                if not runs_pallas(reader, backend):
                    return False
            elif not isinstance(reader, comm.ExchangeStartOp):
                return False
    return True


class StencilInterpreter:
    """Interprets a rank-local, comm-lowered stencil function into a JAX
    computation.

    Calling convention: positional arrays for every *field* argument of the
    function; returns the updated arrays of every stored-to field, in
    first-store order.  ``dmp.swap`` is rejected — run the dmp→comm
    pipeline (``lower-comm``) first.
    """

    def __init__(
        self,
        func: ir.FuncOp,
        axis_sizes: dict[str, int],
        distributed: bool,
        backend: str = "jnp",
        pallas_interpret: Optional[bool] = None,
        pallas_tile: Optional[tuple] = None,
        name: str = "step",
    ) -> None:
        assert backend in ("jnp", "pallas")
        if backend == "pallas" and pallas_interpret is None:
            raise ValueError(
                "backend='pallas' needs pallas_interpret resolved by the "
                "Target (True only for the CPU interpret oracle)"
            )
        self.func = func
        # what ``jax.jit`` names the step, and so the root of every
        # device op's scope path (``jit(<name>)/stencil.apply/...``)
        self.__name__ = name
        self.axis_sizes = dict(axis_sizes)
        self.distributed = distributed
        self.backend = backend
        self.pallas_interpret = pallas_interpret
        self.pallas_tile = pallas_tile
        # halo_pad results that lower to nothing (``pad_folds``)
        self.folded = {
            op.results[0] for op in func.body.ops
            if isinstance(op, comm.HaloPadOp) and pad_folds(op, backend)
        }
        if backend == "pallas":
            self.plan_kernels()
        self.output_fields: list[ir.SSAValue] = []
        for op in func.body.ops:
            if isinstance(op, stencil.StoreOp) and op.field not in self.output_fields:
                self.output_fields.append(op.field)

    # -- public --------------------------------------------------------
    def __call__(self, *arrays):
        args = [a for a in self.func.body.args]
        fields = [a for a in args if isinstance(a.type, stencil.FieldType)]
        assert len(arrays) == len(fields), (
            f"expected {len(fields)} field arrays, got {len(arrays)}"
        )
        env: dict[ir.SSAValue, Any] = {}
        field_state: dict[ir.SSAValue, Any] = {}
        for arg, arr in zip(fields, arrays):
            expect = arg.type.bounds.shape
            assert tuple(arr.shape) == tuple(expect), (
                f"field {arg.name_hint}: array shape {arr.shape} != local "
                f"bounds shape {expect}"
            )
            field_state[arg] = arr

        for op in self.func.body.ops:
            self._exec(op, env, field_state)
        return tuple(field_state[f] for f in self.output_fields)

    # -- op execution ---------------------------------------------------
    def _exec(self, op: ir.Operation, env, field_state) -> None:
        with jax.named_scope(scope(op)):
            self._exec_op(op, env, field_state)

    def _exec_op(self, op: ir.Operation, env, field_state) -> None:
        if isinstance(op, stencil.LoadOp):
            env[op.results[0]] = field_state[op.field]
        elif isinstance(op, stencil.ApplyOp):
            rb = op.result_bounds
            arrays = [env[o] for o in op.operands]
            outs = self._apply_backend(op, arrays, rb)
            for res, arr in zip(op.results, outs):
                env[res] = arr
        elif isinstance(op, stencil.CombineOp):
            env[op.results[0]] = self._exec_combine(op, env)
        elif isinstance(op, stencil.StoreOp):
            temp = env[op.temp]
            field_arr = field_state[op.field]
            tb: stencil.Bounds = op.temp.type.bounds
            fb: stencil.Bounds = op.field.type.bounds
            sb: stencil.Bounds = op.bounds
            start = tuple(s - t for s, t in zip(sb.lb, tb.lb))
            patch = lax.slice(
                temp, start, tuple(s + n for s, n in zip(start, sb.shape))
            )
            dst = tuple(s - f for s, f in zip(sb.lb, fb.lb))
            if sb == fb:
                field_state[op.field] = patch
            else:
                field_state[op.field] = lax.dynamic_update_slice(
                    field_arr, patch, dst
                )
        elif isinstance(op, comm.HaloPadOp):
            x = env[op.operands[0]]
            env[op.results[0]] = (
                x if op.results[0] in self.folded else _exec_halo_pad(op, x)
            )
        elif (isinstance(op, (comm.ExchangeStartOp, comm.WaitOp))
              and halo_source(op.operands[0]) in self.folded):
            # an exchange or wait on a folded pad: its halo is all zeros
            if isinstance(op, comm.WaitOp):
                env[op.results[0]] = env[op.temp]
        elif isinstance(op, comm.ExchangeStartOp):
            env[op.results[0]] = self._exec_comm_start(op, env[op.temp])
        elif isinstance(op, comm.WaitOp):
            self._exec_comm_wait(op, env)
        elif isinstance(op, comm.BoundaryMaskOp):
            env[op.results[0]] = self._exec_boundary_mask(op, env[op.temp])
        elif isinstance(op, stencil.FusedEpochOp):
            self._exec_fused_epoch(op, env)
        elif isinstance(op, comm.AllReduceOp):
            v = env[op.operands[0]]
            red = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin}[op.op]
            env[op.results[0]] = (
                red(v, tuple(op.axes)) if self.distributed else v
            )
        elif isinstance(op, ir.ReturnOp):
            pass
        elif isinstance(op, dmp.SwapOp):
            raise NotImplementedError(
                "dmp.swap reached the interpreter — run the canonical "
                "dmp→comm pipeline (lower-comm pass) before execution"
            )
        else:
            raise NotImplementedError(f"function-level op {op.name}")

    # -- apply backends -------------------------------------------------
    def _apply_tile(self, op: stencil.ApplyOp) -> Optional[tuple]:
        """The user tile for one pallas apply, or ``None`` (auto-tile): a
        split interior (or an epoch-tiled apply, whose grown frame changes
        the shape per step) may not fit the user tile; unsplit applies
        keep the kernel's loud tile check so a misconfigured pallas_tile
        stays diagnosable."""
        from repro.kernels.stencil_apply import is_legal_tile

        tile = self.pallas_tile
        if (
            tile is not None
            and ("part" in op.attributes or "epoch_step" in op.attributes)
            and not is_legal_tile(op.result_bounds.shape, tile)
        ):
            return None
        return tile

    def plan_kernels(self) -> None:
        """Lay out every Pallas kernel of the function without tracing:
        raises ``KernelPlanError`` (naming the sizes) for a tile or VMEM
        budget the TPU cannot take — at construction, not first call."""
        from repro.kernels.epoch_kernel import plan_epoch
        from repro.kernels.stencil_apply import plan_apply

        for op in self.func.body.ops:
            if isinstance(op, stencil.ApplyOp) and runs_pallas(op, self.backend):
                plan_apply(op, op.result_bounds, self._apply_tile(op),
                           self._sources(op))
            elif isinstance(op, stencil.FusedEpochOp) and op.results:
                plan_epoch(op, self.pallas_tile)

    def _sources(self, op: stencil.ApplyOp) -> list:
        """Per operand, the bounds of the array it is read from and
        whether it reads as zero outside them: a folded pad's operand
        (``pad_folds``) is passed unpadded, with a zero halo."""
        out = []
        for v in op.operands:
            pad = halo_source(v)
            if pad in self.folded:
                out.append((pad.op.temp.type.bounds, True))
            else:
                out.append((v.type.bounds, False))
        return out

    def _apply_backend(self, op, arrays, rb):
        sources = self._sources(op)
        if runs_pallas(op, self.backend):
            from repro.kernels.stencil_apply import run_apply_pallas

            return run_apply_pallas(
                op,
                arrays,
                [b.lb for b, _ in sources],
                rb,
                tile=self._apply_tile(op),
                interpret=self.pallas_interpret,
                name=scope(op),
                zero=[z for _, z in sources],
            )
        return eval_apply_body(op, arrays, [b.lb for b, _ in sources], rb)

    def _exec_combine(self, op: stencil.CombineOp, env):
        rb = op.result_bounds
        parts = [env[o] for o in op.operands]
        out = jnp.zeros(rb.shape, parts[0].dtype)
        for val, part in zip(op.operands, parts):
            idx = tuple(l - b for l, b in zip(val.type.bounds.lb, rb.lb))
            out = lax.dynamic_update_slice(out, part, idx)
        return out

    # -- comm ops (the mpi-level execution path) -------------------------
    def _exec_comm_start(self, op: comm.ExchangeStartOp, x):
        origin = op.temp.type.bounds.lb
        idx = tuple(o - g for o, g in zip(op.send_offset, origin))
        patch = lax.slice(
            x, idx, tuple(i + s for i, s in zip(idx, op.size))
        )
        periodic = bool(op.attributes.get("periodic", ir.IntAttr(0)).value)
        if self.distributed:
            axis_arg, pairs = comm.permute_pairs(
                op.axis_shifts, self.axis_sizes, periodic
            )
            return lax.ppermute(patch, axis_arg, pairs)
        # local emulation: every grid axis has size 1
        return patch if periodic else jnp.zeros_like(patch)

    def _grid_coords(self, grid: dmp.GridAttr, rank: int) -> list:
        """This rank's coordinate along the grid axis of every array dim
        (0 where the dim is undecomposed or the program runs locally)."""
        coords = []
        for d in range(rank):
            gax = grid.axis_of_dim(d)
            if self.distributed and gax is not None and grid.shape[gax] > 1:
                coords.append(lax.axis_index(grid.axis_names[gax]))
            else:
                coords.append(0)
        return coords

    def _exec_boundary_mask(self, op: comm.BoundaryMaskOp, x):
        """Zero every point outside the physical (global) domain — the
        temporal-tiling analogue of the zero-BC halo_pad, applied to
        redundantly-computed epoch intermediates."""
        rank = x.ndim
        keep = boundary_keep(
            op, tuple(x.shape), self._grid_coords(op.grid, rank), (0,) * rank
        )
        if keep is None:
            return x
        return jnp.where(keep, x, jnp.zeros_like(x))

    def _exec_fused_epoch(self, op: stencil.FusedEpochOp, env) -> None:
        """Route a fused epoch through the megakernel.  The rank's grid
        coordinates are read here — outside the kernel, where
        ``lax.axis_index`` exists — and the kernel rebuilds each
        boundary keep-mask from them."""
        from repro.kernels.epoch_kernel import run_epoch_pallas

        if self.backend != "pallas":
            raise NotImplementedError(
                "stencil.fused_epoch lowers only to the pallas backend"
            )
        rank = len(op.operands[0].type.bounds.lb)
        masks = [i for i in op.body.ops if isinstance(i, comm.BoundaryMaskOp)]
        coords = self._grid_coords(masks[0].grid, rank) if masks else [0] * rank
        outs = run_epoch_pallas(
            op,
            [env[o] for o in op.operands],
            coords,
            boundary_keep,
            tile=self.pallas_tile,
            interpret=self.pallas_interpret,
            name=scope(op),
        )
        for res, arr in zip(op.results, outs):
            env[res] = arr

    def _exec_comm_wait(self, op: comm.WaitOp, env) -> None:
        x = env[op.temp]
        origin = op.temp.type.bounds.lb
        for p in op.patches:
            patch = env[p]
            rect: stencil.Bounds = p.type.bounds
            idx = tuple(o - g for o, g in zip(rect.lb, origin))
            x = lax.dynamic_update_slice(x, patch, idx)
        env[op.results[0]] = x


def boundary_keep(op: comm.BoundaryMaskOp, shape: tuple, coords, shift):
    """Boolean keep-mask (True = inside the physical global domain) for a
    boundary_mask op over an array of ``shape`` whose first point sits
    ``shift`` points past the op's value bounds origin, or ``None`` when
    every point is inside.  ``coords[d]`` is the rank's coordinate along
    the grid axis of dim ``d``.  Communication-free, so it runs both in
    the interpreter and inside the fused-epoch kernel."""
    vb: stencil.Bounds = op.temp.type.bounds
    core: stencil.Bounds = op.core
    grid: dmp.GridAttr = op.grid
    keep = None
    for d in range(vb.rank):
        if core.lb[d] <= vb.lb[d] and vb.ub[d] <= core.ub[d]:
            continue  # no points outside this shard's core along d
        gax = grid.axis_of_dim(d)
        n = core.ub[d] - core.lb[d]
        grid_extent = grid.shape[gax] if gax is not None else 1
        pos = lax.broadcasted_iota(jnp.int32, shape, d) + (
            shift[d] + jnp.int32(vb.lb[d] - core.lb[d])
        )
        glob = coords[d] * n + pos
        k = (glob >= 0) & (glob < grid_extent * n)
        keep = k if keep is None else keep & k
    return keep


def _exec_halo_pad(op: comm.HaloPadOp, x):
    ib: stencil.Bounds = op.operands[0].type.bounds
    ob: stencil.Bounds = op.results[0].type.bounds
    lo = tuple(i - o for i, o in zip(ib.lb, ob.lb))
    hi = tuple(o - i for o, i in zip(ob.ub, ib.ub))
    return _pad_with_bc(
        x, lo, hi, op.attributes["grid"], op.attributes["boundary"].value
    )


def run_func_dataflow(
    func: ir.FuncOp,
    inputs: Sequence[Any],
    axis_sizes: dict[str, int],
    distributed: bool,
) -> tuple:
    """Execute a *value-returning* comm-level function (temp args in,
    ``func.return`` values out) — the entry point ``repro.dist`` uses to
    run its sequence-halo exchanges through the one shared executor."""
    interp = StencilInterpreter(
        func, axis_sizes=axis_sizes, distributed=distributed
    )
    env: dict[ir.SSAValue, Any] = dict(zip(func.body.args, inputs))
    for op in func.body.ops:
        if isinstance(op, ir.ReturnOp):
            return tuple(env[o] for o in op.operands)
        interp._exec(op, env, {})
    raise AssertionError(f"{func.sym_name}: missing func.return")


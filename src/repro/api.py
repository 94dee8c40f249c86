"""One compile surface: ``Program`` / ``Target`` / ``compile``.

The paper's central claim is that three stencil DSLs share one
compilation stack; this module is the one *API* they share, following
MLIR's module → pass-pipeline → target structure and Devito's
Operator-as-cached-artifact design:

    prog   = oec_like.ProgramBuilder(...).finish(boundary="periodic")
    target = Target(mesh=mesh, strategy=make_strategy_2d((4, 2)))
    step   = compile(prog, target)      # CompiledStencil
    u1 = step(u0, out0)                 # global arrays in / out
    step.pipeline_report                # per-pass timings
    step.local_ir                       # the comm-lowered rank-local IR
    step.cost()                         # roofline terms (launch/roofline)

- ``Program``  — the frontend-neutral IR artifact every frontend
  produces: a verified ``func.func`` of stencil ops plus metadata
  (boundary condition, field names, rank) and a stable fingerprint.
- ``Target``   — a frozen description of *where and how* to compile:
  device mesh, decomposition strategy, compute backend, pass-pipeline
  spec, pallas/donation knobs.  Mismatches (unknown backend, strategy
  grid vs mesh axes) are rejected at construction, not deep inside
  lowering.
- ``compile(program, target) -> CompiledStencil`` — runs the shared
  pass pipeline and wraps the interpreter in ``shard_map``/``jit``.
  Results are cached process-wide on ``(program.fingerprint,
  target.fingerprint)``, so sweep loops, the tuner and the serve engine
  never re-run passes or re-trace for a program + target they have
  already compiled.  ``cache_stats()`` reports
  hits/misses; ``clear_cache()`` resets.

"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ir
from repro.core.dialects import comm, stencil
from repro.core.lowering import StencilInterpreter, pad_folds, runs_pallas
from repro.obs import trace as _obs
from repro.core.passes import (
    PassManager,
    PipelineContext,
    build_pipeline,
)
from repro.core.passes.decompose import SlicingStrategy


class TargetError(ValueError):
    """A target description that can never compile (bad backend, strategy
    grid not matching the mesh, decomposed dim outside the program rank)."""


# --------------------------------------------------------------------------
# Program — the frontend-neutral IR artifact
# --------------------------------------------------------------------------


class Program:
    """A verified stencil program plus the metadata compilation needs.

    All three frontends produce this: ``devito_like.Operator.program``,
    ``psyclone_like.recognize(...)``, ``oec_like.ProgramBuilder.finish()``.
    The fingerprint is taken at construction (stable textual IR +
    boundary), so mutate the ``FuncOp`` *before* wrapping it.

    ``static_fields`` names the fields every step reads and none writes
    (velocities, masks, coefficients): the time loop's state holds them
    first, and hands them to each step unchanged (``rotate``).
    """

    def __init__(
        self,
        func: ir.FuncOp,
        boundary: str = "zero",
        field_names: Optional[Sequence[str]] = None,
        name: Optional[str] = None,
        static_fields: Sequence[str] = (),
    ) -> None:
        if boundary not in ("zero", "periodic"):
            raise ValueError(f"unknown boundary condition {boundary!r}")
        ir.verify_module(func)
        self.func = func
        self.boundary = boundary
        self.name = name or func.sym_name
        self.field_args = [
            a for a in func.body.args if isinstance(a.type, stencil.FieldType)
        ]
        self.field_names = tuple(
            field_names
            if field_names is not None
            else (f"field{i}" for i in range(len(self.field_args)))
        )
        if len(self.field_names) != len(self.field_args):
            raise ValueError(
                f"{len(self.field_names)} field names for "
                f"{len(self.field_args)} field arguments"
            )
        self.static_fields = tuple(static_fields)
        stored = set(_stored_fields(func))
        for n in self.static_fields:
            if n not in self.field_names:
                raise ValueError(f"static field {n!r} is not a field of {self.name!r}")
            if self.field_args[self.field_names.index(n)] in stored:
                raise ValueError(f"static field {n!r} of {self.name!r} is stored to")
        # metadata is part of the identity: a cache hit must hand back an
        # artifact whose .program matches in name/fields, not just in IR
        self._salt = (
            f"boundary={boundary}",
            f"name={self.name}",
            "fields=" + ",".join(self.field_names),
        ) + (("static=" + ",".join(self.static_fields),) if self.static_fields else ())
        self.fingerprint = ir.fingerprint(func, *self._salt)

    @property
    def rank(self) -> int:
        return self.field_args[0].type.bounds.rank if self.field_args else 0

    @property
    def output_fields(self) -> list:
        """Field arguments that are stored to, in first-store order."""
        return _stored_fields(self.func)

    def ir_text(self) -> str:
        """The stable textual IR (what the fingerprint hashes)."""
        return ir.print_module(self.func)

    def global_zeros(self, dtype=jnp.float32) -> list:
        return [jnp.zeros(f.type.bounds.shape, dtype) for f in self.field_args]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"Program({self.name!r}, rank={self.rank}, "
            f"fields={list(self.field_names)}, boundary={self.boundary!r}, "
            f"fingerprint={self.fingerprint})"
        )


# --------------------------------------------------------------------------
# Target — where and how to compile
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Target:
    """Frozen bundle of everything 'backend' about a compile.

    ``mesh``/``strategy`` describe the decomposition (both ``None`` =
    single device); ``backend`` picks the compute lowering (``None``
    resolves to the Pallas kernels on a TPU, jnp elsewhere); ``pipeline``
    is an explicit pass spec (DESIGN.md §2 grammar) overriding the
    ``fuse``/``cse``/``diagonal``/``overlap`` flags; the remaining knobs
    control pallas codegen and jit wrapping.  Validation happens here, at
    construction — a constructed Target either compiles or exposes a
    program-shape mismatch (checked against the program in ``compile``).
    """

    mesh: Optional[Mesh] = None
    strategy: Optional[SlicingStrategy] = None
    # "jnp" | "pallas"; None resolves via kernels.default_backend(): the
    # Pallas kernels on a TPU, the jnp lowering (XLA fusions) elsewhere
    backend: Optional[str] = None
    pipeline: Optional[str] = None
    fuse: bool = True
    cse: bool = True
    overlap: bool = False
    diagonal: bool = False
    # Deep-halo temporal tiling (temporal-tile pass): exchange a depth-k
    # halo once, then run k stencil steps with redundant boundary compute
    # before the next exchange.  One call of the compiled artifact is one
    # *epoch* of k time steps; ``time_loop`` keeps counting single steps
    # and iterates in epochs.  1 = one exchange per step (the baseline).
    exchange_every: int = 1
    # Fuse each epoch's apply chain into ONE Pallas megakernel
    # (fuse-epoch-kernel pass + kernels/epoch_kernel.py): the k sub-steps'
    # intermediates stay in fast memory, one pallas_call dispatch per
    # epoch instead of k.  Requires backend="pallas"; incompatible with
    # overlap (split frame applies cannot fuse into one kernel).
    fused_epoch: bool = False
    # Slot mesh axis (serving/ensemble batching): name of a mesh axis that
    # carries a leading *batch* ("slot") dimension instead of an array
    # dimension.  The compiled step then takes arrays of shape
    # ``[B, *field_shape]`` and runs as ONE ``shard_map`` over
    # ``(slot, *spatial)`` — the batch dim is sharded over the slot axis
    # and each device block vmaps the rank-local stencil over its rows,
    # so halo exchanges stay per-slot-correct (collectives only ever run
    # over the spatial axes).  ``B`` must divide by the slot-axis size at
    # call time.  Factored out of the device inventory with
    # ``pooled_target`` / ``dist.sharding.factor_slot_mesh``; this is how
    # the serve engine dispatches a whole distributed slot pool as one
    # pooled call (DESIGN.md §9).
    slot_axis: Optional[str] = None
    # None resolves via kernels.default_interpret(): native Pallas on a
    # TPU, interpret mode (the CPU correctness oracle) elsewhere.  Only an
    # explicit True runs interpret mode on a TPU.
    pallas_interpret: Optional[bool] = None
    pallas_tile: Optional[tuple] = None
    # Donate every field buffer to jit (classic double-buffer rotation:
    # the caller hands over ownership; inputs are invalidated after the
    # call).  Off by default — only safe when the caller rotates buffers.
    donate: bool = False
    jit: bool = True

    def __post_init__(self) -> None:
        if self.backend is None:
            from repro.kernels import default_backend

            object.__setattr__(self, "backend", default_backend())
        if self.backend not in ("jnp", "pallas"):
            raise TargetError(
                f"unknown backend {self.backend!r}; expected 'jnp' or 'pallas'"
            )
        if self.pallas_tile is not None:
            object.__setattr__(self, "pallas_tile", tuple(self.pallas_tile))
        if self.pallas_interpret is None:
            from repro.kernels import default_interpret

            object.__setattr__(self, "pallas_interpret", default_interpret())
        else:
            object.__setattr__(
                self, "pallas_interpret", bool(self.pallas_interpret)
            )
        if self.fused_epoch:
            if self.backend != "pallas":
                raise TargetError(
                    f"Target(fused_epoch=True) requires backend='pallas' "
                    f"(the epoch megakernel IS a pallas_call), got "
                    f"backend={self.backend!r}"
                )
            if self.overlap:
                raise TargetError(
                    "Target(fused_epoch=True) is incompatible with "
                    "overlap=True: split interior/frame applies cannot fuse "
                    "into one epoch kernel"
                )
        if int(self.exchange_every) != self.exchange_every or self.exchange_every < 1:
            raise TargetError(
                f"exchange_every must be a positive integer (1 = exchange "
                f"every step), got {self.exchange_every!r}"
            )
        object.__setattr__(self, "exchange_every", int(self.exchange_every))
        if self.pipeline is not None:
            from repro.core.passes import parse_pipeline

            stages = parse_pipeline(self.pipeline)  # raises if malformed
            # an explicit pipeline must agree with exchange_every: the
            # time_loop epoch arithmetic is driven by the Target knob
            k_spec = 1
            has_fuse_stage = any(
                name == "fuse-epoch-kernel" for name, _ in stages
            )
            if has_fuse_stage != self.fused_epoch:
                raise TargetError(
                    f"explicit pipeline "
                    f"{'contains' if has_fuse_stage else 'lacks'} the "
                    f"fuse-epoch-kernel stage but "
                    f"Target(fused_epoch={self.fused_epoch}); set both "
                    "consistently (the kernel routing is driven by the "
                    "Target knob)"
                )
            for name, opts in stages:
                if name == "temporal-tile":
                    try:
                        k_spec = int(opts.get("k", self.exchange_every))
                    except ValueError:
                        raise TargetError(
                            f"pipeline stage temporal-tile: k must be an "
                            f"integer, got {opts.get('k')!r}"
                        )
            if k_spec != self.exchange_every:
                raise TargetError(
                    f"pipeline stage temporal-tile{{k={k_spec}}} disagrees "
                    f"with Target(exchange_every={self.exchange_every}); "
                    "set both to the same epoch depth"
                )
        if self.slot_axis is not None:
            # validated here like exchange_every: a slot-axis target either
            # compiles or names the mismatch at construction
            if not isinstance(self.slot_axis, str) or not self.slot_axis:
                raise TargetError(
                    f"slot_axis must be a mesh axis name, got "
                    f"{self.slot_axis!r}"
                )
            if self.mesh is None:
                raise TargetError(
                    f"Target(slot_axis={self.slot_axis!r}) needs a mesh "
                    "carrying that axis; factor one out of the device "
                    "inventory with api.pooled_target / "
                    "dist.sharding.factor_slot_mesh"
                )
            if self.slot_axis not in self.mesh.axis_names:
                raise TargetError(
                    f"slot_axis {self.slot_axis!r} not in mesh axes "
                    f"{tuple(self.mesh.axis_names)}"
                )
            if self.strategy is not None and self.slot_axis in tuple(
                self.strategy.axis_names
            ):
                raise TargetError(
                    f"slot_axis {self.slot_axis!r} is already a spatial "
                    f"decomposition axis of the strategy "
                    f"{tuple(self.strategy.axis_names)}; the slot axis "
                    "carries the batch dimension, not an array dimension"
                )
        s = self.strategy
        if s is not None:
            decomposed = [
                (g, ax) for g, ax in zip(s.grid_shape, s.axis_names) if g > 1
            ]
            if decomposed and self.mesh is None:
                raise TargetError(
                    f"strategy decomposes over {[ax for _, ax in decomposed]} "
                    "but no mesh was given"
                )
            for g, ax in decomposed:
                if ax not in (self.mesh.axis_names if self.mesh else ()):
                    raise TargetError(
                        f"strategy axis {ax!r} not in mesh axes "
                        f"{tuple(self.mesh.axis_names)}"
                    )
                if self.mesh.shape[ax] != g:
                    raise TargetError(
                        f"strategy grid size {g} on axis {ax!r} != mesh size "
                        f"{self.mesh.shape[ax]}"
                    )

    # ------------------------------------------------------------------
    @classmethod
    def auto(cls, ranks: Optional[int] = None, **overrides) -> "Target":
        """Device discovery: decompose 1-D over the available devices
        (or the first ``ranks`` of them); single-device target when only
        one device exists."""
        import numpy as np

        from repro.core.passes.decompose import make_strategy_1d

        devices = jax.devices()
        n = len(devices) if ranks is None else int(ranks)
        if n > len(devices):
            raise TargetError(f"requested {n} ranks, have {len(devices)} devices")
        if n <= 1:
            return cls(**overrides)
        return cls(
            mesh=Mesh(np.array(devices[:n]), ("x",)),
            strategy=make_strategy_1d(n),
            **overrides,
        )

    @classmethod
    def tuned(
        cls,
        program: "Program",
        ranks: Optional[int] = None,
        *,
        measure: bool = True,
        cache: bool = True,
        **tune_kwargs,
    ) -> "Target":
        """The autotuned target for ``program`` on this machine
        (``repro.tune``): enumerate the mesh/overlap/exchange_every/
        backend/tile space, score it with the roofline model, optionally
        measure the survivors, and return the winner — persisted on disk
        so a second call (any process, same hardware) is a cache hit."""
        from repro.tune import tune

        return tune(
            program, ranks=ranks, measure=measure, cache=cache, **tune_kwargs
        ).target

    # ------------------------------------------------------------------
    def pipeline_spec(self) -> str:
        """The pass-pipeline spec this target denotes (explicit ``pipeline``
        or the canonical flag expansion, fig. 4): [fuse,cse] → decompose →
        swap-elim → [temporal-tile] → [diagonal] → [overlap] → lower-comm."""
        if self.pipeline is not None:
            return self.pipeline
        stages: list[str] = []
        if self.fuse:
            stages.append("fuse")
        if self.cse:
            stages += ["cse", "dce"]
        stages += ["decompose", "swap-elim"]
        if self.exchange_every > 1:
            stages.append(f"temporal-tile{{k={self.exchange_every}}}")
        if self.diagonal:
            stages.append("diagonal")
        if self.overlap:
            stages.append("overlap")
        stages.append("lower-comm")
        if self.fused_epoch:
            # after lower-comm: the fused region holds only apply +
            # boundary_mask ops; exchanges stay outside the kernel
            stages.append("fuse-epoch-kernel")
        return ",".join(stages)

    @property
    def distributed(self) -> bool:
        """True when compilation wraps the step in ``shard_map`` — a
        spatial decomposition with > 1 rank, a slot mesh axis, or both."""
        if self.mesh is not None and self.slot_axis is not None:
            return True
        return self.mesh is not None and self.strategy is not None and any(
            g > 1 for g in self.strategy.grid_shape
        )

    @property
    def spatial_ranks(self) -> int:
        """Devices per slot: the product of the spatial decomposition grid
        (1 for an undecomposed target)."""
        if self.strategy is None:
            return 1
        out = 1
        for g in self.strategy.grid_shape:
            out *= int(g)
        return out

    @property
    def fingerprint(self) -> str:
        mesh_desc = "none"
        if self.mesh is not None:
            mesh_desc = (
                f"axes={tuple(self.mesh.axis_names)}"
                f"shape={tuple(self.mesh.shape[a] for a in self.mesh.axis_names)}"
                f"devices={tuple((d.platform, d.id) for d in self.mesh.devices.flat)}"
            )
        s = self.strategy
        strat_desc = (
            "none" if s is None
            else f"grid={tuple(s.grid_shape)}axes={tuple(s.axis_names)}dims={tuple(s.dims)}"
        )
        text = "\n".join(
            [
                f"mesh={mesh_desc}",
                f"strategy={strat_desc}",
                f"backend={self.backend}",
                f"pipeline={self.pipeline_spec()}",
                # explicit even though the default spec carries it: an
                # explicit ``pipeline`` must still produce distinct cached
                # artifacts per epoch depth (time_loop arithmetic differs)
                f"exchange_every={self.exchange_every}",
                # explicit even though the mesh desc carries the axis: a
                # slot-axis artifact has a different calling convention
                # ([B, *shape] arrays), so it must never collide with its
                # spatial-only sibling in the compile cache
                f"slot_axis={self.slot_axis}",
                f"fused_epoch={self.fused_epoch}",
                f"pallas_interpret={self.pallas_interpret}",
                f"pallas_tile={self.pallas_tile}",
                f"donate={self.donate}",
                f"jit={self.jit}",
            ]
        )
        return hashlib.sha256(text.encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# CompiledStencil — the reusable artifact
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PipelineReport:
    """What the pass pipeline did for one compile: the resolved spec and
    per-pass wall-clock timings."""

    spec: str
    timings: tuple  # ((pass name, seconds), ...)

    def __str__(self) -> str:
        lines = [f"pipeline: {self.spec}"]
        for name, sec in self.timings:
            lines.append(f"  {name:<16} {sec * 1e3:8.2f} ms")
        return "\n".join(lines)


class CompiledStencil:
    """A compiled stencil step: callable over *global* arrays, plus the
    artifacts a user inspects — the rank-local comm-lowered IR, the
    pipeline report, partition specs, AOT lowering and roofline cost."""

    def __init__(
        self,
        program: Program,
        target: Target,
        strategy: SlicingStrategy,
        local_ir: ir.FuncOp,
        pipeline_report: PipelineReport,
        fn: Callable,
        partition_specs: tuple,
        donate_argnums: tuple,
        raw_fn: Callable,
        ret_indices: Optional[tuple] = None,
    ) -> None:
        self.program = program
        self.target = target
        self.strategy = strategy
        self.local_ir = local_ir
        self.pipeline_report = pipeline_report
        self.partition_specs = partition_specs
        self.donate_argnums = donate_argnums
        self._fn = fn
        self._raw_fn = raw_fn  # pre-jit (shard_map'd) callable, for .lower()
        # buffers step() allocates internally: the program's stored fields
        self._out_indices = tuple(
            program.field_args.index(f) for f in program.output_fields
        )
        # field-arg positions of the values a call RETURNS (first-store
        # order of the local IR) — equals _out_indices except for epoched
        # carried-state programs (wave, p > q), whose epochs also hand
        # back the rotated-through intermediate buffers
        self._ret_indices = (
            ret_indices if ret_indices is not None else self._out_indices
        )

    # -- execution -------------------------------------------------------
    def __call__(self, *arrays):
        return self._fn(*arrays)

    @property
    def static_indices(self) -> tuple:
        """Field-arg positions of the program's static fields."""
        return tuple(
            self.program.field_names.index(n) for n in self.program.static_fields
        )

    @property
    def input_indices(self) -> tuple:
        """Field-arg positions ``step()`` consumes, in the order of the
        time-loop state: the static fields, then the time levels oldest →
        newest; the complement of the internally-allocated output
        buffers."""
        outs = set(self._out_indices)
        static = self.static_indices
        return static + tuple(
            i for i in range(len(self.program.field_args))
            if i not in outs and i not in static
        )

    @property
    def ret_indices(self) -> tuple:
        """Field-arg positions of the values one call RETURNS (first-store
        order of the local IR).  Equals the program's stored fields except
        for epoched carried-state programs (wave, p > q), whose epochs
        also hand back the rotated-through intermediate buffers.  The
        resilience driver records this in checkpoint manifests — the
        rotation arithmetic of a resumed run must match the killed one."""
        return self._ret_indices

    def step(self, dtype=None) -> Callable:
        """A step over the *input* fields only: output buffers (fully
        overwritten every call) are allocated internally — the shape
        ``time_loop`` rotation wants.  With ``Target(exchange_every=k)``
        one call advances a whole k-step epoch.  A slot-axis target takes
        (and allocates) ``[B, *field_shape]`` arrays — one pooled call
        advances ``B`` independent simulations.  Each trace of it into an
        enclosing computation counts in ``step_traces()``."""
        outs = set(self._out_indices)
        pooled = self.target.slot_axis is not None
        position = {f: n for n, f in enumerate(self.input_indices)}

        def fn(*inputs):
            if inputs and isinstance(inputs[0], jax.core.Tracer):
                _count_step_trace()
            assert len(inputs) == len(position), (
                f"{self.program.name!r} takes {len(position)} input arrays "
                f"(static fields, then time levels), got {len(inputs)}"
            )
            dt = dtype or (inputs[0].dtype if inputs else jnp.float32)
            lead = (inputs[0].shape[0],) if (pooled and inputs) else ()
            args = [
                jnp.zeros(lead + tuple(f.type.bounds.shape), dt)
                if i in outs
                else inputs[position[i]]
                for i, f in enumerate(self.program.field_args)
            ]
            return self._fn(*args)

        return fn

    def epochs(self, n_steps: int) -> int:
        """``n_steps`` time steps as a whole number of epochs of this
        artifact — the shared validation for every driver (``time_loop``,
        ``repro.resilience``, the serve engine's admission check): a
        depth-k artifact advances k steps per call, so ``n_steps`` must
        divide evenly (a partial epoch has no compiled form)."""
        k = self.target.exchange_every
        if n_steps % k != 0:
            raise ValueError(
                f"n_steps={n_steps} with "
                f"Target(exchange_every={k}): n_steps must be a multiple of "
                f"the epoch depth (each call advances {k} steps)"
            )
        return n_steps // k

    def advance(self, state: Sequence[Any]) -> tuple:
        """One epoch with time-buffer rotation applied: consume ``state``
        (static fields, then time levels oldest → newest), return the
        rotated state after ``exchange_every`` time steps — exactly one
        iteration of ``time_loop``'s body, exposed so epoch-granular
        drivers (``repro.resilience.ResilientLoop``, the serve engine) and
        the fori-loop driver share one rotation rule (``rotate``)."""
        return rotate(state, self.step()(*state), len(self.static_indices))

    def time_loop(
        self, state: Sequence[Any], n_steps: int, unroll: Optional[int] = None
    ):
        """Iterate ``n_steps`` *time steps* with time-buffer rotation
        (``state`` ordered oldest→newest) under one ``lax.fori_loop``
        that runs ``unroll`` epochs an iteration (``loop_unroll`` by
        default).

        ``n_steps`` always counts single time steps regardless of the
        target's ``exchange_every``: the loop runs ``self.epochs(n_steps)``
        epochs.  For a checkpointable / fault-tolerant loop with the same
        arithmetic, see ``repro.resilience.ResilientLoop``.

        The ``time_loop`` span (``repro.obs``) covers the host side of a
        call: its trace, compile and dispatch, not the device's run."""
        static = len(self.static_indices)
        with _obs.span("time_loop", program=self.program.name,
                       n_steps=n_steps, k=self.target.exchange_every,
                       static=static):
            return time_loop(
                self.step(), tuple(state), self.epochs(n_steps),
                unroll=unroll or self.loop_unroll, static=static,
            )

    @property
    def loop_unroll(self) -> int:
        """Epochs an iteration of ``time_loop``'s loop runs.  Where a
        Pallas kernel reads a time level in place (directly, or through a
        pad ``lowering.pad_folds`` folds), the epoch's new level cannot
        take that level's buffer, and with one epoch an iteration XLA
        copies a level every epoch to make room.  Running as many epochs
        as it takes the ``n + k`` buffers of ``n`` levels and ``k`` new
        ones, rotating by ``k`` an epoch, to come back round lets XLA
        alternate them and copy none (2 for one level)."""
        backend = self.target.backend
        fields = [a for a in self.local_ir.body.args
                  if isinstance(a.type, stencil.FieldType)]
        static = set(self.static_indices)
        levels = {fields[i] for i in self.input_indices if i not in static}
        in_place = any(
            (isinstance(r, comm.HaloPadOp) and pad_folds(r, backend))
            or (isinstance(r, stencil.ApplyOp) and runs_pallas(r, backend))
            for op in self.local_ir.body.ops
            if isinstance(op, stencil.LoadOp) and op.field in levels
            for r in (use.operation for use in op.results[0].uses)
        )
        if not in_place:
            return 1
        n, k = len(levels), len(self.ret_indices)
        return (n + k) // math.gcd(n + k, k)

    # -- inspection ------------------------------------------------------
    @property
    def kernel_dispatches(self) -> dict:
        """Static kernel-op census of one epoch of the compiled program:
        how many fused-epoch megakernels and how many standalone applies
        the local IR executes per call, how many of those applies lower
        to the Pallas window kernel (``pallas_apply``; a split's frames
        run jnp), how many ``comm.halo_pad`` ops the IR holds
        (``halo_pad``), and how many of those still copy their operand
        (``halo_pad_copies``: the pads ``lowering.pad_folds`` leaves).
        With ``Target(fused_epoch=True)`` an epoched
        program reads ``{"fused_epoch": 1, "apply": 0, ...}`` — one kernel
        dispatch per epoch (cross-checked at trace time by
        ``repro.kernels.dispatch_stats``)."""
        fused = sum(
            1
            for op in self.local_ir.body.ops
            if isinstance(op, stencil.FusedEpochOp)
        )
        applies = [
            op for op in self.local_ir.body.ops
            if isinstance(op, stencil.ApplyOp)
        ]
        pads = [
            op for op in self.local_ir.body.ops
            if isinstance(op, comm.HaloPadOp)
        ]
        return {
            "fused_epoch": fused,
            "apply": len(applies),
            "pallas_apply": sum(
                runs_pallas(op, self.target.backend) for op in applies
            ),
            "halo_pad": len(pads),
            "halo_pad_copies": sum(
                not pad_folds(op, self.target.backend) for op in pads
            ),
            "total": fused + len(applies),
        }

    def lower(self, dtype=jnp.float32):
        """AOT-lower with ShapeDtypeStruct inputs (no allocation) — the
        dry-run entry point: ``.lower().compile().memory_analysis()``."""
        # a slot-axis artifact takes [B, *shape]: lower at one row per
        # slot-axis shard, the narrowest batch the mesh can carry
        lead = (
            (int(self.target.mesh.shape[self.target.slot_axis]),)
            if self.target.slot_axis is not None
            else ()
        )
        args = []
        for f, spec in zip(self.program.field_args, self.partition_specs):
            sharding = (
                NamedSharding(self.target.mesh, spec)
                if self.target.mesh is not None
                else None
            )
            args.append(
                jax.ShapeDtypeStruct(
                    lead + tuple(f.type.bounds.shape), dtype, sharding=sharding
                )
            )
        return jax.jit(self._raw_fn).lower(*args)

    def cost(self, dtype=jnp.float32, device_kind: Optional[str] = None):
        """Roofline terms of the compiled executable (launch/roofline):
        per-device FLOPs / HBM bytes / collective bytes → seconds per
        term, dominant bottleneck, overlapped/serial step time — plus the
        temporal-tiling tradeoff terms (message count per epoch, per-step
        halo widths, shard extents) so ``.cost().recommend_exchange_every()``
        can pick the epoch depth that balances amortized exchange latency
        against redundant boundary compute.

        ``device_kind`` names the chip whose peaks the terms use (a key of
        ``launch.roofline.PEAKS``); by default the kind of the target's
        first device.  A caller on the CPU that models a TPU names it
        (``launch.roofline.V5E``): the CPU has no peaks entry."""
        from repro.core.dialects import comm
        from repro.core.passes.temporal import TemporalTilingError, epoch_halo
        from repro.launch.roofline import (
            RooflineTerms,
            collective_bytes_from_hlo,
            device_peaks,
        )

        if device_kind is None:
            mesh = self.target.mesh
            device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
            device_kind = device.device_kind
        device_peaks(device_kind)  # unknown kinds fail before compiling
        compiled = self.lower(dtype).compile()
        cost = compiled.cost_analysis()

        step_halo: tuple = ()
        try:
            lo1, hi1 = epoch_halo(self.program.func, 1)
            step_halo = tuple(max(l, h) for l, h in zip(lo1, hi1))
        except TemporalTilingError:
            pass  # non-epochable program shapes carry no tiling terms
        local_shape: tuple = ()
        if self.program.field_args:
            local_shape = self.strategy.local_bounds(
                self.program.field_args[0].type.bounds
            ).shape
        messages = sum(
            1
            for op in self.local_ir.body.ops
            if isinstance(op, comm.ExchangeStartOp)
        )
        return RooflineTerms(
            flops=cost.get("flops") or 0.0,
            bytes_accessed=cost.get("bytes accessed") or 0.0,
            collectives=collective_bytes_from_hlo(compiled.as_text()),
            exchange_every=self.target.exchange_every,
            messages_per_epoch=messages,
            step_halo=step_halo,
            local_shape=local_shape,
            device_kind=device_kind,
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"CompiledStencil({self.program.name!r}, "
            f"backend={self.target.backend!r}, "
            f"distributed={self.target.distributed}, "
            f"pipeline={self.pipeline_report.spec!r})"
        )


# --------------------------------------------------------------------------
# compile + the process-wide cache
# --------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0

    def as_dict(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }


# LRU-bounded: a long-lived serving process compiles an open-ended stream
# of (program, target) pairs; without a bound the process-wide cache —
# and every XLA executable it pins — grows monotonically.  Capacity is
# generous (sweeps and the serve engine fit comfortably); override with
# REPRO_COMPILE_CACHE_CAP or set_cache_capacity().
_DEFAULT_CAPACITY = 256
_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_CAPACITY = max(
    1, int(os.environ.get("REPRO_COMPILE_CACHE_CAP", _DEFAULT_CAPACITY))
)
_STATS = CacheStats()
# Global lock guards the dicts only (held briefly); builds run under a
# per-key lock, so concurrent compiles of the SAME key return the same
# artifact ("second is first" is part of the contract) while unrelated
# compiles — and the serve engine's per-request lookups — stay parallel.
_LOCK = threading.RLock()
_KEY_LOCKS: dict[tuple, threading.Lock] = {}


# steps traced into a computation: ``compile.step_traces`` of
# ``obs.snapshot()``
_STEP_TRACES = 0


def _count_step_trace() -> None:
    global _STEP_TRACES
    with _LOCK:
        _STEP_TRACES += 1


def step_traces() -> int:
    """How many times a ``CompiledStencil.step()`` was traced into an
    enclosing computation (a ``time_loop``'s ``fori_loop`` body, a
    ``jax.jit``, the serve engine's vmapped pool).  Each such computation
    is compiled anew, so a count that grows with the calls finds a step
    that recompiles: an eager ``time_loop`` call adds one every call, a
    ``time_loop`` under one ``jax.jit`` one in all."""
    return _STEP_TRACES


def cache_stats() -> CacheStats:
    """Process-wide compile-cache counters (shared by ``compile`` and
    ``cached_callable``) — truthful hit/miss/eviction
    counts of the LRU-bounded cache."""
    return _STATS


def cache_capacity() -> int:
    return _CAPACITY


def set_cache_capacity(n: int) -> int:
    """Bound the process-wide compile cache to ``n`` entries (LRU
    eviction; evicting frees the artifact for GC).  Returns the previous
    capacity.  ``n`` must be >= 1 — a serving process needs at least the
    artifact it is currently dispatching."""
    global _CAPACITY
    if int(n) < 1:
        raise ValueError(f"cache capacity must be >= 1, got {n!r}")
    with _LOCK:
        prev, _CAPACITY = _CAPACITY, int(n)
        _evict_over_capacity()
    return prev


def _evict_over_capacity() -> None:
    # caller holds _LOCK
    while len(_CACHE) > _CAPACITY:
        key, _ = _CACHE.popitem(last=False)
        _KEY_LOCKS.pop(key, None)
        _STATS.evictions += 1


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _KEY_LOCKS.clear()
        _STATS.hits = 0
        _STATS.misses = 0
        _STATS.evictions = 0


def _cached(key: tuple, build: Callable[[], Any]) -> Any:
    with _LOCK:
        if key in _CACHE:
            _STATS.hits += 1
            _CACHE.move_to_end(key)  # LRU freshness
            return _CACHE[key]
        key_lock = _KEY_LOCKS.setdefault(key, threading.Lock())
    with key_lock:
        with _LOCK:
            if key in _CACHE:  # built by the thread we waited on
                _STATS.hits += 1
                _CACHE.move_to_end(key)
                return _CACHE[key]
        out = build()
        with _LOCK:
            _STATS.misses += 1
            _CACHE[key] = out
            _evict_over_capacity()
        return out


def trivial_strategy(rank: int) -> SlicingStrategy:
    names = ("x", "y", "z", "w")[:rank]
    return SlicingStrategy((1,) * rank, names, tuple(range(rank)))


def compile(
    program: Program,
    target: Optional[Target] = None,
    *,
    tune=None,
) -> CompiledStencil:
    """Compile ``program`` for ``target`` (default: single device).

    ``tune=True`` (or a dict of ``repro.tune.tune`` keyword arguments)
    picks the target automatically via the autotuner instead —
    mutually exclusive with an explicit ``target``.

    Cached process-wide on ``(program.fingerprint, target.fingerprint)``:
    a repeated compile of the same program + target returns the same
    ``CompiledStencil`` without re-running the pass pipeline or
    re-tracing, and its jit cache carries over."""
    if tune:
        if target is not None:
            raise ValueError(
                "pass either target= or tune=, not both (tune selects "
                "the target)"
            )
        target = Target.tuned(
            program, **(tune if isinstance(tune, dict) else {})
        )
    target = target or Target()
    _validate_for_program(program, target)
    # the fingerprint is taken at Program construction; a func mutated
    # afterwards would poison the cache under a stale key — refuse it
    if ir.fingerprint(program.func, *program._salt) != program.fingerprint:
        raise ValueError(
            f"Program {program.name!r}: IR was mutated after construction; "
            "run rewrites on the FuncOp first, then wrap it in a Program"
        )
    key = ("compile", program.fingerprint, target.fingerprint)
    if _obs.enabled():
        with _LOCK:
            hit = key in _CACHE
        with _obs.span("api.compile", program=program.name,
                       cache="hit" if hit else "miss"):
            return _cached(key, lambda: _build(program, target))
    return _cached(key, lambda: _build(program, target))


def _validate_for_program(program: Program, target: Target) -> None:
    s = target.strategy
    if s is not None:
        for g, d in zip(s.grid_shape, s.dims):
            if d >= program.rank:
                raise TargetError(
                    f"strategy decomposes dim {d} of a rank-{program.rank} "
                    f"program {program.name!r}"
                )
            if g > 1:
                for f in program.field_args:
                    extent = f.type.bounds.shape[d]
                    if extent % g != 0:
                        raise TargetError(
                            f"dim {d} extent {extent} of {program.name!r} not "
                            f"divisible by grid size {g}"
                        )
    if target.backend == "pallas" and target.pallas_tile is not None:
        _validate_pallas_tile(program, target)
    if program.static_fields:
        knobs = [
            knob for knob, on in (
                (f"exchange_every={target.exchange_every}", target.exchange_every > 1),
                ("fused_epoch=True", target.fused_epoch),
                (f"slot_axis={target.slot_axis!r}", target.slot_axis is not None),
            ) if on
        ]
        if knobs:
            raise TargetError(
                f"program {program.name!r} has static fields "
                f"{list(program.static_fields)}; Target({', '.join(knobs)}) "
                "does not carry static fields yet"
            )
    if target.distributed and _reads_index(program.func):
        raise TargetError(
            f"program {program.name!r} reads stencil.index, which is the "
            "rank-local index under a decomposition; compile it for one device"
        )
    if target.exchange_every > 1:
        _validate_exchange_every(program, target)


def refuse_static_fields(program: Program, driver: str) -> None:
    """Raise ``TargetError`` where ``driver`` is given a program with
    static fields, which its state rotation does not hold apart yet."""
    if program.static_fields:
        raise TargetError(
            f"program {program.name!r} has static fields "
            f"{list(program.static_fields)}; {driver} does not carry static "
            "fields yet: run it with CompiledStencil.time_loop"
        )


def _reads_index(func: ir.FuncOp) -> bool:
    return any(isinstance(op, stencil.IndexOp) for op in func.walk())


def _validate_pallas_tile(program: Program, target: Target) -> None:
    """A user tile must obey the kernels' (8, 128) tile rule on the *local
    shard* shape the kernel will see — caught here with a named error, not
    deep inside the kernel.  Split-overlapped and epoch-tiled applies
    re-tile automatically (their per-part shapes vary), so only their
    tile *rank* is checked."""
    from repro.kernels.stencil_apply import is_legal_tile

    tile = target.pallas_tile
    if not program.field_args:
        return
    rank = program.rank
    if len(tile) != rank:
        raise TargetError(
            f"pallas_tile {tile} has {len(tile)} dims but program "
            f"{program.name!r} is rank-{rank}"
        )
    if any(int(t) < 1 for t in tile):
        raise TargetError(f"pallas_tile {tile} must be positive")
    spec = target.pipeline_spec()
    if "overlap" in spec or "temporal-tile" in spec or "fuse-epoch-kernel" in spec:
        return  # lowering auto-tiles split/epoched/fused applies that mismatch
    s = target.strategy
    grid_of_dim = {}
    if s is not None:
        for g, ax, d in zip(s.grid_shape, s.axis_names, s.dims):
            grid_of_dim[d] = (g, ax)
    shape = program.field_args[0].type.bounds.shape
    local = tuple(
        shape[d] // grid_of_dim.get(d, (1, None))[0] for d in range(rank)
    )
    if not is_legal_tile(local, tile):
        axes = [
            f"dim {d} decomposed over mesh axis {ax!r} (grid {g})"
            for d, (g, ax) in sorted(grid_of_dim.items())
            if ax is not None and g > 1
        ]
        where = "; ".join(axes) if axes else "undecomposed"
        raise TargetError(
            f"pallas_tile {tile} is illegal for the local shard shape "
            f"{local} of program {program.name!r} ({where}): each extent "
            f"must be the shard's extent or, along the last two dims, a "
            f"multiple of 8 (sublanes) and 128 (lanes) below it; pick such "
            f"a tile or drop pallas_tile for auto-tiling"
        )


def _validate_exchange_every(program: Program, target: Target) -> None:
    """A depth-k epoch exchanges a k-times-accumulated halo in one shot;
    the send slab must come out of the neighbour's core, so the deep width
    cannot exceed the local shard extent on any axis."""
    from repro.core.passes.temporal import TemporalTilingError, epoch_halo

    k = target.exchange_every
    try:
        lo1, hi1 = epoch_halo(program.func, 1)
        lok, hik = epoch_halo(program.func, k)
    except TemporalTilingError as e:
        raise TargetError(
            f"Target(exchange_every={k}) cannot epoch program "
            f"{program.name!r}: {e}"
        )
    s = target.strategy
    grid_of_dim = {}
    if s is not None:
        for g, ax, d in zip(s.grid_shape, s.axis_names, s.dims):
            grid_of_dim[d] = (g, ax)
    if not program.field_args:
        return
    shape = program.field_args[0].type.bounds.shape
    for d in range(program.rank):
        g, ax = grid_of_dim.get(d, (1, None))
        local_n = shape[d] // g
        deep = max(lok[d], hik[d])
        step = max(lo1[d], hi1[d])
        if deep > local_n:
            where = (
                f"mesh axis {ax!r}" if ax is not None else "undecomposed"
            )
            max_k = local_n // step if step else k
            raise TargetError(
                f"Target(exchange_every={k}) on {program.name!r}: deep halo "
                f"{deep} (inferred per-step depth {step}, accumulated over "
                f"{k} steps) along dim {d} ({where}) exceeds the local shard "
                f"extent {local_n}; use exchange_every <= {max_k} or "
                f"decompose dim {d} over fewer ranks"
            )


def pooled_target(
    target: Target,
    slots: int = 1,
    axis: str = "slot",
    devices: Optional[Sequence] = None,
) -> Target:
    """The slot-axis sibling of a distributed ``target``: the same spatial
    decomposition plus a leading slot mesh axis of size ``slots`` factored
    out of the device inventory (``dist.sharding.factor_slot_mesh``).

    The sibling's compiled step takes ``[B, *field_shape]`` arrays
    (``B % slots == 0``) and advances every row in ONE ``shard_map``
    dispatch over ``(slot, *spatial)`` — the serve engine's batched
    distributed dispatch, and the ensemble axis of the ROADMAP (one
    compiled stencil over ``B`` perturbed initial conditions).
    """
    from repro.dist.sharding import factor_slot_mesh

    if target.mesh is None:
        raise TargetError(
            "pooled_target needs a distributed target (mesh + strategy); "
            "a single-device pool is just jax.vmap over the step"
        )
    if target.slot_axis is not None:
        raise TargetError(
            f"target already carries slot axis {target.slot_axis!r}"
        )
    mesh = factor_slot_mesh(target.mesh, slots, axis=axis, devices=devices)
    return dataclasses.replace(target, mesh=mesh, slot_axis=axis)


def partition_specs(program: Program, strategy: SlicingStrategy) -> list:
    """PartitionSpec per field argument, from the decomposition map."""
    specs = []
    for f in program.field_args:
        rank = f.type.bounds.rank
        entries: list = [None] * rank
        for gax, d in enumerate(strategy.dims):
            if d < rank and strategy.grid_shape[gax] > 1:
                entries[d] = strategy.axis_names[gax]
        specs.append(P(*entries))
    return specs


def _build(program: Program, target: Target) -> CompiledStencil:
    with _obs.span("api.build", program=program.name,
                   backend=target.backend, k=target.exchange_every):
        return _build_inner(program, target)


def device_major_to_minor(device, shape: Sequence[int]) -> tuple:
    """The order, major to minor, in which ``device`` lays out the dims of
    an f32 array of ``shape`` by default (row-major where the device
    cannot say)."""
    import numpy as np

    try:
        text = str(device.client.get_default_layout(
            np.dtype(np.float32), tuple(shape), device))
    except Exception:  # noqa: BLE001 - a client without layouts is row-major
        return tuple(range(len(shape)))
    minor_to_major = text[text.index("{") + 1:].split(":")[0].rstrip("}")
    return tuple(reversed([int(d) for d in minor_to_major.split(",") if d]))


def layout_perm(program: Program, target: Target) -> Optional[tuple]:
    """The dim permutation a single-device Pallas step runs its program in:
    the device's default layout of the fields where it swaps the two
    minor dims (``core/passes/permute.py``), else ``None``.  A target
    that names a tile keeps the program's own order: the tile names it."""
    if (target.backend != "pallas" or target.distributed or program.rank < 2
            or target.pallas_tile is not None):
        return None
    shapes = {tuple(f.type.bounds.shape) for f in program.field_args}
    if len(shapes) != 1:
        return None
    device = (target.mesh.devices.flat[0] if target.mesh is not None
              else jax.devices()[0])
    order = device_major_to_minor(device, shapes.pop())
    rank = program.rank
    swapped = tuple(range(rank - 2)) + (rank - 1, rank - 2)
    return swapped if order == swapped else None


def _permuted(fn: Callable, perm: tuple) -> Callable:
    """``fn`` over permuted arrays, called and returning in program order
    (``perm`` swaps two dims, so it is its own inverse)."""

    def call(*arrays):
        outs = fn(*(jnp.transpose(a, perm) for a in arrays))
        return tuple(jnp.transpose(o, perm) for o in outs)

    call.__name__ = fn.__name__
    return call


def _build_inner(program: Program, target: Target) -> CompiledStencil:
    strategy = target.strategy or trivial_strategy(program.rank)
    spec = target.pipeline_spec()
    ctx = PipelineContext(
        strategy=strategy,
        boundary=program.boundary,
        exchange_every=target.exchange_every,
    )
    pm = PassManager(build_pipeline(spec, ctx))
    perm = layout_perm(program, target)
    func = _clone_func(program.func)
    if perm is not None:
        from repro.core.passes.permute import PermuteError, permute_dims

        try:
            func = permute_dims(program.func, perm)
        except PermuteError:
            perm = None
    local = pm.run(func)
    report = PipelineReport(spec=spec, timings=tuple(pm.timings))

    distributed = target.distributed
    axis_sizes = (
        {name: target.mesh.shape[name] for name in target.mesh.axis_names}
        if target.mesh is not None
        else {}
    )
    from repro.kernels import KernelPlanError

    try:  # a pallas interpreter plans its kernels as it is built
        interp = StencilInterpreter(
            local,
            axis_sizes=axis_sizes,
            distributed=distributed,
            backend=target.backend,
            pallas_interpret=target.pallas_interpret,
            pallas_tile=target.pallas_tile,
            name=f"{program.name}.step",
        )
    except KernelPlanError as e:
        raise TargetError(f"program {program.name!r}: {e}") from e
    specs = partition_specs(program, strategy)
    # return arity/order comes from the LOCAL IR (first-store order):
    # an epoched carried-state program (wave, p > q) stores — and returns
    # — more buffers per call than the single-step program does
    local_fields = [
        a for a in local.body.args if isinstance(a.type, stencil.FieldType)
    ]
    ret_indices = tuple(
        local_fields.index(f) for f in _stored_fields(local)
    )

    raw: Callable = interp if perm is None else _permuted(interp, perm)
    if distributed:
        body: Callable = interp
        if target.slot_axis is not None:
            # slot-axis calling convention: every field carries a leading
            # batch dim sharded over the slot axis; each device block
            # vmaps the rank-local step over its rows.  Collectives
            # (ppermute halo exchanges, axis_index boundary masks) bind
            # the *spatial* axis names, which vmap batches through — each
            # row sees exactly the solo exchange pattern, so the pooled
            # dispatch stays bitwise-equal to per-slot solo dispatches.
            body = jax.vmap(interp)
            specs = [P(target.slot_axis, *tuple(s)) for s in specs]
        out_specs = tuple(specs[i] for i in ret_indices)
        raw = jax.shard_map(
            body,
            mesh=target.mesh,
            in_specs=tuple(specs),
            out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
            check_vma=False,  # pallas_call outputs carry no vma info
        )
    fn = raw
    # all field buffers: output buffers alias outputs, dead input
    # time-buffers free their storage
    donate = (
        tuple(range(len(program.field_args)))
        if (target.donate and target.jit)
        else ()
    )
    if target.jit:
        fn = jax.jit(raw, donate_argnums=donate)
    return CompiledStencil(
        program=program,
        target=target,
        strategy=strategy,
        local_ir=local,
        pipeline_report=report,
        fn=fn,
        partition_specs=tuple(specs),
        donate_argnums=donate,
        raw_fn=raw,
        ret_indices=ret_indices,
    )


# --------------------------------------------------------------------------
# Cache entry points for the other subsystems
# --------------------------------------------------------------------------


def cached_callable(key: tuple, build: Callable[[], Callable]) -> Callable:
    """Process-wide cache for compiled callables keyed by explicit
    fingerprints — the stencil serve engine keys its vmapped pool step on
    (program, target, pool capacity) so engine restarts skip re-tracing."""
    return _cached(("callable",) + tuple(key), build)


# --------------------------------------------------------------------------
# Shared helpers
# --------------------------------------------------------------------------


def _stored_fields(func: ir.FuncOp) -> list:
    out = []
    for op in func.body.ops:
        if isinstance(op, stencil.StoreOp) and op.field not in out:
            out.append(op.field)
    return out


def _clone_func(func: ir.FuncOp) -> ir.FuncOp:
    new = ir.FuncOp(func.sym_name, [a.type for a in func.body.args])
    vmap: dict[ir.SSAValue, ir.SSAValue] = {}
    for oa, na in zip(func.body.args, new.body.args):
        vmap[oa] = na
    for op in func.body.ops:
        new.body.add_op(op.clone_into(vmap))
    return new


# --------------------------------------------------------------------------
# Time-loop driver (paper benchmarks iterate stencils over timesteps)
# --------------------------------------------------------------------------


def rotate(state: Sequence[Any], outs, static: int = 0) -> tuple:
    """The time-loop state after one step: the ``static`` leading fields
    unchanged, then the time levels (oldest → newest) with the step's
    outputs rotated in: ``state[:static] + levels[len(outs):] + outs``.
    The one rotation rule of ``time_loop``, ``CompiledStencil.advance``
    and ``CompiledStencil.time_loop``."""
    state = tuple(state)
    outs = outs if isinstance(outs, tuple) else (outs,)
    return state[:static] + state[static:][len(outs):] + tuple(outs)


def time_loop(
    step: Callable,
    state: Sequence[Any],
    n_steps: int,
    unroll: int = 1,
    static: int = 0,
) -> tuple:
    """Iterate ``step`` with time-buffer rotation (``rotate``).

    ``state`` is the ``static`` fields, then the time levels oldest →
    newest; each call consumes the full state and produces the newest
    level(s), which rotate in.  The static fields are not carried by the
    loop: each step reads them as they are, and they come back unchanged,
    uncopied.  Runs under ``lax.fori_loop`` so the whole simulation is
    one XLA computation.
    """
    state = tuple(state)
    fixed, levels = state[:static], state[static:]

    def body(_, s):
        return rotate(s, step(*fixed, *s))

    return fixed + jax.lax.fori_loop(0, n_steps, body, levels, unroll=unroll)


# --------------------------------------------------------------------------
# Resilience entry points (repro.resilience)
# --------------------------------------------------------------------------


def resilient_loop(program, target=None, state=(), n_steps=0, **kwargs):
    """A checkpointing, fault-tolerant ``time_loop``: epoch-aligned
    snapshots every ``checkpoint_every`` epochs, killable and resumable —
    see ``repro.resilience.ResilientLoop``."""
    from repro.resilience import ResilientLoop

    return ResilientLoop(program, target, state, n_steps, **kwargs)


def resume(program, directory: str, target=None, **kwargs):
    """Resume a checkpointed run from ``directory`` onto ``target`` — a
    *different* mesh factorization / rank count is allowed: the restored
    host arrays are resharded through ``dist/sharding`` and the program
    recompiled.  See ``repro.resilience.resume``."""
    from repro.resilience import resume as _resume

    return _resume(program, directory, target, **kwargs)

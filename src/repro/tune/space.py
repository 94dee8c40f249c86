"""Search-space enumeration: ``Program`` + device inventory → candidate
``Target``s.

The space is the cross product of every knob the compile surface
exposes, filtered down to configurations that can actually compile:

- **mesh factorizations** of the rank count over the program's array
  dims (8 ranks, rank-2 program → 8×1 slabs on dim 0 or 1, 4×2, 2×4,
  2×2×2 is dropped — more mesh dims than array dims), keeping only
  grids that divide every field extent;
- **overlap** on/off (IR-level comm/compute overlap, PR 2);
- **exchange_every** ∈ ``ks`` filtered by
  ``RooflineTerms.feasible_exchange_every`` on the program's per-step
  halo and shard extents (deep halo must fit the neighbour's core);
- **backend** jnp/pallas, with ``pallas_tile`` candidates derived from
  the local shard shape (whole-shard and split-leading-dim tiles that
  divide it).

Every candidate is validated through ``api._validate_for_program`` —
what comes out of ``enumerate_candidates`` either compiles or was never
offered.  The baseline ``Target.auto(ranks)`` configuration is always
candidate #0 and is never pruned, so a tuned result can be compared
against the default it replaces.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Optional, Sequence

AXIS_NAMES = ("x", "y", "z", "w")


@dataclasses.dataclass
class Candidate:
    """One point of the search space, with its scores as they accrue:
    ``modeled_s`` from the roofline stage, ``measured_s`` from the
    on-device stage (``None`` when pruned before measurement)."""

    target: object  # repro.api.Target
    origin: str = "enumerated"  # "baseline" | "enumerated" | "cached"
    modeled_s: Optional[float] = None
    measured_s: Optional[float] = None
    pruned: bool = False
    note: str = ""
    # why the candidate was dropped: the error its model or measurement
    # raised ("" when it was scored)
    error: str = ""

    @property
    def fingerprint(self) -> str:
        return self.target.fingerprint

    def describe(self) -> str:
        t = self.target
        if t.strategy is not None and any(g > 1 for g in t.strategy.grid_shape):
            grid = "x".join(
                f"{g}@d{d}"
                for g, d in zip(t.strategy.grid_shape, t.strategy.dims)
                if g > 1
            )
        else:
            grid = "1"
        parts = [f"grid={grid}", f"backend={t.backend}", f"k={t.exchange_every}"]
        if t.overlap:
            parts.append("overlap")
        if t.fused_epoch:
            parts.append("fused")
        if t.backend == "pallas" and not t.pallas_interpret:
            parts.append("native")
        if t.pallas_tile:
            parts.append("tile=" + "x".join(str(x) for x in t.pallas_tile))
        return " ".join(parts)

    def as_dict(self) -> dict:
        return {
            "describe": self.describe(),
            "fingerprint": self.fingerprint,
            "origin": self.origin,
            "modeled_s": self.modeled_s,
            "measured_s": self.measured_s,
            "pruned": self.pruned,
            "note": self.note,
            "error": self.error,
        }


# --------------------------------------------------------------------------
# mesh factorizations
# --------------------------------------------------------------------------


def factorizations(n: int) -> list:
    """Ordered tuples of factors ≥ 2 with product ``n`` (``8 → (8,),
    (2,4), (4,2), (2,2,2)``); ``(())`` for n=1."""
    if n <= 1:
        return [()]
    out: list[tuple] = []

    def rec(rem: int, cur: list) -> None:
        if rem == 1:
            out.append(tuple(cur))
            return
        for f in range(2, rem + 1):
            if rem % f == 0:
                rec(rem // f, cur + [f])

    rec(n, [])
    return out


def mesh_assignments(n_ranks: int, rank: int) -> list:
    """Every way to decompose ``n_ranks`` over a rank-``rank`` program:
    tuples of (grid size, array dim), deduplicated (a 2×2 grid on dims
    (0,1) equals the same grid on dims (1,0))."""
    seen = set()
    out = []
    for factors in factorizations(n_ranks):
        if len(factors) > rank:
            continue
        for dims in itertools.permutations(range(rank), len(factors)):
            key = frozenset(zip(factors, dims))
            if len(key) != len(factors) or key in seen:
                continue
            seen.add(key)
            out.append(tuple(sorted(zip(factors, dims), key=lambda fd: fd[1])))
    return out


def strategy_candidates(program, n_ranks: int) -> list:
    """``SlicingStrategy`` per feasible mesh assignment (every field
    extent divisible by its dim's grid size); ``[None]`` at 1 rank."""
    from repro.core.passes.decompose import SlicingStrategy

    if n_ranks <= 1:
        return [None]
    out = []
    for assignment in mesh_assignments(n_ranks, program.rank):
        if not assignment:
            continue
        ok = True
        for g, d in assignment:
            for f in program.field_args:
                if f.type.bounds.shape[d] % g != 0:
                    ok = False
        if not ok:
            continue
        grid = tuple(g for g, _ in assignment)
        dims = tuple(d for _, d in assignment)
        axes = tuple(AXIS_NAMES[i] for i in range(len(grid)))
        out.append(SlicingStrategy(grid, axes, dims))
    return out


def mesh_for_strategy(strategy, devices):
    """A JAX mesh matching ``strategy``'s grid over ``devices``."""
    import numpy as np
    from jax.sharding import Mesh

    if strategy is None:
        return None
    n = int(np.prod(strategy.grid_shape))
    return Mesh(
        np.array(list(devices)[:n]).reshape(strategy.grid_shape),
        strategy.axis_names,
    )


# --------------------------------------------------------------------------
# per-strategy knob candidates
# --------------------------------------------------------------------------


def exchange_every_candidates(
    program, strategy, ks: Sequence[int] = (1, 2, 4, 8)
) -> list:
    """Epoch depths from ``ks`` that are feasible for this program +
    decomposition, via ``RooflineTerms.feasible_exchange_every`` on the
    per-step halo and shard extents; non-epochable programs (e.g.
    time_order=2 state that does not rotate closed) keep only k=1."""
    from repro.core.passes.temporal import TemporalTilingError, epoch_halo
    from repro.launch.roofline import RooflineTerms

    ks = sorted(set(int(k) for k in ks))
    if not program.field_args:
        return [k for k in ks if k == 1]
    try:
        lo1, hi1 = epoch_halo(program.func, 1)
    except TemporalTilingError:
        return [k for k in ks if k == 1] or [1]
    step_halo = tuple(max(l, h) for l, h in zip(lo1, hi1))
    local_shape = _local_shape(program, strategy)
    probe = RooflineTerms(
        flops=0.0,
        bytes_accessed=0.0,
        step_halo=step_halo,
        local_shape=local_shape,
    )
    out = [k for k in ks if k == 1 or probe.feasible_exchange_every(k)]
    return out or [1]


def pallas_tile_candidates(program, strategy) -> list:
    """Tiles derived from the local shard shape: ``None`` (auto), the
    whole shard, and the shard with its leading extent halved — each
    kept only when it divides the shard and obeys the kernels' (8, 128)
    tile rule."""
    from repro.kernels.stencil_apply import is_legal_tile

    local = _local_shape(program, strategy)
    out: list = [None]
    if not local or any(n <= 0 for n in local):
        return out
    out.append(tuple(local))
    half = (local[0] // 2,) + tuple(local[1:])
    if local[0] % 2 == 0 and local[0] >= 16 and is_legal_tile(local, half):
        out.append(half)
    # dedupe, preserve order
    seen: set = set()
    uniq = []
    for t in out:
        if t not in seen:
            seen.add(t)
            uniq.append(t)
    return uniq


def _local_shape(program, strategy) -> tuple:
    if not program.field_args:
        return ()
    bounds = program.field_args[0].type.bounds
    if strategy is None:
        return tuple(bounds.shape)
    return tuple(strategy.local_bounds(bounds).shape)


# --------------------------------------------------------------------------
# pool widths (slot mesh axis — serving / ensemble batching)
# --------------------------------------------------------------------------


def slot_width_candidates(
    n_devices: int, spatial_ranks: int, capacity: int
) -> list:
    """Feasible slot-axis widths for a pool of ``capacity`` slots over a
    ``spatial_ranks``-device decomposition: every ``s`` that divides the
    pool (shard_map needs ``capacity % s == 0``) and fits the inventory
    (``s * spatial_ranks <= n_devices``), widest first.  Always non-empty
    — width 1 (the whole pool vmapped inside each spatial shard) is
    feasible whenever the spatial mesh itself is."""
    cap = max(1, int(capacity))
    spatial = max(1, int(spatial_ranks))
    hi = max(1, min(cap, int(n_devices) // spatial))
    out = [s for s in range(hi, 0, -1) if cap % s == 0]
    return out or [1]


def enumerate_pool_candidates(
    program,
    capacity: int,
    devices: Optional[Sequence] = None,
    backends: Sequence[str] = ("jnp",),
    exchange_every: Sequence[int] = (1,),
    slot_axis: str = "slot",
) -> list:
    """The ROADMAP's ensemble axis as a search space: every way to trade
    pool (ensemble) batch width against mesh factorization on this
    inventory.  For each slot width ``s`` dividing ``capacity``, the
    remaining ``n_devices // s`` devices enumerate spatial strategies
    (``strategy_candidates``), and each feasible pair becomes a slot-axis
    ``Target`` whose compiled step advances ``capacity`` same-fingerprint
    simulations in ONE ``shard_map`` dispatch over ``(slot, *spatial)``.

    Candidates carry ``origin="pool"``; ``describe()`` shows the slot
    width as ``slots=s``.  Widest slot axis enumerates first — the serve
    engine takes the head as its default factorization."""
    import jax

    from repro import api

    devices = list(devices) if devices is not None else jax.devices()
    cap = max(1, int(capacity))
    out: list = []
    seen: set = set()
    widths = sorted(
        {s for s in range(1, min(cap, len(devices)) + 1) if cap % s == 0},
        reverse=True,
    )
    for s in widths:
        n_spatial = len(devices) // s
        if n_spatial < 1:
            continue
        for strategy in strategy_candidates(program, n_spatial):
            spatial_mesh = (
                mesh_for_strategy(strategy, devices)
                if strategy is not None
                else None
            )
            if spatial_mesh is None:
                # pure-ensemble pool: no spatial decomposition.  The
                # lowered IR still binds spatial axis names for its
                # (trivial) exchanges, so the mesh carries them at size 1
                # alongside the slot axis.
                import numpy as np
                from jax.sharding import Mesh

                strategy = api.trivial_strategy(program.rank)
                shape = (s,) + (1,) * program.rank
                mesh = Mesh(
                    np.array(devices[:s]).reshape(shape),
                    (slot_axis,) + tuple(strategy.axis_names),
                )
                kw = dict(mesh=mesh, strategy=strategy, slot_axis=slot_axis)
            else:
                from repro.dist.sharding import factor_slot_mesh

                mesh = factor_slot_mesh(
                    spatial_mesh, s, axis=slot_axis, devices=devices
                )
                kw = dict(mesh=mesh, strategy=strategy, slot_axis=slot_axis)
            ks = exchange_every_candidates(program, strategy, exchange_every)
            for k in ks:
                for backend in backends:
                    try:
                        t = api.Target(
                            backend=backend, exchange_every=k, **kw
                        )
                        api._validate_for_program(program, t)
                    except api.TargetError:
                        continue
                    if t.fingerprint in seen:
                        continue
                    seen.add(t.fingerprint)
                    out.append(
                        Candidate(target=t, origin="pool", note=f"slots={s}")
                    )
    return out


# --------------------------------------------------------------------------
# the full space
# --------------------------------------------------------------------------


def pallas_interpret_candidates(devices: Sequence) -> list:
    """Interpret-mode values the search varies for pallas candidates:
    only the resolved default on CPU-only inventories (interpret — the
    real-device path would crash), the *native* non-interpret path first
    when the inventory has an accelerator (interpret mode on a GPU/TPU is
    a debugging oracle, never a perf winner, so it is not enumerated)."""
    if any(getattr(d, "platform", "cpu") in ("gpu", "tpu") for d in devices):
        return [False]
    return [None]  # resolves via kernels.default_interpret()


def enumerate_candidates(
    program,
    devices: Optional[Sequence] = None,
    ranks: Optional[int] = None,
    backends: Sequence[str] = ("jnp", "pallas"),
    exchange_every: Sequence[int] = (1, 2, 4, 8),
    overlap: Sequence[bool] = (False, True),
    pallas_tiles: bool = True,
    fused_epoch: Sequence[bool] = (False, True),
) -> list:
    """The candidate list for ``program`` on ``devices`` (default: all),
    baseline first.  Simple configurations enumerate first (no overlap,
    shallow epochs, jnp, no tile, per-step dispatch), so stable
    min-by-score tie-breaks prefer the least exotic winner.  Pallas
    candidates additionally vary ``fused_epoch`` (one megakernel per
    epoch) and — when the device inventory has an accelerator — run the
    native non-interpret path (``pallas_interpret_candidates``)."""
    import jax

    from repro import api

    devices = list(devices) if devices is not None else jax.devices()
    n_ranks = len(devices) if ranks is None else int(ranks)
    if n_ranks > len(devices):
        raise api.TargetError(
            f"requested {n_ranks} ranks, have {len(devices)} devices"
        )
    devices = devices[:n_ranks]

    baseline = Candidate(
        target=api.Target.auto(ranks=n_ranks), origin="baseline"
    )
    try:
        api._validate_for_program(program, baseline.target)
    except api.TargetError as e:
        # e.g. extents not divisible by the device count 1-D: fall back
        # to single-device as the reference configuration
        baseline = Candidate(
            target=api.Target(), origin="baseline", note=f"auto invalid: {e}"
        )

    seen = {baseline.fingerprint}
    out = [baseline]
    interprets = pallas_interpret_candidates(devices)
    for strategy in strategy_candidates(program, n_ranks):
        mesh = mesh_for_strategy(strategy, devices)
        ks = exchange_every_candidates(program, strategy, exchange_every)
        tiles = (
            pallas_tile_candidates(program, strategy)
            if pallas_tiles
            else [None]
        )
        for ov in overlap:
            for k in ks:
                for backend in backends:
                    # fused_epoch / pallas_interpret only vary on the
                    # pallas backend (they are inert — and fused_epoch
                    # invalid — on jnp, and would only duplicate
                    # fingerprint-identical candidates)
                    pallas_axes = (
                        [
                            (fe, pi)
                            for fe in fused_epoch
                            for pi in interprets
                            if not (fe and ov)  # fused ⊥ overlap
                        ]
                        if backend == "pallas"
                        else [(False, None)]
                    )
                    for tile in tiles if backend == "pallas" else [None]:
                        for fe, pi in pallas_axes:
                            try:
                                t = api.Target(
                                    mesh=mesh,
                                    strategy=strategy,
                                    backend=backend,
                                    overlap=bool(ov),
                                    exchange_every=k,
                                    fused_epoch=bool(fe),
                                    pallas_interpret=pi,
                                    pallas_tile=tile,
                                )
                                api._validate_for_program(program, t)
                            except api.TargetError:
                                continue
                            if t.fingerprint in seen:
                                continue
                            seen.add(t.fingerprint)
                            out.append(Candidate(target=t))
    return out

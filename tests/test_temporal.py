"""Deep-halo temporal tiling: exchange once, step k times.

The property-based equivalence harness (ISSUE 4 acceptance): random
stencil programs — rank, offsets, chained applies, either boundary — must
produce *bitwise-identical* results for ``exchange_every ∈ {1, 2, 4}``
vs the one-exchange-per-step baseline, plus unit coverage of the pass
mechanics, Target validation, epoch time_loop arithmetic, cache identity
and the roofline tradeoff terms.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings
from _strategies import build_program, exchange_everys, program_descriptors

from repro import api
from repro.api import Target, TargetError
from repro.core.dialects import comm, dmp
from repro.core.passes.temporal import TemporalTilingError, epoch_halo, temporal_tile
from repro.frontends.oec_like import ProgramBuilder


def _jacobi(shape=(16, 16), boundary="periodic", name="jacobi_t"):
    p = ProgramBuilder(name, shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
    )
    p.store(r, out)
    return p.finish(boundary=boundary)


def _run_steps(step, u0, n):
    u = u0
    for _ in range(n):
        u = np.asarray(step(u, np.zeros_like(u0))[0])
    return u


# -------------------------------------------------------------------------
# the property: epochs == steps, bitwise, for generated programs
# -------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(descriptor=program_descriptors, k=exchange_everys)
def test_epoch_equals_steps_bitwise(descriptor, k):
    """exchange_every=k over lcm(k, 2·k) steps is bitwise-equal to the
    k=1 baseline for a random program (≥50 generated programs per run)."""
    seed, rank, n_applies, boundary = descriptor
    prog = build_program(seed, rank, n_applies, boundary)
    shape = prog.field_args[0].type.bounds.shape
    lo, hi = epoch_halo(prog.func, k)
    if any(max(l, h) > n for l, h, n in zip(lo, hi, shape)):
        # the accumulated halo outgrew the domain: the validator must
        # reject the depth instead of computing garbage
        with pytest.raises(TargetError, match="deep halo"):
            api.compile(prog, Target(exchange_every=k, jit=False))
        return
    # jit=False: the eager interpreter path — identical arithmetic,
    # no per-program XLA compile, so the sweep stays fast
    base = api.compile(prog, Target(jit=False))
    tiled = api.compile(prog, Target(exchange_every=k, jit=False))
    rng = np.random.default_rng(seed + 1)
    u0 = rng.standard_normal(shape).astype(np.float32)
    steps = 2 * k  # two epochs: exercises epoch-to-epoch rotation too
    want = _run_steps(base, u0, steps)
    got = u0
    for _ in range(steps // k):
        got = np.asarray(tiled(got, np.zeros_like(u0))[0])
    np.testing.assert_array_equal(want, got)


# -------------------------------------------------------------------------
# pass mechanics
# -------------------------------------------------------------------------


def test_temporal_tile_k1_is_identity():
    from repro.core.passes import decompose_stencil
    from repro.core.passes.decompose import make_strategy_2d

    local = decompose_stencil(_jacobi().func, make_strategy_2d((2, 2)))
    assert temporal_tile(local, 1) is local


def test_epoch_halo_accumulates_with_depth():
    func = _jacobi().func
    lo1, hi1 = epoch_halo(func, 1)
    lo4, hi4 = epoch_halo(func, 4)
    assert lo1 == hi1 == (1, 1)
    assert lo4 == hi4 == (4, 4)


def test_epoch_halo_accumulates_through_chains():
    p = ProgramBuilder("chain_t", (24, 24))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    a = p.apply([t], lambda b, u: (u.at(-1, 0) + u.at(1, 0)) * 0.5)
    r = p.apply([a], lambda b, a: (a.at(0, -1) + a.at(0, 1)) * 0.5)
    p.store(r, out)
    func = p.finish().func
    # one step reads (1, 1); two chained steps read (2, 2) per step
    assert epoch_halo(func, 1) == ((1, 1), (1, 1))
    assert epoch_halo(func, 2) == ((2, 2), (2, 2))


def test_single_deep_swap_per_epoch_even_for_chains():
    """A chain with an intermediate per-step exchange collapses to ONE
    deep exchange per epoch: the intermediate halo becomes redundant
    boundary compute."""
    p = ProgramBuilder("chain_one", (24, 24))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    a = p.apply([t], lambda b, u: (u.at(-1, 0) + u.at(1, 0)) * 0.5)
    r = p.apply([a], lambda b, a: (a.at(0, -1) + a.at(0, 1)) * 0.5)
    p.store(r, out)
    prog = p.finish(boundary="periodic")

    base = api.compile(prog, Target())
    tiled = api.compile(prog, Target(exchange_every=2))
    waits = lambda s: sum(
        1 for op in s.local_ir.body.ops if isinstance(op, comm.WaitOp)
    )
    # baseline: one exchange per apply per step; epoch: one deep exchange
    assert waits(base) == 2
    assert waits(tiled) <= waits(base)
    starts = sum(
        1
        for op in tiled.local_ir.body.ops
        if isinstance(op, comm.ExchangeStartOp)
    )
    assert starts == 4  # one deep volley (4 faces on the trivial 2-d grid)


def test_boundary_mask_only_for_zero_bc():
    def masks(boundary, k):
        prog = _jacobi(boundary=boundary, name=f"mask_probe_{boundary}_{k}")
        step = api.compile(prog, Target(exchange_every=k))
        return sum(
            1
            for op in step.local_ir.body.ops
            if isinstance(op, comm.BoundaryMaskOp)
        )

    assert masks("periodic", 4) == 0
    # k-1 grown intermediates each get re-masked to the physical domain
    assert masks("zero", 4) == 3
    assert masks("zero", 1) == 0


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_wave_rotates_closed_bitwise(k, boundary):
    """time_order-2 (wave-style) programs carry p=2 buffers through a
    q=1 output: the epoch now emits the carried state into the dead
    oldest buffer, so a k-step epoch returns the FULL rotated state and
    exchange_every>1 is bitwise-equal to the per-step baseline."""
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    g = Grid(shape=(32, 32), extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=2, time_order=2)
    op = Operator(Eq(u.dt2, u.laplace), dt=1e-3, boundary=boundary)
    rng = np.random.default_rng(7)
    state = tuple(
        rng.standard_normal((32, 32)).astype(np.float32) for _ in range(2)
    )
    base = api.compile(op.program, Target())
    tiled = api.compile(op.program, Target(exchange_every=k))
    want = base.time_loop(state, 4)
    got = tiled.time_loop(state, 4)
    assert len(got) == 2  # full rotated state: (u@t+3, u@t+4)
    for w, o in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(o))


def test_rejects_more_outputs_than_inputs():
    """q > p state can never rotate closed — must still fail loudly at
    validation (no input buffer exists to carry the extra output)."""
    p = ProgramBuilder("two_out", (16, 16))
    u = p.input("u")
    a = p.output("a")
    b = p.output("b")
    t = p.load(u)
    r = p.apply([t], lambda bb, uu: uu.at(0, 0) * 0.5)
    p.store(r, a)
    p.store(r, b)
    with pytest.raises(TargetError, match="rotate"):
        api.compile(p.finish(), Target(exchange_every=2))


def test_rejects_position_dependent_bodies():
    from repro.core.builder import Expr
    from repro.core.dialects import stencil

    p = ProgramBuilder("idx_probe", (16, 16))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: u.at(0, 0)
        + Expr(b, b.insert(stencil.IndexOp(0)).results[0]),
    )
    p.store(r, out)
    with pytest.raises(TemporalTilingError, match="position-dependent"):
        epoch_halo(p.finish().func, 2)


def _step_func(build):
    """The function of a ``ProgramBuilder`` program whose fields are ``u``
    (input), ``v`` (input) and ``out`` (output), filled in by ``build``."""
    p = ProgramBuilder("epoch_probe", (16, 16))
    fields = {n: p.input(n) for n in ("u", "v")}
    fields["out"] = p.output("out")
    build(p, fields)
    return p.build_func()


def _avg(b, x, y):
    return (x.at(0, 0) + y.at(0, 0)) * 0.5


def _loads_u_twice(p, f):
    p.store(p.apply([p.load(f["u"]), p.load(f["u"])], _avg), f["out"])


def _stores_out_twice(p, f):
    r = p.apply([p.load(f["u"]), p.load(f["v"])], _avg)
    p.store(r, f["out"])
    p.store(r, f["out"])


def _never_loads_v(p, f):
    p.store(p.apply([p.load(f["u"])], lambda b, u: u.at(0, 0)), f["out"])


def _reads_and_writes_out(p, f):
    r = p.apply([p.load(f["u"]), p.load(f["out"])], _avg)
    p.load(f["v"])
    p.store(r, f["out"])


@pytest.mark.parametrize(
    "func,match",
    [
        (lambda: _step_func(_loads_u_twice), "loaded twice"),
        (lambda: _step_func(_stores_out_twice), "stored twice per step"),
        (lambda: _step_func(_never_loads_v), "is never loaded"),
        (lambda: _step_func(_reads_and_writes_out),
         "both loaded and stored"),
        (lambda: _jacobi().func, "run decompose before temporal-tile"),
        (lambda: api.compile(_jacobi()).local_ir,
         "function-level op comm.halo_pad not supported in an epoch"),
    ],
    ids=["load-twice", "store-twice", "input-never-loaded",
         "read-modify-write", "undecomposed", "lowered-comm"],
)
def test_temporal_tile_refuses_steps_it_cannot_epoch(func, match):
    """A step whose state does not rotate cleanly, or a function not at
    the stage the pass expects (undecomposed, or already lowered to
    ``comm``), is refused by name instead of being mis-tiled."""
    with pytest.raises(TemporalTilingError, match=match):
        temporal_tile(func(), 2)


# -------------------------------------------------------------------------
# Target validation + fingerprints + time_loop epochs
# -------------------------------------------------------------------------


def test_target_rejects_bad_exchange_every():
    with pytest.raises(TargetError, match="positive integer"):
        Target(exchange_every=0)
    with pytest.raises(TargetError, match="positive integer"):
        Target(exchange_every=-2)


def test_target_rejects_pipeline_epoch_mismatch():
    with pytest.raises(TargetError, match="temporal-tile"):
        Target(
            pipeline="decompose,swap-elim,temporal-tile{k=4},lower-comm",
            exchange_every=2,
        )
    with pytest.raises(TargetError, match="temporal-tile"):
        # exchange_every>1 with a pipeline that never tiles
        Target(pipeline="decompose,swap-elim,lower-comm", exchange_every=2)


def test_deep_halo_validation_names_axis_and_depth():
    """Satellite fix: exceeding the shard capacity must name the offending
    axis and the inferred per-step depth, mirroring the strategy-grid
    error style."""
    import jax
    from jax.sharding import Mesh

    from repro.core.passes.decompose import make_strategy_1d

    prog = _jacobi(shape=(16, 16), name="deep_probe")
    mesh = Mesh(np.array(jax.devices() * 8), ("x",))
    target = Target(
        mesh=mesh, strategy=make_strategy_1d(8), exchange_every=4
    )
    # shard extent 16/8 = 2 < deep halo 4
    with pytest.raises(TargetError) as ei:
        api.compile(prog, target)
    msg = str(ei.value)
    assert "mesh axis 'x'" in msg
    assert "per-step depth 1" in msg
    assert "deep halo 4" in msg
    assert "exchange_every <= 2" in msg


def test_fingerprints_distinct_per_epoch_depth():
    assert (
        Target(exchange_every=4).fingerprint != Target().fingerprint
    )
    assert (
        Target(exchange_every=4).fingerprint
        != Target(exchange_every=2).fingerprint
    )
    prog = _jacobi(name="fp_probe")
    a = api.compile(prog, Target())
    b = api.compile(prog, Target(exchange_every=4))
    assert a is not b
    assert "temporal-tile{k=4}" in b.pipeline_report.spec


def test_time_loop_iterates_in_epochs():
    import jax.numpy as jnp

    prog = _jacobi(name="epoch_loop_probe")
    base = api.compile(prog, Target())
    tiled = api.compile(prog, Target(exchange_every=4))
    rng = np.random.default_rng(7)
    u0 = jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32))
    (want,) = base.time_loop([u0], 8)
    (got,) = tiled.time_loop([u0], 8)  # 2 epochs of 4
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    with pytest.raises(ValueError, match="multiple of the epoch depth"):
        tiled.time_loop([u0], 6)


# -------------------------------------------------------------------------
# roofline tradeoff terms
# -------------------------------------------------------------------------


def test_cost_carries_tiling_terms_and_recommends():
    from repro.launch.roofline import V5E, RooflineTerms

    prog = _jacobi(name="cost_probe")
    terms = api.compile(prog, Target()).cost(device_kind=V5E)
    assert terms.exchange_every == 1
    assert terms.messages_per_epoch == 4  # 4 faces on the trivial 2-d grid
    assert terms.step_halo == (1, 1)
    assert terms.local_shape == (16, 16)
    assert terms.redundant_compute_factor(1) == 1.0
    assert terms.redundant_compute_factor(4) > 1.0
    d = terms.as_dict()
    assert "recommended_exchange_every" in d and "t_latency" in d

    # latency-dominated regime (tiny shard, many messages): deep epochs win
    lat = RooflineTerms(
        flops=1e6, bytes_accessed=1e5, collectives={},
        exchange_every=1, messages_per_epoch=8,
        step_halo=(1, 1), local_shape=(32, 32), device_kind=V5E,
    )
    assert lat.recommend_exchange_every(max_k=8) > 1
    # compute-dominated regime (huge shard FLOPs): stay at k=1
    comp = RooflineTerms(
        flops=1e13, bytes_accessed=1e5, collectives={},
        exchange_every=1, messages_per_epoch=2,
        step_halo=(4, 4), local_shape=(8, 8), device_kind=V5E,
    )
    assert comp.recommend_exchange_every(max_k=8) == 1
    # infeasible depths (deep halo > shard) are never recommended
    assert not lat.feasible_exchange_every(64)


def test_epoch_emits_scaled_swap_extents():
    """The deep swap's halo extents are the per-step extents scaled by k
    (golden structural property of the rewrite)."""
    from repro.core.passes import (
        decompose_stencil,
        eliminate_redundant_swaps,
    )
    from repro.core.passes.decompose import make_strategy_2d

    local = decompose_stencil(
        _jacobi((32, 32)).func, make_strategy_2d((2, 2)), boundary="periodic"
    )
    eliminate_redundant_swaps(local)
    tiled = temporal_tile(local, 4)
    (swap,) = [op for op in tiled.body.ops if isinstance(op, dmp.SwapOp)]
    assert swap.halo_widths() == ((4, 4), (4, 4))
    # step j computes core grown by (k-j): 22, 20, 18, 16
    from repro.core.dialects import stencil

    shapes = [
        op.result_bounds.shape
        for op in tiled.body.ops
        if isinstance(op, stencil.ApplyOp)
    ]
    assert shapes == [(22, 22), (20, 20), (18, 18), (16, 16)]

"""Multi-device distribution correctness worker.

Run in a SUBPROCESS (tests/test_distributed.py) so the 8-device flag
never leaks into the main pytest process:

    python tests/dist_worker.py <scenario>

Exit 0 = all assertions passed.  Each scenario compares an N-rank
decomposed run (shard_map + dmp halo exchanges over virtual CPU devices)
against the single-device run of the same program — the decomposition +
swap machinery is correct by test, not by construction.
"""
import os
import sys

# a CPU-only tool: virtual CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.api import Target, compile as api_compile  # noqa: E402
from repro.core.passes.decompose import (  # noqa: E402
    make_strategy_1d,
    make_strategy_2d,
    make_strategy_3d,
)
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction  # noqa: E402
from repro.frontends.oec_like import ProgramBuilder  # noqa: E402


def _jacobi(shape):
    p = ProgramBuilder("jacobi", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    nd = len(shape)
    if nd == 2:
        r = p.apply(
            [t],
            lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
        )
    else:
        r = p.apply(
            [t],
            lambda b, u: (
                u.at(-1, 0, 0) + u.at(1, 0, 0) + u.at(0, -1, 0)
                + u.at(0, 1, 0) + u.at(0, 0, -1) + u.at(0, 0, 1)
            ) * (1.0 / 6.0),
        )
    p.store(r, out)
    return p


def _box(shape):
    """Corner-reading stencil — exercises multi-round / diagonal paths."""
    p = ProgramBuilder("box", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: u.at(-1, -1) + u.at(1, 1) * 0.5 + u.at(-1, 1) * 0.25
        + u.at(0, 0),
    )
    p.store(r, out)
    return p


def _mesh(axes_shape, names):
    devs = np.array(jax.devices()[: int(np.prod(axes_shape))]).reshape(axes_shape)
    return Mesh(devs, names)


def check(name, got, want, tol=0.0):
    got, want = np.asarray(got), np.asarray(want)
    if tol == 0.0:
        ok = np.array_equal(got, want)
    else:
        ok = np.allclose(got, want, rtol=tol, atol=tol)
    if not ok:
        print(f"MISMATCH in {name}: max abs diff "
              f"{np.abs(got - want).max()}")
        sys.exit(1)
    print(f"ok: {name}")


def run_single(builder_fn, shape, boundary):
    prog = builder_fn(shape).finish(boundary=boundary)
    rng = np.random.default_rng(42)
    u0 = rng.standard_normal(shape).astype(np.float32)
    ref = api_compile(prog)(u0, np.zeros_like(u0))
    return u0, np.asarray(ref[0])


def scenario_1d(boundary):
    shape = (64, 32)
    u0, want = run_single(_jacobi, shape, boundary)
    mesh = _mesh((8,), ("x",))
    prog = _jacobi(shape).finish(boundary=boundary)
    step = api_compile(prog, Target(mesh=mesh, strategy=make_strategy_1d(8)))
    got = step(u0, np.zeros(shape, np.float32))
    # fp32 stencil: distribution must be bitwise-identical
    check(f"1d-{boundary}", got[0], want)


def scenario_2d(boundary):
    shape = (32, 64)
    u0, want = run_single(_jacobi, shape, boundary)
    mesh = _mesh((4, 2), ("x", "y"))
    prog = _jacobi(shape).finish(boundary=boundary)
    step = api_compile(prog, Target(mesh=mesh, strategy=make_strategy_2d((4, 2))))
    got = step(u0, np.zeros(shape, np.float32))
    check(f"2d-{boundary}", got[0], want)


def scenario_3d():
    shape = (16, 16, 32)
    u0, want = run_single(_jacobi, shape, "periodic")
    mesh = _mesh((2, 2, 2), ("x", "y", "z"))
    prog = _jacobi(shape).finish(boundary="periodic")
    step = api_compile(prog, Target(mesh=mesh, strategy=make_strategy_3d((2, 2, 2))))
    got = step(u0, np.zeros(shape, np.float32))
    check("3d-periodic", got[0], want)


def scenario_box(diagonal):
    """Corner-reading stencil under 2D decomposition; with/without the
    beyond-paper diagonal-exchange rewrite."""
    shape = (32, 32)
    u0, want = run_single(_box, shape, "periodic")
    mesh = _mesh((2, 2), ("x", "y"))
    prog = _box(shape).finish(boundary="periodic")
    step = api_compile(
        prog,
        Target(mesh=mesh, strategy=make_strategy_2d((2, 2)), diagonal=diagonal),
    )
    got = step(u0, np.zeros(shape, np.float32))
    check(f"box-diagonal={diagonal}", got[0], want)


def scenario_options(opt):
    """overlap / explicit pipeline spec / pallas backend under distribution."""
    shape = (32, 64)
    u0, want = run_single(_jacobi, shape, "periodic")
    mesh = _mesh((4, 2), ("x", "y"))
    prog = _jacobi(shape).finish(boundary="periodic")
    kw = {}
    tol = 0.0
    if opt == "pallas":
        kw["backend"] = "pallas"
        tol = 1e-6
    elif opt == "pipeline-spec":
        # the canonical spec written out explicitly (replaces the removed
        # comm_dialect flag): must equal the flag-denoted default pipeline
        kw["pipeline"] = "fuse,cse,dce,decompose,swap-elim,lower-comm"
    else:
        kw[opt] = True
    step = api_compile(
        prog, Target(mesh=mesh, strategy=make_strategy_2d((4, 2)), **kw)
    )
    got = step(u0, np.zeros(shape, np.float32))
    check(f"options-{opt}", got[0], want, tol=tol)


def scenario_overlap_matrix(boundary, builder="jacobi", diagonal=False,
                            backend="jnp"):
    """split_overlapped_applies equivalence: overlap=True crossed with
    boundary × schedule (star=concurrent, box=sequential/diagonal) ×
    backend on a 2-D grid — distributed must stay bitwise-equal."""
    shape = (32, 32)
    builder_fn = _jacobi if builder == "jacobi" else _box
    u0, want = run_single(builder_fn, shape, boundary)
    mesh = _mesh((2, 2), ("x", "y"))
    prog = builder_fn(shape).finish(boundary=boundary)
    step = api_compile(
        prog,
        Target(mesh=mesh, strategy=make_strategy_2d((2, 2)),
               overlap=True, diagonal=diagonal, backend=backend),
    )
    got = step(u0, np.zeros(shape, np.float32))
    tol = 1e-6 if backend == "pallas" else 0.0
    check(
        f"overlap-{builder}-{boundary}-diag={diagonal}-{backend}",
        got[0], want, tol=tol,
    )
    # the overlap structure must be visible in the lowered IR
    from repro.core.dialects import comm, stencil

    names = [op.name for op in step.local_ir.body.ops]
    assert "comm.exchange_start" in names and "stencil.combine" in names, names
    first_apply = names.index("stencil.apply")
    assert names.index("comm.exchange_start") < first_apply < names.index(
        "comm.wait"
    ), f"interior apply not between starts and wait: {names}"


def scenario_wide_halo():
    """SDO-8 stencil (radius 4): halo wider than 1, both directions."""
    shape = (64, 64)
    g = Grid(shape=shape, extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=8)
    op = Operator(Eq(u.dt, 0.3 * u.laplace), dt=1e-6, boundary="periodic")
    rng = np.random.default_rng(3)
    u0 = rng.standard_normal(shape).astype(np.float32)
    want = np.asarray(op.apply([u0], timesteps=2)[0])

    mesh = _mesh((4, 2), ("x", "y"))
    got = np.asarray(
        op.apply(
            [u0], timesteps=2, mesh=mesh, strategy=make_strategy_2d((4, 2))
        )[0]
    )
    check("wide-halo-sdo8", got, want)


def _step_n(step, u0, shape, n):
    """n single steps with explicit rotation (p == q == 1 programs)."""
    u = u0
    for _ in range(n):
        u = np.asarray(step(u, np.zeros(shape, np.float32))[0])
    return u


def scenario_exchange_every(k, boundary, overlap=False, backend="jnp",
                            builder="jacobi", steps=8):
    """Deep-halo temporal tiling under a real mesh: a depth-k epoch
    (exchange once, step k times, redundant boundary compute) must stay
    bitwise-equal to k single-exchange steps — crossed with overlap
    (interior of step 1 rides the deep exchange) and backend."""
    shape = (32, 32)
    builder_fn = _jacobi if builder == "jacobi" else _box
    prog = builder_fn(shape).finish(boundary=boundary)
    rng = np.random.default_rng(42)
    u0 = rng.standard_normal(shape).astype(np.float32)
    want = _step_n(api_compile(prog), u0, shape, steps)

    mesh = _mesh((2, 2), ("x", "y"))
    base = api_compile(
        prog, Target(mesh=mesh, strategy=make_strategy_2d((2, 2)),
                     overlap=overlap, backend=backend)
    )
    tiled = api_compile(
        prog, Target(mesh=mesh, strategy=make_strategy_2d((2, 2)),
                     overlap=overlap, backend=backend, exchange_every=k)
    )
    got = u0
    for _ in range(steps // k):
        got = np.asarray(tiled(got, np.zeros(shape, np.float32))[0])
    tol = 1e-6 if backend == "pallas" else 0.0
    check(
        f"exchange-every-{builder}-{boundary}-k{k}-overlap={overlap}-{backend}",
        got, want, tol=tol,
    )
    # one exchange volley per k-step epoch: the tiled IR must not carry
    # more exchange_start ops than the single-step IR (let alone k×)
    from repro.core.dialects import comm

    def starts(s):
        return sum(
            1 for op in s.local_ir.body.ops
            if isinstance(op, comm.ExchangeStartOp)
        )

    assert starts(tiled) <= starts(base), (starts(tiled), starts(base))
    if overlap:
        names = [op.name for op in tiled.local_ir.body.ops]
        first_apply = names.index("stencil.apply")
        assert names.index("comm.exchange_start") < first_apply < names.index(
            "comm.wait"
        ), f"step-1 interior does not overlap the deep exchange: {names}"


def scenario_heat_epoch():
    """ISSUE 4 acceptance: the fig7 heat kernel on a 4-shard mesh with
    exchange_every=4 emits exactly ONE exchange pair per 4-step epoch
    (asserted on .local_ir) and is bitwise-equal to exchange_every=1
    over 32 steps."""
    shape = (64, 32)
    g = Grid(shape=shape, extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=2)
    dt = 0.1 * (g.spacing[0] ** 2) / 0.5
    op = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="periodic")
    rng = np.random.default_rng(8)
    u0 = rng.standard_normal(shape).astype(np.float32)
    want = np.asarray(op.apply([u0], timesteps=32)[0])

    import jax.numpy as jnp

    mesh = _mesh((4,), ("x",))
    tiled = api_compile(
        op.program,
        Target(mesh=mesh, strategy=make_strategy_1d(4), exchange_every=4),
    )
    got = np.asarray(tiled.time_loop((jnp.asarray(u0),), 32)[0])
    from repro.core.dialects import comm

    starts = [
        o for o in tiled.local_ir.body.ops
        if isinstance(o, comm.ExchangeStartOp)
    ]
    waits = [
        o for o in tiled.local_ir.body.ops if isinstance(o, comm.WaitOp)
    ]
    # 1-D decomposition: one send/recv pair (low + high face) per epoch
    assert len(starts) == 2 and len(waits) == 1, (len(starts), len(waits))
    check("heat-epoch-k4-32steps", got, want)


def scenario_tune_4rank():
    """ISSUE 5 acceptance: measured autotuning on a 4-shard mesh — every
    rank selects the identical winner (deterministic search + one shared
    timing vector), the winner's measured per-step time is ≤ the default
    ``Target.auto()`` config's, and a second tune() is a persistent
    disk-cache hit that reproduces the winner."""
    import tempfile

    os.environ["REPRO_TUNE_CACHE"] = tempfile.mkdtemp(prefix="repro-tune-dist-")
    from repro.tune import cache_stats, tune

    shape = (64, 32)
    prog = _jacobi(shape).finish(boundary="periodic")
    kwargs = dict(
        ranks=4, measure=True, steps=4, trials=2, warmup=1,
        backends=("jnp",), exchange_every=(1, 2, 4), overlap=(False, True),
        device_kind="TPU v5 lite",  # the CPU models a v5e
    )
    res = tune(prog, **kwargs)
    assert not res.from_cache and cache_stats().stores == 1

    measured = [c for c in res.candidates if c.measured_s is not None]
    assert res.winner in measured, "winner must come from the measured set"
    assert all(res.winner.measured_s <= c.measured_s for c in measured)
    baseline = [c for c in measured if c.origin == "baseline"]
    assert baseline, "the Target.auto() default must always be measured"
    assert res.winner.measured_s <= baseline[0].measured_s, (
        res.winner.measured_s, baseline[0].measured_s,
    )

    # all ranks agree: the search is deterministic given the agreed
    # timing vector, and the second call reads the identical winner back
    # from the on-disk cache
    res2 = tune(prog, **kwargs)
    assert res2.from_cache and cache_stats().hits == 1
    assert res2.target.fingerprint == res.winner.fingerprint

    # the tuned winner is still *correct*: bitwise vs single-device
    u0, want = run_single(_jacobi, shape, "periodic")
    k = res.target.exchange_every
    steps = 4  # every candidate k ∈ {1,2,4} divides 4
    assert steps % k == 0
    got = u0
    tuned = api_compile(prog, res.target)
    for _ in range(steps // k):
        got = np.asarray(tuned(got, np.zeros(shape, np.float32))[0])
    ref = _step_n(api_compile(prog), u0, shape, steps)
    check(f"tune-4rank-winner-k{k}", got, ref)
    print(f"ok: tune-4rank (winner {res.winner.describe()}, "
          f"{len(measured)} measured)")


def scenario_pallas_tile_shard_error():
    """Satellite: a pallas_tile that does not divide the *local shard*
    is rejected at compile() with an error naming the tile, the shard
    shape, and the mesh axis — not by the assert in core/lowering."""
    from repro.api import TargetError

    shape = (64, 32)
    prog = _jacobi(shape).finish(boundary="periodic")
    mesh = _mesh((4,), ("x",))
    # global 64 over 4 ranks → shard (16, 32); tile 7 does not divide 16
    bad = Target(
        mesh=mesh, strategy=make_strategy_1d(4),
        backend="pallas", pallas_tile=(7, 32),
    )
    try:
        api_compile(prog, bad)
    except TargetError as e:
        msg = str(e)
        for needle in ("(7, 32)", "(16, 32)", "mesh axis 'x'"):
            assert needle in msg, f"{needle!r} missing from: {msg}"
        print("ok: pallas-tile-shard-error")
    else:
        print("MISSING TargetError for shard-nondividing pallas_tile")
        sys.exit(1)
    # the same global tile on a single device divides (64, 32): valid —
    # proof the check is shard-aware, not global-shape-aware
    ok = Target(backend="pallas", pallas_tile=(16, 32))
    api_compile(prog, ok)
    print("ok: pallas-tile-shard-aware")


def scenario_time_loop():
    """Many timesteps under fori_loop + distribution (the fig. 8 path)."""
    shape = (64, 32)
    g = Grid(shape=shape, extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=4)
    op = Operator(Eq(u.dt, 0.5 * u.laplace), dt=1e-6, boundary="zero")
    rng = np.random.default_rng(4)
    u0 = rng.standard_normal(shape).astype(np.float32)
    want = np.asarray(op.apply([u0], timesteps=20)[0])
    mesh = _mesh((8,), ("x",))
    got = np.asarray(
        op.apply([u0], timesteps=20, mesh=mesh, strategy=make_strategy_1d(8))[0]
    )
    check("time-loop-20", got, want)


def _wave(shape):
    """p=2 inputs > q=1 output — carried-state rotation under resume."""
    p = ProgramBuilder("wave_res", shape)
    um = p.input("u_prev")
    u0 = p.input("u_now")
    out = p.output("u_next")
    tm, t0 = p.load(um), p.load(u0)
    r = p.apply(
        [tm, t0],
        lambda b, um, u0: 2.0 * u0.at(0, 0) - um.at(0, 0)
        + 0.1 * (
            u0.at(-1, 0) + u0.at(1, 0) + u0.at(0, -1) + u0.at(0, 1)
            - 4.0 * u0.at(0, 0)
        ),
    )
    p.store(r, out)
    return p


def scenario_resilience_reshape(builder="jacobi", k=4, steps=32):
    """ISSUE 8 acceptance: a FaultPlan-killed 4-rank run resumed onto a
    2-rank mesh (different factorization AND rank count) finishes
    bitwise-identical to both the uninterrupted 4-rank resilient run and
    the single-device time_loop reference — for k ∈ {1, 4}, heat + wave."""
    import shutil
    import tempfile

    from repro.resilience import FaultPlan, ResilientLoop, SimulatedFault, resume

    shape = (64, 32)
    builder_fn = _jacobi if builder == "jacobi" else _wave
    prog = builder_fn(shape).finish(
        boundary="periodic" if builder == "jacobi" else "zero"
    )
    rng = np.random.default_rng(13)
    n_in = 1 if builder == "jacobi" else 2
    state0 = tuple(
        rng.standard_normal(shape).astype(np.float32) for _ in range(n_in)
    )

    # single-device reference over the full horizon
    ref = api_compile(prog, Target(exchange_every=k)).time_loop(state0, steps)
    ref = tuple(np.asarray(a) for a in (ref if isinstance(ref, tuple) else (ref,)))

    big = Target(
        mesh=_mesh((4,), ("x",)), strategy=make_strategy_1d(4),
        exchange_every=k,
    )
    small = Target(
        mesh=_mesh((2,), ("x",)), strategy=make_strategy_1d(2),
        exchange_every=k,
    )

    d = tempfile.mkdtemp(prefix="repro-res-")
    try:
        # uninterrupted resilient run on the big mesh
        full = ResilientLoop(
            prog, big, state0, steps, directory=os.path.join(d, "full"),
            checkpoint_every=1,
        ).run()
        for i, (g, w) in enumerate(zip(full, ref)):
            check(f"res-{builder}-k{k}-uninterrupted-b{i}", g, w)

        # killed mid-run on 4 ranks, resumed onto 2 ranks
        kill = (steps // k) // 2
        loop = ResilientLoop(
            prog, big, state0, steps, directory=os.path.join(d, "killed"),
            checkpoint_every=1, fault_plan=FaultPlan(kill_at_epoch=kill),
        )
        try:
            loop.run()
            print(f"MISSING SimulatedFault at epoch {kill}")
            sys.exit(1)
        except SimulatedFault:
            pass
        resumed = resume(prog, os.path.join(d, "killed"), small)
        assert resumed.step_count == kill * k, (resumed.step_count, kill, k)
        got = resumed.run()
        for i, (g, w) in enumerate(zip(got, ref)):
            check(f"res-{builder}-k{k}-4to2ranks-b{i}", g, w)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def scenario_tune_transfer():
    """Cross-hardware-signature warm start: a winner tuned at 2 ranks
    transfers to a 4-rank job (the rank count is part of the hardware
    signature, so an elastic resize IS a transfer), counts as a
    transfer_hit (never a hit), and reuses the stored winner verbatim."""
    import tempfile

    os.environ["REPRO_TUNE_CACHE"] = tempfile.mkdtemp(prefix="repro-tune-xfer-")
    from repro.tune import cache_stats, reset_cache_stats, tune

    shape = (64, 32)
    prog = _jacobi(shape).finish(boundary="periodic")
    kwargs = dict(
        measure=False, backends=("jnp",), exchange_every=(1, 2),
        overlap=(False,), fused_epoch=(False,),
        device_kind="TPU v5 lite",  # the CPU models a v5e
    )
    reset_cache_stats()  # counters are process-wide; earlier scenarios tune
    res2 = tune(prog, ranks=2, **kwargs)
    assert not res2.from_cache and cache_stats().stores == 1

    # 4-rank primary key misses; with transfer=True the 2-rank winner is
    # adopted (its mesh rebuilds on this inventory's device prefix)
    reset_cache_stats()
    moved = tune(prog, ranks=4, transfer=True, **kwargs)
    s = cache_stats().as_dict()
    assert moved.from_cache and moved.winner.origin == "transfer", (
        moved.from_cache, moved.winner.origin,
    )
    assert s["transfer_hits"] == 1 and s["hits"] == 0 and s["stores"] == 0, s
    assert moved.target.fingerprint == res2.target.fingerprint

    # without transfer the same miss falls through to a fresh search
    reset_cache_stats()
    fresh = tune(prog, ranks=4, **kwargs)
    s = cache_stats().as_dict()
    assert not fresh.from_cache and s["transfer_hits"] == 0, s
    print("ok: tune-transfer")


def scenario_slot_axis():
    """ISSUE 9 tentpole oracle: a slot-axis pooled Target (shard_map over
    ``(slot, *spatial)``, vmap inside) advances a ``[B, *shape]`` batch
    bitwise-identically to B per-slot solo dispatches of the spatial-only
    sibling — for k ∈ {1, 2} and both boundaries, and across slot widths
    that do (4) and do not (2 with B=4) equal the batch size."""
    from repro.api import TargetError, pooled_target

    shape = (32, 32)
    B = 4
    for boundary, k, slots in (("zero", 1, 4), ("periodic", 2, 2)):
        prog = _jacobi(shape).finish(boundary=boundary)
        solo_t = Target(
            mesh=_mesh((2,), ("x",)), strategy=make_strategy_1d(2),
            exchange_every=k,
        )
        pooled_t = pooled_target(solo_t, slots=slots)
        assert pooled_t.fingerprint != solo_t.fingerprint
        assert pooled_t.mesh.shape["slot"] == slots
        solo = api_compile(prog, solo_t)
        pooled = api_compile(prog, pooled_t)
        rng = np.random.default_rng(7)
        u = rng.standard_normal((B,) + shape).astype(np.float32)
        got = pooled.time_loop((u,), 8)
        got = np.asarray(got[0] if isinstance(got, tuple) else got)
        want = np.stack([
            np.asarray(
                (lambda r: r[0] if isinstance(r, tuple) else r)(
                    solo.time_loop((u[i],), 8)
                )
            )
            for i in range(B)
        ])
        check(f"slot-axis-{boundary}-k{k}-s{slots}", got, want)
    # validation: a slot axis colliding with a spatial axis is rejected
    try:
        Target(
            mesh=_mesh((2,), ("x",)), strategy=make_strategy_1d(2),
            slot_axis="x",
        )
        print("MISSING TargetError for colliding slot_axis")
        sys.exit(1)
    except TargetError:
        print("ok: slot-axis collision rejected")


def scenario_serve_pooled():
    """ISSUE 9 acceptance: a 2-rank distributed bucket with 4 live slots
    executes as ONE pooled dispatch per engine step (per-bucket counters:
    batched > 0, solo == 0) and every request's final state is
    bitwise-equal to its solo ``time_loop``."""
    from repro.serve.stencil import StencilEngine, StencilEngineConfig

    shape = (32, 32)
    prog = _jacobi(shape).finish(boundary="periodic")
    target = Target(mesh=_mesh((2,), ("x",)), strategy=make_strategy_1d(2))
    rng = np.random.default_rng(3)
    states = [rng.standard_normal(shape).astype(np.float32) for _ in range(4)]
    eng = StencilEngine(StencilEngineConfig(slots_per_group=4))
    # equal n_steps: the bucket stays at 4 live slots every dispatch
    hs = [eng.submit(prog, (s,), 8, target=target) for s in states]
    done = eng.run()
    assert len(done) == 4, len(done)
    bd = eng.metrics.bucket_dispatches[
        f"{prog.fingerprint}/{target.fingerprint}"
    ]
    assert bd["batched"] > 0 and bd["solo"] == 0, bd
    solo = api_compile(prog, target)
    for h, s in zip(hs, states):
        want = solo.time_loop((s,), 8)
        want = np.asarray(want[0] if isinstance(want, tuple) else want)
        check(f"serve-pooled-rid{h.rid}", np.asarray(h.result()[0]), want)
    print(f"ok: serve-pooled counters {bd}")


def scenario_serve_autoscale():
    """ISSUE 9 acceptance: a queue burst against a small distributed
    bucket forces ≥1 autoscale grow, the long tail forces ≥1 shrink,
    every event carries queue-depth/utilization provenance, and every
    request's final state stays bitwise-equal across the resizes."""
    from repro.serve.stencil import (
        PoolSizerConfig,
        StencilEngine,
        StencilEngineConfig,
    )

    shape = (32, 32)
    prog = _jacobi(shape).finish(boundary="periodic")
    target = Target(mesh=_mesh((2,), ("x",)), strategy=make_strategy_1d(2))
    rng = np.random.default_rng(5)
    states = [rng.standard_normal(shape).astype(np.float32) for _ in range(8)]
    steps = [8] * 7 + [48]
    eng = StencilEngine(
        StencilEngineConfig(
            slots_per_group=2,
            autoscale=PoolSizerConfig(
                min_capacity=1, max_capacity=8, cooldown_steps=1,
                ewma_alpha=1.0,
            ),
        )
    )
    hs = [eng.submit(prog, (s,), n, target=target)
          for s, n in zip(states, steps)]
    eng.run()
    auto = eng.metrics.snapshot()["autoscale"]
    assert auto["grows"] >= 1 and auto["shrinks"] >= 1, auto
    for e in auto["events"]:
        missing = {
            "queue_ewma", "utilization_ewma", "queue_depth", "live",
            "from_capacity", "to_capacity",
        } - set(e)
        assert not missing, f"provenance missing {missing}"
    solo = api_compile(prog, target)
    for h, s, n in zip(hs, states, steps):
        want = solo.time_loop((s,), n)
        want = np.asarray(want[0] if isinstance(want, tuple) else want)
        check(f"serve-autoscale-rid{h.rid}", np.asarray(h.result()[0]), want)
    print(f"ok: serve-autoscale grows={auto['grows']} "
          f"shrinks={auto['shrinks']}")


def scenario_scopes_2x2(k):
    """Every collective-permute of the compiled 2x2 step lies under the
    ``comm.exchange_start`` scope, and every halo write (the step's
    dynamic-update-slices) under ``comm.wait``: the device trace names
    the exchange by IR op, not by XLA's generated op names."""
    import re

    grid = Grid(shape=(64, 64))
    u = TimeFunction(name="u", grid=grid, space_order=8)
    prog = Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program
    mesh = _mesh((2, 2), ("x", "y"))
    step = api_compile(prog, Target(mesh=mesh, strategy=make_strategy_2d((2, 2)),
                                    exchange_every=k))
    by_opcode = {}
    for line in step.lower().compile().as_text().splitlines():
        m = re.search(r"= \S+ ([a-z-]+)\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            by_opcode.setdefault(m.group(1), []).append(
                name.group(1) if name else None)
    permutes = by_opcode.get("collective-permute", []) + by_opcode.get(
        "collective-permute-start", [])
    writes = by_opcode.get("dynamic-update-slice", [])
    assert permutes and writes, sorted(by_opcode)
    for name in permutes:
        assert name and "/comm.exchange_start/" in name, name
    for name in writes:
        assert name and "/comm.wait/" in name, name
    assert all(n.startswith(f"jit({prog.name}.step)/") for n in permutes + writes)
    print(f"ok: scopes-2x2-k{k} ({len(permutes)} permutes, "
          f"{len(writes)} halo writes)")


def scenario_pallas_2x2_received_halo():
    """The 2x2 heat cell's path at a small grid: every shard receives a
    halo, so its ``comm.halo_pad`` keeps the copy (``halo_pad_copies``
    1) while the Pallas kernel reads that copy with its last windows'
    overhang as Mosaic high padding; 4 jitted steps equal the jnp
    lowering's on the same mesh, bitwise."""
    grid = Grid(shape=(256, 512))
    u = TimeFunction(name="u", grid=grid, space_order=8)
    prog = Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program
    mesh = _mesh((2, 2), ("x", "y"))
    strategy = make_strategy_2d((2, 2))
    fast = api_compile(prog, Target(mesh=mesh, strategy=strategy,
                                    backend="pallas", pallas_tile=(64, 128)))
    slow = api_compile(prog, Target(mesh=mesh, strategy=strategy, backend="jnp"))
    n = fast.kernel_dispatches
    assert n["halo_pad"] == 1 and n["halo_pad_copies"] == 1, n
    assert fast.loop_unroll == 1
    u0 = (np.random.default_rng(5).standard_normal((256, 512)).astype(np.float32),)
    got = jax.jit(lambda s: fast.time_loop(s, 4))(u0)
    want = jax.jit(lambda s: slow.time_loop(s, 4))(u0)
    check("pallas-2x2-received-halo", got[0], want[0])


def scenario_1d_backward(boundary):
    """A 1-D program that reads only backwards, ``out[i] = u[i] + 0.5 u[i-1]
    - 0.25 u[i-3]``, on 8 ranks: each shard receives a 3-point halo from
    its lower neighbour and sends none the other way (one exchange, where
    a centred stencil needs two).  At a zero boundary rank 0 receives
    zeros; at a periodic one it receives the last rank's tail.  One step
    and a 6-step ``time_loop`` equal one device's, bitwise."""
    shape = (256,)

    def build():
        p = ProgramBuilder("backward", shape)
        u = p.input("u")
        out = p.output("out")
        r = p.apply([p.load(u)],
                    lambda b, u: u.at(0) + u.at(-1) * 0.5 - u.at(-3) * 0.25)
        p.store(r, out)
        return p.finish(boundary=boundary)

    u0 = np.random.default_rng(6).standard_normal(shape).astype(np.float32)
    single = api_compile(build())
    want = np.asarray(single(u0, np.zeros_like(u0))[0])
    rolled = u0 + np.roll(u0, 1) * 0.5 - np.roll(u0, 3) * 0.25
    if boundary == "zero":
        rolled[0] = u0[0]
        rolled[1:3] = u0[1:3] + u0[0:2] * 0.5
    assert np.allclose(want, rolled, rtol=1e-6, atol=1e-6)
    step = api_compile(build(), Target(mesh=_mesh((8,), ("x",)),
                                       strategy=make_strategy_1d(8)))
    starts = [op for op in step.local_ir.body.ops
              if op.name == "comm.exchange_start"]
    assert len(starts) == 1, [op.name for op in step.local_ir.body.ops]
    check(f"1d-backward-{boundary}", step(u0, np.zeros_like(u0))[0], want)
    check(f"1d-backward-{boundary}-loop",
          step.time_loop((u0,), 6)[0], single.time_loop((u0,), 6)[0])


SCENARIOS = {
    "1d-zero": lambda: scenario_1d("zero"),
    "1d-periodic": lambda: scenario_1d("periodic"),
    "1d-backward-zero": lambda: scenario_1d_backward("zero"),
    "1d-backward-periodic": lambda: scenario_1d_backward("periodic"),
    "2d-zero": lambda: scenario_2d("zero"),
    "2d-periodic": lambda: scenario_2d("periodic"),
    "3d": scenario_3d,
    "box": lambda: scenario_box(False),
    "box-diagonal": lambda: scenario_box(True),
    "overlap": lambda: scenario_options("overlap"),
    "overlap-zero": lambda: scenario_overlap_matrix("zero"),
    "overlap-periodic": lambda: scenario_overlap_matrix("periodic"),
    "overlap-box-seq": lambda: scenario_overlap_matrix("periodic", "box"),
    "overlap-diagonal": lambda: scenario_overlap_matrix(
        "periodic", "box", diagonal=True
    ),
    "overlap-pallas": lambda: scenario_overlap_matrix(
        "periodic", backend="pallas"
    ),
    "pipeline-spec": lambda: scenario_options("pipeline-spec"),
    "pallas": lambda: scenario_options("pallas"),
    "wide-halo": scenario_wide_halo,
    "time-loop": scenario_time_loop,
    # deep-halo temporal tiling: exchange_every × overlap × backend
    "ee2-periodic": lambda: scenario_exchange_every(2, "periodic"),
    "ee4-zero": lambda: scenario_exchange_every(4, "zero"),
    "ee4-overlap": lambda: scenario_exchange_every(4, "periodic", overlap=True),
    "ee4-overlap-zero": lambda: scenario_exchange_every(4, "zero", overlap=True),
    "ee2-box-overlap": lambda: scenario_exchange_every(
        2, "periodic", overlap=True, builder="box"
    ),
    "ee4-pallas": lambda: scenario_exchange_every(
        4, "periodic", backend="pallas"
    ),
    "ee-heat-epoch": scenario_heat_epoch,
    # repro.tune: measured autotuning under a real mesh + shard-aware
    # pallas_tile validation
    "tune-4rank": scenario_tune_4rank,
    "pallas-tile-shard-error": scenario_pallas_tile_shard_error,
    # repro.resilience: killed on 4 ranks, resumed onto 2 (elastic) —
    # bitwise vs the uninterrupted run and the single-device reference
    "resilience-heat-k1": lambda: scenario_resilience_reshape("jacobi", k=1),
    "resilience-heat-k4": lambda: scenario_resilience_reshape("jacobi", k=4),
    "resilience-wave-k4": lambda: scenario_resilience_reshape("wave", k=4),
    "tune-transfer": scenario_tune_transfer,
    # ISSUE 9 — elastic slot pools: slot-axis compile oracle, pooled
    # distributed serving, queue-depth autoscaling (all bitwise vs solo)
    "slot-axis": scenario_slot_axis,
    "serve-pooled": scenario_serve_pooled,
    "serve-autoscale": scenario_serve_autoscale,
    # device ops named by the IR op that emitted them (named scopes)
    "scopes-2x2-k1": lambda: scenario_scopes_2x2(1),
    "scopes-2x2-k4": lambda: scenario_scopes_2x2(4),
    # a halo received from neighbours keeps its pad's copy
    "pallas-2x2-received-halo": scenario_pallas_2x2_received_halo,
}


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    names = list(SCENARIOS) if which == "all" else [which]
    for n in names:
        SCENARIOS[n]()
    print("ALL OK")

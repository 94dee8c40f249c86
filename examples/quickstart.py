"""Quickstart: the paper's listing-5 experience on the JAX/TPU stack,
through the one compile surface — ``Program`` / ``Target`` / ``compile``.

1. model 2-D heat diffusion symbolically (Devito-like DSL) — the
   frontend produces a ``repro.api.Program`` (frontend-neutral IR);
2. describe *where and how* to run with a ``repro.api.Target`` (device
   mesh + decomposition strategy + backend + pipeline knobs);
3. ``repro.api.compile(program, target)`` returns a ``CompiledStencil``
   — a reusable artifact cached process-wide on (program fingerprint,
   target fingerprint), so compiling the same program twice is free.

    PYTHONPATH=src python examples/quickstart.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py --ranks 8
"""
import argparse

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=1)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--tune", action="store_true",
                    help="let repro.tune pick the Target (cost-model "
                         "search, persisted in ~/.cache/repro-tune)")
    args = ap.parse_args()

    import jax.numpy as jnp

    import repro
    from repro import compile_cache
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
    from repro.launch.roofline import V5E

    compile_cache.enable()

    # -- 1. model the problem (paper listing 5) → Program ------------------
    grid = Grid(shape=(args.size, args.size), extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=grid, space_order=2)
    eqn = Eq(u.dt, 0.5 * u.laplace)
    # explicit-Euler stability: dt <= h²/(4·alpha); run at 80% of it
    dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
    op = Operator(eqn, dt=dt, boundary="zero")
    prog = op.program
    print(f"program: {prog.name} fields={list(prog.field_names)} "
          f"fingerprint={prog.fingerprint}")

    # -- 2. describe the target -------------------------------------------
    # Target.auto() discovers devices (1-D decomposition over all of them);
    # an explicit Target(mesh=..., strategy=...) pins the layout; and
    # Target.tuned(prog) searches the whole space (mesh factorization,
    # overlap, exchange_every, backend, tile) with the roofline model —
    # the winner persists on disk, so the search runs once per machine:
    #
    #     target = repro.Target.tuned(prog)               # measured search
    #     target = repro.Target.tuned(prog, measure=False)  # cost model only
    #     step = repro.api.compile(prog, tune=True)         # tune + compile
    if args.tune:
        # the cost model's peaks are the v5e's (launch/roofline PEAKS)
        target = repro.Target.tuned(
            prog, ranks=args.ranks, measure=False, device_kind=V5E
        )
        print(f"tuned target: backend={target.backend} "
              f"exchange_every={target.exchange_every} "
              f"overlap={target.overlap} distributed={target.distributed}")
    else:
        target = repro.Target.auto(ranks=args.ranks)
    if target.distributed:
        print(f"decomposed over {args.ranks} ranks (1-D slabs + halo swaps)")

    # -- 3. compile → CompiledStencil --------------------------------------
    step = repro.compile(prog, target)
    print(step.pipeline_report)

    # a second compile of the same program+target is a cache hit: the
    # pass pipeline does not re-run and the artifact is the same object
    again = repro.compile(op.program, target)
    stats = repro.cache_stats()
    print(f"recompile: cached={again is step} "
          f"(cache hits={stats.hits} misses={stats.misses})")

    # -- initial condition: hot square in the center ----------------------
    u0 = np.zeros(grid.shape, np.float32)
    c = args.size // 2
    u0[c - 8 : c + 8, c - 8 : c + 8] = 1.0

    # a depth-k tuned artifact advances whole epochs: round the step
    # count up to a multiple of k
    k = target.exchange_every
    if args.steps % k:
        args.steps += k - args.steps % k
    (uT,) = step.time_loop([jnp.asarray(u0)], args.steps)
    uT = np.asarray(uT)

    print(f"steps={args.steps}  total heat: {u0.sum():.3f} -> {uT.sum():.3f}")
    print(f"peak: {u0.max():.3f} -> {uT.max():.3f} (diffused)")
    assert np.isfinite(uT).all()

    # -- observability: a profile with host spans (DESIGN.md §12) ---------
    # obs.enable() turns on the host spans (api.compile, time_loop, ...);
    # jax.profiler.trace records them beside the device ops, whose
    # op_name metadata names the IR op that emitted them (stencil.apply,
    # comm.halo_pad, ...).  Open the .xplane.pb in XProf or Perfetto.
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro import obs

    obs.enable()
    with jax.profiler.trace("results/quickstart_trace"):
        jax.block_until_ready(step.time_loop([jnp.asarray(u0)], 2 * k))
    obs.disable()
    path = sorted(glob.glob("results/quickstart_trace/**/*.xplane.pb",
                            recursive=True))[-1]
    spans = [
        (e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name == "time_loop"
    ]
    print(f"profile {path}: {spans}")
    print(f"steps traced so far: {obs.snapshot()['compile']['step_traces']}")

    # -- serving: many tenants, one engine (DESIGN.md §9) ------------------
    # StencilEngine batches same-fingerprint requests into ONE vmapped
    # dispatch over a slot pool; results stay bitwise-equal to the solo
    # time_loop above.  frame_every streams intermediate states.
    from repro.serve.stencil import StencilEngine

    eng = StencilEngine()
    handles = [
        eng.submit(prog, (jnp.asarray(u0),), n_steps=4 * k, target=target,
                   frame_every=2 * k, tenant=f"tenant{i}")
        for i in range(3)
    ]
    eng.run()
    served = np.asarray(handles[0].result()[0])
    solo = np.asarray(step.time_loop([jnp.asarray(u0)], 4 * k)[0])
    snap = eng.metrics.snapshot()
    print(f"served 3 tenants in {snap['engine_steps']} engine steps "
          f"({snap['batched_dispatches']} batched dispatches, "
          f"{snap['frames_emitted']} frames); "
          f"bitwise-equal to solo: {np.array_equal(served, solo)}")

    # -- elastic pools: an ensemble burst (DESIGN.md §9) -------------------
    # an ensemble study lands as a same-instant burst of one fingerprint:
    # the queue-depth autoscaler grows the slot pool to meet it, shrinks
    # it on the long tail (resizes ride the checkpoint-migration path, so
    # results stay bitwise), and the drained bucket retires — its pooled
    # device arrays freed.
    from repro.serve.stencil import (
        PoolSizerConfig, StencilEngine as _Eng, StencilEngineConfig,
    )

    burst_eng = _Eng(StencilEngineConfig(
        slots_per_group=2,
        autoscale=PoolSizerConfig(min_capacity=1, max_capacity=8,
                                  cooldown_steps=1, ewma_alpha=1.0),
        bucket_idle_steps=4,
    ))
    rng = np.random.default_rng(0)
    members = [u0 + 0.01 * rng.standard_normal(grid.shape).astype(np.float32)
               for _ in range(8)]
    # most members run short; the last runs long, so after the burst
    # drains the pool sits underutilized and the autoscaler shrinks it
    member_steps = [4 * k] * 7 + [24 * k]
    burst_handles = [
        burst_eng.submit(prog, (jnp.asarray(m),), n_steps=n,
                         target=target, tenant=f"member{i}")
        for i, (m, n) in enumerate(zip(members, member_steps))
    ]
    burst_eng.run()
    for _ in range(5):  # idle steps: let the drained bucket retire
        burst_eng.step()
    auto = burst_eng.metrics.snapshot()["autoscale"]
    print(f"ensemble burst of {len(burst_handles)}: pool grew "
          f"{auto['grows']}x / shrank {auto['shrinks']}x, "
          f"{burst_eng.metrics.buckets_retired} bucket retired after drain")

    # crude ASCII rendering of the diffused blob
    ds = uT[:: args.size // 32, :: args.size // 32]
    chars = " .:-=+*#%@"
    for row in ds:
        print("".join(chars[int(min(v, 0.999) * 10)] for v in row))


if __name__ == "__main__":
    main()

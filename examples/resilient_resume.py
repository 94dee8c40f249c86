"""Resilient resume quickstart: survive a mid-run kill, continue on a
different rank count, end bitwise-identical (DESIGN.md §11).

A long stencil run wrapped in ``repro.resilience.ResilientLoop``
snapshots its global state every ``checkpoint_every`` epochs.  When the
process dies — here deterministically, via an injected ``FaultPlan`` —
``resume()`` picks up from the last committed snapshot, optionally onto
a *different* mesh factorization, and the final state is bitwise-equal
to the run that was never interrupted.

    PYTHONPATH=src python examples/resilient_resume.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/resilient_resume.py --ranks 4
"""
import argparse
import tempfile

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=1,
                    help="ranks for the first (killed) run; the resume "
                         "uses half of them when >1")
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--steps", type=int, default=64)
    args = ap.parse_args()

    import repro
    from repro import compile_cache
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
    from repro.resilience import FaultPlan, ResilientLoop, SimulatedFault, resume

    compile_cache.enable()

    # -- the simulation: 2-D heat, depth-4 epochs --------------------------
    grid = Grid(shape=(args.size, args.size), extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=grid, space_order=2)
    dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
    prog = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program

    k = 4
    big = repro.Target.auto(ranks=args.ranks, exchange_every=k)
    u0 = np.zeros(grid.shape, np.float32)
    c = args.size // 2
    u0[c - 8 : c + 8, c - 8 : c + 8] = 1.0

    # uninterrupted reference (plain time_loop — the bitwise oracle)
    want = repro.compile(prog, big).time_loop((u0,), args.steps)
    want = want if isinstance(want, tuple) else (want,)

    # -- run with checkpointing; die mid-run deterministically -------------
    ckpt_dir = tempfile.mkdtemp(prefix="repro-resume-")
    kill = (args.steps // k) // 2
    loop = ResilientLoop(
        prog, big, (u0,), args.steps,
        directory=ckpt_dir, checkpoint_every=1, keep_last=3,
        fault_plan=FaultPlan(kill_at_epoch=kill),  # stands in for preemption
    )
    try:
        loop.run()
    except SimulatedFault as e:
        print(f"killed: {e}")
    print(f"committed snapshots: {loop.checkpointer.available_steps()} "
          f"(stats {loop.checkpointer.stats.as_dict()})")

    # -- resume: same program, possibly a different mesh -------------------
    # the snapshot holds GLOBAL state; resume() reshards it for whatever
    # target you hand it — halve the rank count when we have ranks to halve
    new_ranks = max(1, args.ranks // 2)
    small = repro.Target.auto(ranks=new_ranks, exchange_every=k)
    resumed = resume(prog, ckpt_dir, small)
    print(f"resumed at step {resumed.step_count}/{args.steps} "
          f"on {new_ranks} rank(s)")
    got = resumed.run()

    ok = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(got, want)
    )
    print(f"final state bitwise-equal to the uninterrupted run: {ok}")
    assert ok


if __name__ == "__main__":
    main()

"""Chip smoke test: drive the stencil stack's main path once on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the 2x2 host: decomposed phase only

One chip runs, in order, through the user entry points
(``devito_like.Operator`` -> ``api.compile`` -> ``CompiledStencil.time_loop``
and ``serve.stencil.StencilEngine``):

1. device check: the default device is a TPU and nothing asks for Pallas
   interpret mode;
2. jnp path: 2-D heat, 16384^2 f32, space order 8, zero boundary,
   ``Target(backend="jnp")``, 64 steps, against a plain jitted f32
   reference;
3. per-apply Pallas: the same with ``Target()``, which resolves to
   ``backend="pallas"`` on a TPU, reading its windows from the unpadded
   level and building the zero halo itself (no ``comm.halo_pad`` copy,
   no ``window_source`` copy);
4. fused-epoch Pallas: ``exchange_every=4, fused_epoch=True``, one kernel
   per epoch, bitwise against the unfused Pallas run at the same depth
   (DESIGN.md §10) and within tolerance of the reference;
5. serving: four 8192^2 requests through one ``StencilEngine``, batched,
   bitwise against solo ``time_loop`` runs.

``--chips 4`` runs only the decomposed phase: 16384^2 heat on a 2x2 mesh
at ``exchange_every=1``, ``exchange_every=4`` and ``overlap=True``, each
against ``Target()`` on device 0.

Lines before the last are smoke timings (first call = compile + run,
second call = run), not benchmarks.  The last line of stdout is
``{"ok": true, "device": {...}}``; it is printed only when every phase
passed.  The exit code is non-zero otherwise, and when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
N = 16384           # the paper's 2-D grid (fig. 7)
SERVE_N = 8192
ORDER = 8           # space order: radius-4 star
DT = 0.1            # explicit-Euler step; stable for so8 in 2-D below 0.15
STEPS = 64
SERVE_STEPS = 16
EPS32 = float(np.finfo(np.float32).eps)


def heat_operator(n: int):
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    grid = Grid(shape=(n, n))
    u = TimeFunction(name="u", grid=grid, space_order=ORDER)
    return Operator(Eq(u.dt, u.laplace), dt=DT, boundary="zero")


def initial_state(n: int, seed: int):
    return jax.random.normal(jax.random.key(seed), (n, n), jnp.float32)


def reference(u0, steps: int):
    """Plain f32 heat: pad with the zero boundary, one star update per step."""
    from repro.kernels.ref import heat_step_ref

    r = ORDER // 2

    def body(_, u):
        return heat_step_ref(jnp.pad(u, r), DT, ORDER, r)

    return jax.jit(lambda u: jax.lax.fori_loop(0, steps, body, u))(u0)


def tolerance(u0, steps: int) -> float:
    """Each step rounds a 17-tap sum of |u| <= max|u0| in another order
    than the reference: a few ulp of max|u0| per step.  The scheme is a
    contraction, so the differences add at most linearly over the steps:
    8 ulp per step bounds it with room to spare."""
    return steps * 8 * EPS32 * float(jnp.max(jnp.abs(u0)))


def max_abs_diff(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def timed_loop(compiled, state, steps: int, label: str):
    """Run ``time_loop`` twice and print the smoke timings; returns the
    final newest buffer."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled.time_loop(state, steps))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled.time_loop(state, steps))
    second = time.perf_counter() - t0
    print(f"smoke timing  {label}: first call {first:.3f} s "
          f"(compile + run), second call {second:.3f} s (run)", flush=True)
    return out[-1]


class Smoke:
    """Shared state of the one-chip phases: the operator, the initial
    state and the reference result, made once."""

    def __init__(self) -> None:
        self.op = heat_operator(N)
        self.u0 = initial_state(N, SEED)
        t0 = time.perf_counter()
        self.want = jax.block_until_ready(reference(self.u0, STEPS))
        print(f"smoke timing  reference: {time.perf_counter() - t0:.3f} s",
              flush=True)
        self.tol = tolerance(self.u0, STEPS)
        self.unfused_k4 = None

    def check(self, got, label: str) -> None:
        err = max_abs_diff(got, self.want)
        print(f"{label}: max|got - reference| = {err:.3e} "
              f"(tolerance {self.tol:.3e})", flush=True)
        if not err <= self.tol:
            raise AssertionError(f"{label}: {err} > tolerance {self.tol}")

    def jnp_path(self) -> None:
        from repro import api

        compiled = api.compile(self.op.program, api.Target(backend="jnp"))
        self.check(timed_loop(compiled, (self.u0,), STEPS, "jnp"), "jnp")

    def pallas_apply(self) -> None:
        from repro import api, kernels

        target = api.Target()
        if target.backend != "pallas" or target.pallas_interpret is not False:
            raise AssertionError(
                f"Target() resolved to backend={target.backend!r}, "
                f"pallas_interpret={target.pallas_interpret!r} on a TPU"
            )
        compiled = api.compile(self.op.program, target)
        copies = compiled.kernel_dispatches["halo_pad_copies"]
        kernels.reset_dispatch_stats()
        got = timed_loop(compiled, (self.u0,), STEPS, "pallas per-apply")
        stats = kernels.dispatch_stats()
        print(f"pallas per-apply: {stats.apply_calls} apply kernel(s) traced, "
              f"{stats.window_copies} window copies, {copies} halo pad "
              "copies", flush=True)
        if stats.apply_calls <= 0:
            raise AssertionError("no per-apply Pallas kernel was traced")
        if stats.window_copies:
            raise AssertionError("the kernel copied an operand window")
        if copies:
            raise AssertionError("the zero halo pad still copies the level")
        self.check(got, "pallas per-apply")

    def pallas_fused(self) -> None:
        from repro import api

        unfused = api.compile(
            self.op.program, api.Target(backend="pallas", exchange_every=4)
        )
        fused = api.compile(
            self.op.program,
            api.Target(backend="pallas", exchange_every=4, fused_epoch=True),
        )
        dispatches = fused.kernel_dispatches
        print(f"fused epoch: kernel_dispatches = {dispatches}", flush=True)
        if dispatches["fused_epoch"] != 1:
            raise AssertionError(f"expected one fused kernel per epoch: {dispatches}")
        ref_k4 = timed_loop(unfused, (self.u0,), STEPS, "pallas unfused k=4")
        got = timed_loop(fused, (self.u0,), STEPS, "pallas fused k=4")
        differ = int(jnp.sum(got != ref_k4))
        print(f"fused vs unfused k=4: {differ} differing points, "
              f"max|diff| = {max_abs_diff(got, ref_k4):.3e}", flush=True)
        self.check(got, "pallas fused k=4")
        if differ:
            raise AssertionError(
                f"fused epoch differs from the unfused run at {differ} points"
            )

    def serving(self) -> None:
        from repro import api
        from repro.serve.stencil import StencilEngine, StencilEngineConfig

        op = heat_operator(SERVE_N)
        states = [(initial_state(SERVE_N, SEED + 1 + i),) for i in range(4)]
        engine = StencilEngine(StencilEngineConfig(slots_per_group=4))
        t0 = time.perf_counter()
        handles = [
            engine.submit(op.program, s, SERVE_STEPS, api.Target()) for s in states
        ]
        engine.run()
        results = [jax.block_until_ready(h.result()[-1]) for h in handles]
        print(f"smoke timing  serving: 4 requests in "
              f"{time.perf_counter() - t0:.3f} s (compile + run)", flush=True)
        batched = engine.metrics.batched_dispatches
        print(f"serving: batched_dispatches = {batched}, solo_dispatches = "
              f"{engine.metrics.solo_dispatches}", flush=True)
        if batched <= 0:
            raise AssertionError("the engine made no batched dispatch")
        solo = api.compile(op.program, api.Target())
        for i, (s, got) in enumerate(zip(states, results)):
            want = solo.time_loop(s, SERVE_STEPS)[-1]
            differ = int(jnp.sum(got != want))
            if differ:
                raise AssertionError(
                    f"request {i}: {differ} points differ from solo time_loop "
                    f"(max|diff| = {max_abs_diff(got, want):.3e})"
                )
        print("serving: all 4 results bitwise-equal to solo time_loop", flush=True)


def decomposed() -> None:
    """The 2x2 phase: halo exchanges (comm -> ppermute under shard_map)
    against the single-device program on device 0."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import api
    from repro.core.passes.decompose import make_strategy_2d

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devices)}")
    op = heat_operator(N)
    u0 = jax.device_put(initial_state(N, SEED), devices[0])
    single = api.compile(op.program, api.Target())
    want = timed_loop(single, (u0,), STEPS, "device 0, Target()")
    tol = tolerance(u0, STEPS)
    mesh = Mesh(np.array(devices).reshape(2, 2), ("x", "y"))
    strategy = make_strategy_2d((2, 2))
    u0_sharded = jax.device_put(u0, NamedSharding(mesh, P("x", "y")))
    for label, extra in [
        ("exchange_every=1", {}),
        ("exchange_every=4", {"exchange_every": 4}),
        ("overlap=True", {"overlap": True}),
    ]:
        target = api.Target(mesh=mesh, strategy=strategy, **extra)
        compiled = api.compile(op.program, target)
        got = timed_loop(compiled, (u0_sharded,), STEPS, f"2x2 {label}")
        err = max_abs_diff(jax.device_put(got, devices[0]), want)
        print(f"2x2 {label}: max|got - device 0| = {err:.3e} "
              f"(tolerance {tol:.3e})", flush=True)
        if not err <= tol:
            raise AssertionError(f"2x2 {label}: {err} > tolerance {tol}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (default device is {device.platform!r})",
              file=sys.stderr)
        return 2
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; a chip run never "
              "interprets Pallas kernels", file=sys.stderr)
        return 2
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    print(f"device: {device.device_kind} x {len(jax.devices())}", flush=True)

    if args.chips == 4:
        phases = [("decomposed 2x2", decomposed)]
    else:
        smoke = Smoke()
        phases = [
            ("jnp path", smoke.jnp_path),
            ("per-apply Pallas", smoke.pallas_apply),
            ("fused-epoch Pallas", smoke.pallas_fused),
            ("serving", smoke.serving),
        ]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # noqa: BLE001 - report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAILED", flush=True)
        else:
            print(f"phase {name}: passed ({time.perf_counter() - t0:.1f} s)",
                  flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env bash
# Repo check: fast import smoke over every module, then tier-1 tests.
#
#   scripts/check.sh            # smoke + full tier-1 suite
#   scripts/check.sh --smoke    # smoke only (seconds; used by CI's first job)
#
# Works both with an editable install (pip install -e .) and without
# (falls back to PYTHONPATH=src).
set -euo pipefail
cd "$(dirname "$0")/.."

if ! python -c "import repro" >/dev/null 2>&1; then
  export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
fi

echo "== import smoke: src/repro/** =="
python - <<'EOF'
import importlib
import pathlib
import sys

failed = []
root = pathlib.Path("src")
mods = sorted(
    str(p.relative_to(root).with_suffix("")).replace("/", ".")
    for p in root.glob("repro/**/*.py")
)
for mod in mods:
    name = mod[: -len(".__init__")] if mod.endswith(".__init__") else mod
    try:
        importlib.import_module(name)
    except Exception as e:  # noqa: BLE001 - report every failure
        failed.append((name, f"{type(e).__name__}: {e}"))
for name, err in failed:
    print(f"FAIL  {name}: {err}")
print(f"{len(mods) - len(failed)}/{len(mods)} modules import cleanly")
sys.exit(1 if failed else 0)
EOF

echo "== compile-cache smoke =="
python - <<'EOF'
# the quickstart program compiled twice: the second compile must be a
# cache hit (same artifact, hit counter bumped) and run zero passes
from repro import api
from repro.core.passes import PassManager
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction


def quickstart_program():
    grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=grid, space_order=2)
    dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
    return Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program


target = api.Target()
first = api.compile(quickstart_program(), target)
runs = PassManager.runs_completed
hits = api.cache_stats().hits
second = api.compile(quickstart_program(), target)
assert second is first, "second compile did not return the cached artifact"
assert api.cache_stats().hits == hits + 1, "cache hit counter did not bump"
assert PassManager.runs_completed == runs, (
    "cache hit re-ran the pass pipeline"
)
print(f"cache smoke OK: hit on recompile, {runs} pipeline run(s) total, "
      f"stats={api.cache_stats().as_dict()}")
EOF

echo "== pass-pipeline smoke =="
python -m repro.core.passes \
  "fuse,cse,dce,decompose{grid=2x2},swap-elim,overlap,lower-comm" --quiet
python -m repro.core.passes \
  "decompose{grid=2x2xy,boundary=periodic},swap-elim,diagonal,overlap,lower-comm" \
  --program box --quiet
python -m repro.core.passes \
  "decompose{grid=2x2},swap-elim,temporal-tile{k=2},overlap,lower-comm" --quiet

echo "== temporal-tiling smoke =="
python - <<'EOF'
# the heat kernel at exchange_every 1 vs 4: distinct cache keys, equal
# outputs over one epoch, and no more exchange_start ops per EPOCH than
# the per-STEP baseline emits (1 exchange volley serves 4 steps)
import numpy as np

from repro import api
from repro.core.dialects import comm
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
op = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero")

t1, t4 = api.Target(), api.Target(exchange_every=4)
assert t1.fingerprint != t4.fingerprint, "epoch depth must change the cache key"
s1, s4 = api.compile(op.program, t1), api.compile(op.program, t4)
assert s1 is not s4, "distinct targets must yield distinct cached artifacts"


def starts(s):
    return sum(
        1 for o in s.local_ir.body.ops if isinstance(o, comm.ExchangeStartOp)
    )


assert starts(s4) <= starts(s1), (starts(s4), starts(s1))
assert starts(s4) < 4 * starts(s1), "k=4 must not exchange per step"

rng = np.random.default_rng(0)
u0 = rng.standard_normal((64, 64)).astype(np.float32)
import jax.numpy as jnp

a = np.asarray(s1.time_loop((jnp.asarray(u0),), 4)[0])
b = np.asarray(s4.time_loop((jnp.asarray(u0),), 4)[0])
assert np.array_equal(a, b), f"epoch != 4 steps, max diff {np.abs(a-b).max()}"
print(f"temporal smoke OK: starts/epoch k=1: {starts(s1)}, k=4: {starts(s4)}, "
      "4-step outputs bitwise-equal")
EOF

echo "== tune smoke =="
python - <<'EOF'
# cost-model-only autotuning of the heat program must return a valid
# cached Target; the second search must hit the on-disk cache
import os
import tempfile

os.environ["REPRO_TUNE_CACHE"] = tempfile.mkdtemp(prefix="repro-tune-smoke-")

from repro import api
from repro.tune import cache_stats, tune
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.launch.roofline import V5E

grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
prog = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program

r1 = tune(prog, measure=False, device_kind=V5E)  # the CPU models a v5e
assert not r1.from_cache, "first search must be a cache miss"
assert cache_stats().misses == 1 and cache_stats().stores == 1, (
    cache_stats().as_dict()
)
api.compile(prog, r1.target)  # the winner is a valid, compilable Target
unpruned = [c for c in r1.candidates if not c.pruned]
assert unpruned and all(
    r1.winner.modeled_s <= c.modeled_s for c in unpruned
), "winner must have the minimal modeled step time among unpruned candidates"

r2 = tune(prog, measure=False, device_kind=V5E)
assert r2.from_cache, "second search must hit the persistent cache"
assert cache_stats().hits == 1, cache_stats().as_dict()
assert r2.target.fingerprint == r1.target.fingerprint
print(f"tune smoke OK: winner {r1.winner.describe()!r}, "
      f"{len(r1.candidates)} candidates ({len(unpruned)} unpruned), "
      f"stats={cache_stats().as_dict()}")
EOF

echo "== serve smoke =="
python - <<'EOF'
# two concurrent same-fingerprint requests plus one epoch-depth wave
# request through one StencilEngine: the heat pair must coalesce into a
# batched vmapped dispatch, and every result must be bitwise-equal to a
# solo compile(...).time_loop(...) run
import numpy as np

from repro import api
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.serve.stencil import StencilEngine, StencilEngineConfig

grid = Grid(shape=(48, 48), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
heat = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program
w = TimeFunction(name="w", grid=grid, space_order=2, time_order=2)
wave = Operator(Eq(w.dt2, w.laplace), dt=1e-3, boundary="zero").program

rng = np.random.default_rng(0)
t_heat, t_wave = api.Target(), api.Target(exchange_every=2)
eng = StencilEngine(StencilEngineConfig(slots_per_group=2))
jobs = []
for i in range(2):  # same fingerprint → one vmapped dispatch
    s = (rng.standard_normal((48, 48)).astype(np.float32),)
    jobs.append((eng.submit(heat, s, 4, tenant=f"heat{i}"), heat, t_heat, s, 4))
s = tuple(rng.standard_normal((48, 48)).astype(np.float32) for _ in range(2))
jobs.append((eng.submit(wave, s, 4, target=t_wave, tenant="wave"),
             wave, t_wave, s, 4))
eng.run()

snap = eng.metrics.snapshot()
assert snap["batched_dispatches"] >= 1, (
    f"heat pair did not coalesce: {snap}"
)
assert snap["requests_completed"] == 3, snap
for h, prog, target, state, n in jobs:
    want = api.compile(prog, target).time_loop(state, n)
    for a, b in zip(h.result(), want):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"serve result differs from solo run for rid={h.rid}"
        )
print(f"serve smoke OK: {snap['batched_dispatches']} batched / "
      f"{snap['solo_dispatches']} solo dispatches over "
      f"{snap['engine_steps']} engine steps, all results bitwise-equal")
EOF

echo "== fused-epoch smoke =="
python - <<'EOF'
# Target(exchange_every=4, fused_epoch=True): the whole epoch must be
# exactly ONE pallas kernel dispatch (trace counter + IR census) and
# bitwise-equal to the unfused pallas path over two epochs
import numpy as np

from repro import api, kernels
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
heat = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program

unfused = api.compile(heat, api.Target(
    backend="pallas", exchange_every=4, pallas_interpret=True))
fused = api.compile(heat, api.Target(
    backend="pallas", exchange_every=4, fused_epoch=True,
    pallas_interpret=True))
assert fused.kernel_dispatches == {
    "fused_epoch": 1, "apply": 0, "pallas_apply": 0, "halo_pad": 1,
    "halo_pad_copies": 1, "total": 1,
}, fused.kernel_dispatches
assert unfused.kernel_dispatches["apply"] == 4, unfused.kernel_dispatches

rng = np.random.default_rng(0)
u0 = rng.standard_normal((64, 64)).astype(np.float32)
kernels.reset_dispatch_stats()
a = fused.time_loop((u0,), 8)[0]  # 2 epochs
stats = kernels.dispatch_stats().as_dict()  # live object: snapshot now
assert stats["fused_epoch_calls"] == 1 and stats["apply_calls"] == 0, (
    stats  # jit traces the epoch once: 1 kernel per epoch
)
b = unfused.time_loop((u0,), 8)[0]
a, b = np.asarray(a), np.asarray(b)
assert np.array_equal(a, b), f"fused != unfused, max {np.abs(a-b).max()}"
print(f"fused-epoch smoke OK: one kernel per k=4 epoch "
      f"(trace stats {stats}), 8-step outputs bitwise-equal")
EOF

echo "== resilience smoke =="
python - <<'EOF'
# a FaultPlan-killed checkpointing run (heat, k=4, checkpoint every
# epoch, keep_last=2) must resume from its last committed snapshot and
# finish bitwise-equal to compile(...).time_loop(...) — and the
# retention knob must have pruned older snapshots truthfully
import os
import shutil
import tempfile

import numpy as np

from repro import api
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.resilience import FaultPlan, ResilientLoop, SimulatedFault, resume

grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
prog = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program

tgt = api.Target(exchange_every=4)
rng = np.random.default_rng(0)
u0 = rng.standard_normal((64, 64)).astype(np.float32)
want = api.compile(prog, tgt).time_loop((u0,), 32)
want = want if isinstance(want, tuple) else (want,)

d = tempfile.mkdtemp(prefix="repro-res-smoke-")
loop = ResilientLoop(
    prog, tgt, (u0,), 32, directory=d, checkpoint_every=1, keep_last=2,
    fault_plan=FaultPlan(kill_at_epoch=5),
)
try:
    loop.run()
    raise SystemExit("FaultPlan did not fire")
except SimulatedFault:
    pass
# 5 epochs checkpointed, keep_last=2: steps 16 & 20 remain, 3 pruned
assert loop.checkpointer.available_steps() == [16, 20], (
    loop.checkpointer.available_steps()
)
assert loop.checkpointer.stats.prunes == 3, loop.checkpointer.stats.as_dict()

resumed = resume(prog, d, tgt, keep_last=2)
assert resumed.step_count == 20, resumed.step_count
got = resumed.run()
for a, b in zip(got, want):
    assert np.array_equal(np.asarray(a), np.asarray(b)), (
        "killed+resumed run is not bitwise-equal to time_loop"
    )
shutil.rmtree(d, ignore_errors=True)
print("resilience smoke OK: killed at epoch 5, resumed from step 20, "
      f"bitwise-equal over 32 steps; ckpt stats "
      f"{loop.checkpointer.stats.as_dict()}")
EOF

echo "== elastic-serve smoke =="
python - <<'EOF'
# ISSUE 9: a 2-rank distributed bucket must batch its live slots into
# ONE pooled slot-axis dispatch per engine step (per-bucket counters:
# batched > 0, solo == 0), and a queue burst against a small autoscaled
# bucket must record >= 1 PoolSizer grow and >= 1 shrink — with every
# result bitwise-equal to a solo compile(...).time_loop(...) run
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np
import jax
from jax.sharding import Mesh

from repro import api
from repro.core.passes.decompose import make_strategy_1d
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.serve.stencil import (
    PoolSizerConfig,
    StencilEngine,
    StencilEngineConfig,
)

grid = Grid(shape=(48, 48), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
heat = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program
mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
target = api.Target(mesh=mesh, strategy=make_strategy_1d(2))
rng = np.random.default_rng(0)
solo = api.compile(heat, target)

# -- pooled distributed dispatch: 4 live slots, ONE dispatch per step --
eng = StencilEngine(StencilEngineConfig(slots_per_group=4))
states = [rng.standard_normal((48, 48)).astype(np.float32) for _ in range(4)]
hs = [eng.submit(heat, (s,), 6, target=target) for s in states]
eng.run()
bd = eng.metrics.bucket_dispatches[f"{heat.fingerprint}/{target.fingerprint}"]
assert bd["batched"] >= 1 and bd["solo"] == 0, (
    f"2-rank bucket did not dispatch pooled: {bd}"
)
for h, s in zip(hs, states):
    want = solo.time_loop((s,), 6)
    for a, b in zip(h.result(), want if isinstance(want, tuple) else (want,)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"pooled result differs from solo run for rid={h.rid}"
        )

# -- queue burst: autoscaler must grow on depth, shrink on the tail ----
eng2 = StencilEngine(StencilEngineConfig(
    slots_per_group=2,
    autoscale=PoolSizerConfig(min_capacity=1, max_capacity=8,
                              cooldown_steps=1, ewma_alpha=1.0),
))
burst = [rng.standard_normal((48, 48)).astype(np.float32) for _ in range(8)]
steps = [6] * 7 + [36]
hs2 = [eng2.submit(heat, (s,), n, target=target)
       for s, n in zip(burst, steps)]
eng2.run()
auto = eng2.metrics.snapshot()["autoscale"]
assert auto["grows"] >= 1 and auto["shrinks"] >= 1, auto
for h, s, n in zip(hs2, burst, steps):
    want = solo.time_loop((s,), n)
    for a, b in zip(h.result(), want if isinstance(want, tuple) else (want,)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"post-resize result differs from solo run for rid={h.rid}"
        )
print(f"elastic-serve smoke OK: bucket counters {bd}, "
      f"autoscale grows={auto['grows']} shrinks={auto['shrinks']}, "
      "all results bitwise-equal")
EOF

echo "== obs smoke =="
python - <<'EOF'
# a profiled 8-step heat run (k=4) with obs on: the time_loop span lands
# on the host plane with its args, the same answer as with obs off, one
# more step trace, the compiled step's ops under their IR scopes, and
# obs.snapshot() over all five counter namespaces.
import glob
import tempfile

import jax
import numpy as np
from jax.profiler import ProfileData

from repro import api, obs
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

grid = Grid(shape=(64, 64), extent=(1.0, 1.0))
u = TimeFunction(name="u", grid=grid, space_order=2)
dt = 0.8 * grid.spacing[0] ** 2 / (4 * 0.5)
prog = Operator(Eq(u.dt, 0.5 * u.laplace), dt=dt, boundary="zero").program
step = api.compile(prog, api.Target(exchange_every=4))
u0 = np.random.default_rng(0).standard_normal((64, 64)).astype(np.float32)

want = np.asarray(step.time_loop((u0,), 8)[0])
traces = obs.snapshot()["compile"]["step_traces"]
out = tempfile.mkdtemp(prefix="obs-smoke-")
obs.enable()
with jax.profiler.trace(out):
    got = np.asarray(step.time_loop((u0,), 8)[0])
obs.disable()
assert np.array_equal(got, want), "time_loop with obs on differs"
assert obs.snapshot()["compile"]["step_traces"] == traces + 1

path = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)[0]
loops = [
    dict(e.stats)
    for plane in ProfileData.from_file(path).planes
    if plane.name.startswith("/host:")
    for line in plane.lines
    for e in line.events
    if e.name == "time_loop"
]
assert loops == [{"program": prog.name, "n_steps": 8, "k": 4, "static": 0}], loops

hlo = step.lower().compile().as_text()
for scope in ("comm.halo_pad", "stencil.apply"):
    assert f"/{scope}/" in hlo, f"no {scope} scope in the compiled step"

snap = obs.snapshot()
missing = {"compile", "kernel", "serve", "checkpoint", "tune"} - set(snap)
assert not missing, f"snapshot missing namespaces {missing}"
print(f"obs smoke OK: time_loop span {loops[0]} in {path}, "
      f"snapshot namespaces {sorted(snap)}")
EOF

if [[ "${1:-}" == "--smoke" ]]; then
  echo "smoke only: skipping tier-1 tests"
  exit 0
fi

echo "== tier-1 tests =="
python -m pytest -x -q

"""repro.obs: host spans on the profiler's clock, IR scopes on the device
ops, the step-trace counter and the unified registry.

Spans are read back the way an operator reads them: from a
``jax.profiler.trace`` of a tiny CPU run, through ``ProfileData``.  The
2x2 exchange scopes are checked on virtual CPU devices in
``tests/dist_worker.py scopes-2x2-k*`` so the device-count flag never
leaks into this process.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api, obs
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with obs disabled."""
    obs.disable()
    yield
    obs.disable()


def _profile(tmp_path, fn):
    """Run ``fn`` under ``jax.profiler.trace``; return the host-plane
    events as ``[(name, stats, start ns, end ns)]``."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [
        (e.name, dict(e.stats), e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]


def _named(events, name):
    return [e for e in events if e[0] == name]


def _heat(n=32, so=8):
    grid = Grid(shape=(n, n))
    u = TimeFunction(name="u", grid=grid, space_order=so)
    return Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------


def test_disabled_tracing_is_a_shared_noop(tmp_path):
    assert not obs.enabled()
    h1 = obs.span("work", big="payload")
    h2 = obs.span("other")
    # one shared null object — nothing allocated per disabled call site
    assert h1 is h2

    def run():
        with h1:
            h1.set_metadata(ignored=True)  # goes nowhere

    events = _profile(tmp_path, run)
    assert not _named(events, "work") and not _named(events, "other")


def test_span_records_nesting_and_args(tmp_path):
    obs.enable()

    def run():
        with obs.span("outer", phase="a"):
            with obs.span("inner", k=4):
                pass
            with obs.span("inner2") as sp:
                sp.set_metadata(count=3)

    events = _profile(tmp_path, run)
    (outer,), (inner,), (inner2,) = (
        _named(events, n) for n in ("outer", "inner", "inner2"))
    assert outer[1] == {"phase": "a"}
    assert inner[1] == {"k": 4}
    assert inner2[1] == {"count": 3}
    # children lie inside the parent on the profiler's clock
    for child in (inner, inner2):
        assert outer[2] <= child[2] and child[3] <= outer[3]
    assert inner[3] <= inner2[2]


def test_traced_decorator_bare_and_named(tmp_path):
    @obs.traced
    def f(x):
        return x + 1

    @obs.traced("custom.name")
    def g(x):
        return x * 2

    assert f(1) == 2 and g(2) == 4  # disabled: plain passthrough
    events = _profile(tmp_path / "off", lambda: (f(1), g(2)))
    assert not _named(events, "custom.name")
    obs.enable()
    events = _profile(tmp_path / "on", lambda: (f(1), g(2)))
    assert _named(events, "custom.name")
    assert any(e[0].endswith("f") for e in events)


def test_enabled_spans_carry_args_on_the_host_plane(tmp_path):
    obs.enable()
    prog = _heat(24, 4)
    u0 = jnp.ones((24, 24), jnp.float32)

    def run():
        c = api.compile(prog, api.Target())
        jax.block_until_ready(c.time_loop((u0,), 3))

    events = _profile(tmp_path, run)
    (compile_span,) = _named(events, "api.compile")
    assert compile_span[1]["program"] == prog.name
    assert compile_span[1]["cache"] in ("hit", "miss")
    (loop,) = _named(events, "time_loop")
    assert loop[1] == {"program": prog.name, "n_steps": 3, "k": 1, "static": 0}


def test_disabled_spans_leave_no_host_events(tmp_path):
    prog = _heat(24, 4)
    u0 = jnp.ones((24, 24), jnp.float32)

    def run():
        c = api.compile(prog, api.Target())
        jax.block_until_ready(c.time_loop((u0,), 3))

    events = _profile(tmp_path, run)
    names = {e[0] for e in events}
    assert not names & {"api.compile", "api.build", "time_loop"}
    assert not any(n.startswith("pass:") for n in names)


# --------------------------------------------------------------------------
# scopes: every IR op names the device ops it emits
# --------------------------------------------------------------------------

# per case: the target, the scopes its device ops carry, and scopes that
# emit nothing (the Pallas kernel builds the zero halo itself, so the
# pad and its waits lower to nothing)
_SCOPE_CASES = {
    "jnp": (api.Target(), {"comm.halo_pad", "comm.wait", "stencil.apply"},
            set()),
    "pallas": (
        api.Target(backend="pallas"),
        {"stencil.apply"},
        {"comm.halo_pad", "comm.wait"},
    ),
    "fused-epoch": (
        api.Target(backend="pallas", exchange_every=4, fused_epoch=True),
        {"comm.halo_pad", "stencil.fused_epoch"},
        set(),
    ),
}


@pytest.mark.parametrize("case", sorted(_SCOPE_CASES))
def test_compiled_step_carries_ir_scopes(case):
    target, want, silent = _SCOPE_CASES[case]
    prog = _heat(32, 8)
    c = api.compile(prog, target)
    names = _op_names(c.lower().compile().as_text())
    root = f"jit({prog.name}.step)/"
    scopes = set()
    for name in names:
        parts = name.split("/")
        scopes |= {p for p in parts if p.startswith(("stencil.", "comm."))}
    assert want <= scopes, (case, sorted(names))
    assert not silent & scopes, (case, sorted(names))
    assert all(n.startswith(root) for n in names if "/" in n), sorted(names)
    assert not any(":" in s for s in scopes)


def test_step_jit_is_named_after_the_program():
    prog = _heat(24, 4)
    c = api.compile(prog, api.Target())
    u0 = jnp.ones((24, 24), jnp.float32)
    looped = jax.jit(lambda s: c.time_loop(s, 2)).lower((u0,)).compile()
    names = _op_names(looped.as_text())
    assert any(f"/jit({prog.name}.step)/" in n for n in names), sorted(names)
    assert not any("<unknown>" in n for n in names), sorted(names)


# --------------------------------------------------------------------------
# the step-trace counter, and obs changing nothing
# --------------------------------------------------------------------------


def test_step_traces_count_eager_calls_and_one_jit():
    prog = _heat(24, 4)
    c = api.compile(prog, api.Target())
    u0 = jnp.ones((24, 24), jnp.float32)

    def traces():
        return obs.snapshot()["compile"]["step_traces"]

    t0 = traces()
    for _ in range(3):
        c.time_loop((u0,), 2)
    assert traces() == t0 + 3  # an eager call traces (and compiles) anew
    looped = jax.jit(lambda s: c.time_loop(s, 2))
    for _ in range(3):
        jax.block_until_ready(looped((u0,)))
    assert traces() == t0 + 4
    c.advance((u0,))  # a concrete call dispatches the cached step
    assert traces() == t0 + 4


def test_time_loop_is_bitwise_the_same_with_obs_enabled():
    prog = _heat(32, 8)
    c = api.compile(prog, api.Target())
    u0 = jnp.asarray(
        np.random.default_rng(0).standard_normal((32, 32)), jnp.float32)
    want = np.asarray(c.time_loop((u0,), 6)[0])
    obs.enable()
    got = np.asarray(c.time_loop((u0,), 6)[0])
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# unified registry
# --------------------------------------------------------------------------


def test_snapshot_unifies_five_counter_islands():
    snap = obs.snapshot()
    for ns in ("compile", "kernel", "serve", "checkpoint", "tune"):
        assert ns in snap, f"missing namespace {ns}"
        assert isinstance(snap[ns], dict) and snap[ns], snap[ns]
    assert {"hits", "misses", "pipeline_runs", "step_traces"} <= set(
        snap["compile"])
    assert {"apply_calls", "pallas_calls"} <= set(snap["kernel"])
    assert "engines" in snap["serve"]
    assert {"saves", "restores"} <= set(snap["checkpoint"])
    assert "hits" in snap["tune"]
    assert snap["trace"]["enabled"] is False
    flat = obs.snapshot(flat=True)
    assert "compile.hits" in flat and "checkpoint.saves" in flat


def test_snapshot_sees_live_traffic():
    from repro.api import Target, compile as api_compile
    from repro.frontends.oec_like import ProgramBuilder

    p = ProgramBuilder("obs_snap", (8, 8))
    u = p.input("u")
    out = p.output("out")
    r = p.apply([p.load(u)], lambda b, u: u.at(0, 0) * 2.0)
    p.store(r, out)
    prog = p.finish(boundary="zero")
    before = obs.snapshot()
    step = api_compile(prog, Target())
    step(np.zeros((8, 8), np.float32), np.zeros((8, 8), np.float32))
    after = obs.snapshot()
    assert after["compile"]["pipeline_runs"] > before["compile"]["pipeline_runs"]
    total = after["compile"]["hits"] + after["compile"]["misses"]
    assert total > before["compile"]["hits"] + before["compile"]["misses"]


def test_recognize_span_counts_statements_and_applies(tmp_path):
    """``psyclone.recognize`` carries the kernel, its statements (array
    stores and scalar temporaries) and the applies they became."""
    from repro.frontends import nemo_traadv

    obs.enable()
    events = _profile(tmp_path, lambda: nemo_traadv.program((4, 8, 130)))
    (span,) = _named(events, "psyclone.recognize")
    assert span[1] == {"kernel": "tra_adv", "statements": 34, "applies": 19}

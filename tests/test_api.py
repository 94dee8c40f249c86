"""The one compile surface: Program / Target / CompiledStencil.

Covers Target validation (construction-time rejection), IR fingerprint
stability, the process-wide fingerprint-keyed compile cache (hit/miss
counters + pass pipeline not re-running), buffer donation, and the
acceptance property that all three frontends compile through
``repro.api.compile`` with one shared Target, and the persistent
compile-cache location.
"""
import dataclasses

import numpy as np
import pytest

import repro
from repro import api
from repro.api import CompiledStencil, Program, Target, TargetError
from repro.core import ir
from repro.core.passes import PassManager
from repro.core.passes.decompose import SlicingStrategy, make_strategy_1d
from repro.frontends.oec_like import ProgramBuilder
from repro.launch.roofline import V5E


def _jacobi_prog(shape=(16, 16), boundary="periodic", name="jacobi"):
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


def _one_device_mesh(axis="x"):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), (axis,))


# -------------------------------------------------------------------------
# Program: metadata + fingerprint stability
# -------------------------------------------------------------------------


def test_program_metadata():
    prog = _jacobi_prog()
    assert prog.rank == 2
    assert prog.field_names == ("u", "out")
    assert len(prog.output_fields) == 1
    assert "stencil.apply" in prog.ir_text()


def test_fingerprint_stable_across_rebuilds():
    # structurally identical programs built twice hash identically
    assert _jacobi_prog().fingerprint == _jacobi_prog().fingerprint


def test_fingerprint_changes_on_op_change():
    base = _jacobi_prog().fingerprint
    # different constant in the apply body
    p = ProgramBuilder("jacobi", (16, 16))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.5,
    )
    p.store(r, out)
    assert p.finish(boundary="periodic").fingerprint != base


def test_fingerprint_changes_on_attr_change():
    # same ops, different boundary attribute → different fingerprint
    assert (
        _jacobi_prog(boundary="zero").fingerprint
        != _jacobi_prog(boundary="periodic").fingerprint
    )
    # op-attribute change (store bounds shape via program shape)
    assert (
        _jacobi_prog(shape=(16, 32)).fingerprint
        != _jacobi_prog(shape=(16, 16)).fingerprint
    )


def test_fingerprint_covers_metadata():
    # same IR, different field names / program name → different identity,
    # so a cache hit always hands back matching metadata
    p1 = _jacobi_prog()
    p2 = Program(_jacobi_prog().func, boundary="periodic",
                 field_names=("in0", "out0"), name="jacobi")
    assert p1.fingerprint != p2.fingerprint


def test_compile_rejects_program_mutated_after_construction():
    prog = _jacobi_prog(name="mutation_probe")
    const = next(
        op for op in prog.func.walk() if isinstance(op, ir.ConstantOp)
    )
    const.attributes["value"] = ir.FloatAttr(0.5)  # rewrite AFTER wrapping
    with pytest.raises(ValueError, match="mutated"):
        api.compile(prog, Target())


def test_ir_fingerprint_ignores_name_hints():
    # name hints are debugging sugar, not structure
    f1 = _jacobi_prog().func
    f2 = _jacobi_prog().func
    f2.body.args[0].name_hint = "renamed"
    assert ir.fingerprint(f1) == ir.fingerprint(f2)


# -------------------------------------------------------------------------
# Target validation: rejected at construction / compile, not inside lowering
# -------------------------------------------------------------------------


def test_target_rejects_unknown_backend():
    with pytest.raises(TargetError, match="backend"):
        Target(backend="cuda")


def test_target_rejects_decomposed_strategy_without_mesh():
    with pytest.raises(TargetError, match="no mesh"):
        Target(strategy=make_strategy_1d(2))


def test_target_rejects_mesh_grid_mismatch():
    mesh = _one_device_mesh()  # axis "x" has size 1
    with pytest.raises(TargetError, match="mesh size"):
        Target(mesh=mesh, strategy=make_strategy_1d(2))
    with pytest.raises(TargetError, match="not in mesh axes"):
        Target(mesh=mesh, strategy=make_strategy_1d(2, axis="q"))


def test_target_rejects_malformed_pipeline_at_construction():
    from repro.core.passes import PipelineError

    with pytest.raises(PipelineError):
        Target(pipeline="decompose{grid=2x2")


def test_compile_rejects_bad_strategy_rank():
    # strategy decomposes dim 4 of a rank-2 program
    prog = _jacobi_prog()
    bad = Target(strategy=SlicingStrategy((1,), ("x",), (4,)))
    with pytest.raises(TargetError, match="rank-2"):
        api.compile(prog, bad)


def test_compile_rejects_indivisible_extent():
    import jax
    from jax.sharding import Mesh

    prog = _jacobi_prog(shape=(15, 16))
    # a validation-only mesh (never executed) of logical size 2
    mesh = Mesh(np.array(jax.devices() * 2), ("x",))
    target = Target(mesh=mesh, strategy=make_strategy_1d(2))
    with pytest.raises(TargetError, match="divisible"):
        api.compile(prog, target)


def test_target_auto_single_device():
    t = Target.auto()
    # the test process sees one CPU device
    assert not t.distributed
    with pytest.raises(TargetError, match="devices"):
        Target.auto(ranks=64)


@pytest.mark.parametrize(
    "exc,make,match",
    [
        (ValueError, lambda: Program(_jacobi_prog().func, boundary="mirror"),
         "unknown boundary condition 'mirror'"),
        (ValueError, lambda: Program(_jacobi_prog().func, field_names=("u",)),
         "1 field names for 2 field arguments"),
        (TargetError,
         lambda: Target(pipeline="decompose{grid=1},temporal-tile{k=two}",
                        exchange_every=2),
         "temporal-tile: k must be an integer, got 'two'"),
        (TargetError,
         lambda: Target(mesh=_one_device_mesh("slot"), slot_axis=3),
         "slot_axis must be a mesh axis name, got 3"),
        (TargetError, lambda: Target(slot_axis="slot"),
         "needs a mesh carrying that axis"),
        (TargetError, lambda: Target(mesh=_one_device_mesh(), slot_axis="slot"),
         r"slot_axis 'slot' not in mesh axes \('x',\)"),
        (TargetError, lambda: api.pooled_target(Target()),
         "pooled_target needs a distributed target"),
        (TargetError,
         lambda: api.pooled_target(
             Target(mesh=_one_device_mesh("slot"), slot_axis="slot")),
         "target already carries slot axis 'slot'"),
    ],
    ids=["program-boundary", "program-field-names", "pipeline-k-not-int",
         "slot-axis-not-a-name", "slot-axis-without-mesh",
         "slot-axis-not-in-mesh", "pooled-single-device", "pooled-twice"],
)
def test_api_refuses_bad_input(exc, make, match):
    """What a caller hands ``Program``, ``Target`` and ``pooled_target``
    is refused at construction, with a message naming the mismatch: a
    ``ValueError`` for a ``Program``, a ``TargetError`` for a target."""
    with pytest.raises(exc, match=match) as e:
        make()
    assert e.type is exc


def test_target_fingerprint_distinguishes_knobs():
    assert Target().fingerprint == Target().fingerprint
    assert Target(backend="pallas").fingerprint != Target().fingerprint
    assert Target(overlap=True).fingerprint != Target().fingerprint
    assert (
        Target(pipeline="decompose,swap-elim,lower-comm").fingerprint
        != Target().fingerprint
    )


# -------------------------------------------------------------------------
# the compute backend: resolved from the platform unless given
# -------------------------------------------------------------------------


def _on_platform(monkeypatch, platform: str) -> None:
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: platform)


def test_target_backend_resolves_to_jnp_off_a_tpu():
    import jax

    assert jax.default_backend() != "tpu"
    t = Target()
    assert t.backend == "jnp"
    assert t.fingerprint == Target(backend="jnp").fingerprint


def test_target_backend_resolves_to_pallas_on_a_tpu(monkeypatch):
    _on_platform(monkeypatch, "tpu")
    t = Target()
    assert t.backend == "pallas"
    assert t.pallas_interpret is False
    assert t.fingerprint == Target(backend="pallas").fingerprint
    # the epoch megakernel needs no backend named on a TPU
    assert Target(exchange_every=2, fused_epoch=True).backend == "pallas"


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_target_backend_given_is_kept(monkeypatch, platform, backend):
    _on_platform(monkeypatch, platform)
    t = Target(backend=backend)
    assert t.backend == backend
    assert dataclasses.replace(t, overlap=True).backend == backend


def test_target_fused_epoch_without_backend_errors_off_a_tpu():
    with pytest.raises(TargetError, match="backend='pallas'"):
        Target(exchange_every=2, fused_epoch=True)


@pytest.mark.parametrize("backend,kernels", [("pallas", 1), ("jnp", 0)])
def test_kernel_dispatches_counts_pallas_applies(backend, kernels):
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    u = TimeFunction(name="u", grid=Grid(shape=(32, 32)), space_order=8)
    prog = Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program
    compiled = api.compile(prog, Target(backend=backend, exchange_every=1))
    assert compiled.kernel_dispatches["apply"] == 1
    assert compiled.kernel_dispatches["pallas_apply"] == kernels


# -------------------------------------------------------------------------
# the process-wide compile cache
# -------------------------------------------------------------------------


def test_compile_cache_hit_returns_same_artifact_and_skips_passes():
    prog = _jacobi_prog(name="cache_probe")
    target = Target()
    first = api.compile(prog, target)
    assert isinstance(first, CompiledStencil)

    stats0 = api.cache_stats().as_dict()
    runs0 = PassManager.runs_completed
    second = api.compile(_jacobi_prog(name="cache_probe"), Target())
    assert second is first  # same artifact object
    assert PassManager.runs_completed == runs0  # pass pipeline did not re-run
    stats1 = api.cache_stats().as_dict()
    assert stats1["hits"] == stats0["hits"] + 1
    assert stats1["misses"] == stats0["misses"]


def test_compile_cache_misses_on_different_target():
    prog = _jacobi_prog(name="cache_probe2")
    a = api.compile(prog, Target())
    b = api.compile(prog, Target(fuse=False))
    assert a is not b
    assert a.pipeline_report.spec != b.pipeline_report.spec


def test_top_level_reexport():
    assert repro.compile is api.compile
    assert repro.Target is Target
    assert repro.Program is Program


# -------------------------------------------------------------------------
# donation
# -------------------------------------------------------------------------


def test_buffers_are_donated():
    """A donate=True Target passes its donate_argnums to jax.jit and the
    buffers are actually donated."""
    import jax
    import jax.numpy as jnp

    prog = _jacobi_prog(name="donate_probe")
    step = api.compile(prog, Target(donate=True))
    assert step.donate_argnums == (0, 1)  # whole-state handover

    # the input→output aliasing must be visible in the lowering…
    u = jnp.ones((16, 16), jnp.float32)
    out = jnp.zeros((16, 16), jnp.float32)
    txt = jax.jit(step._raw_fn, donate_argnums=step.donate_argnums).lower(
        u, out
    ).as_text()
    assert "tf.aliasing_output" in txt or "jax.buffer_donor" in txt

    # …and actually happen at execution: the donated input buffer is
    # consumed (its storage rotated into the result)
    step(u, out)
    assert u.is_deleted()


def test_donation_can_be_disabled():
    import jax.numpy as jnp

    prog = _jacobi_prog(name="donate_probe2")
    step = api.compile(prog, Target(donate=False))
    assert step.donate_argnums == ()
    out = jnp.zeros((16, 16), jnp.float32)
    step(jnp.ones((16, 16), jnp.float32), out)
    assert not out.is_deleted()


# -------------------------------------------------------------------------
# acceptance: three frontends, one Target, one compile — shim equivalent
# -------------------------------------------------------------------------


def test_three_frontends_share_one_target():
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
    from repro.frontends.psyclone_like import recognize

    shape = (24, 24)
    target = Target()  # ONE target for all three frontends

    oec = _jacobi_prog(shape=shape, name="j")

    def kern(u, out):
        out[i, j] = 0.25 * (u[i - 1, j] + u[i + 1, j] + u[i, j - 1] + u[i, j + 1])

    psy = recognize(kern, shape=shape, boundary="periodic")

    g = Grid(shape=shape, extent=shape)  # spacing 1
    u = TimeFunction(name="u", grid=g, space_order=2)
    expr = (
        u.shifted(0, -1) + u.shifted(0, 1) + u.shifted(1, -1) + u.shifted(1, 1)
    ) * 0.25
    dev = Operator(Eq(u.forward, expr), boundary="periodic").program

    for prog in (oec, psy, dev):
        assert isinstance(prog, Program)

    rng = np.random.default_rng(8)
    u0 = rng.standard_normal(shape).astype(np.float32)
    r_oec = np.asarray(api.compile(oec, target)(u0, np.zeros_like(u0))[0])
    r_psy = np.asarray(api.compile(psy, target)(u0, np.zeros_like(u0))[0])
    r_dev = np.asarray(api.compile(dev, target)(u0, np.zeros_like(u0))[0])
    np.testing.assert_array_equal(r_oec, r_psy)
    np.testing.assert_array_equal(r_oec, r_dev)


# -------------------------------------------------------------------------
# artifact surface: local_ir / pipeline_report / specs / lower / cost
# -------------------------------------------------------------------------


def test_artifact_inspection_surface():
    from repro.core.dialects import comm, dmp

    step = api.compile(_jacobi_prog(name="inspect_probe"), Target())
    # comm-lowered local IR, no dmp.swap survives
    assert not any(isinstance(op, dmp.SwapOp) for op in step.local_ir.body.ops)
    assert any(isinstance(op, comm.HaloPadOp) for op in step.local_ir.body.ops)
    # pipeline report matches the spec stage-by-stage
    names = [n for n, _ in step.pipeline_report.timings]
    assert names == step.pipeline_report.spec.split(",")
    assert "pipeline:" in str(step.pipeline_report)
    # partition specs: one per field arg (trivial strategy → all None)
    assert len(step.partition_specs) == 2
    # AOT lower + roofline cost
    cost = step.cost(device_kind=V5E)
    assert cost.flops > 0
    assert cost.dominant in ("compute", "memory", "collective")
    assert cost.t_serial >= cost.t_overlapped


def test_time_loop_on_artifact():
    step = api.compile(_jacobi_prog(name="loop_probe"), Target())
    rng = np.random.default_rng(10)
    u0 = rng.standard_normal((16, 16)).astype(np.float32)
    # 2 steps via time_loop == 2 manual calls
    (via_loop,) = step.time_loop([u0], 2)
    once = step(u0, np.zeros_like(u0))[0]
    twice = step(np.asarray(once), np.zeros_like(u0))[0]
    np.testing.assert_allclose(
        np.asarray(via_loop), np.asarray(twice), rtol=1e-6
    )


# -------------------------------------------------------------------------
# persistent compilation cache location (repro.compile_cache)
# -------------------------------------------------------------------------


def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch, tmp_path):
    import jax

    from repro import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    import pathlib

    import jax

    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first, second = compile_cache.enable(), compile_cache.enable()
        checkout = pathlib.Path(repro.__file__).resolve().parents[2]
        assert first == second == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -------------------------------------------------------------------------
# static fields: one rotation rule for advance, time_loop, api.time_loop
# -------------------------------------------------------------------------


def _advect(c, t):
    t[i, j] = t[i, j] - c[i, j] * (t[i, j] - t[i - 1, j])


def _static_prog(shape=(16, 16)):
    from repro.frontends.psyclone_like import recognize

    return recognize(_advect, shape=shape, boundary="periodic")


def test_static_fields_rotation_rule():
    """The state is the static fields, then the time levels oldest →
    newest; every driver of the step rotates it the same way, and hands
    the static fields back unchanged."""
    import jax
    import jax.numpy as jnp

    prog = _static_prog()
    assert prog.static_fields == ("c",)
    compiled = api.compile(prog)
    assert compiled.static_indices == (0,)
    rng = np.random.default_rng(3)
    c0 = jnp.asarray(0.3 * rng.random((16, 16)), jnp.float32)
    t0 = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)

    state = (c0, t0)
    for _ in range(3):
        state = compiled.advance(state)
    assert state[0] is c0 and len(state) == 2
    looped = compiled.time_loop((c0, t0), 3)
    plain = api.time_loop(compiled.step(), (c0, t0), 3, static=1)
    jitted = jax.jit(lambda s: compiled.time_loop(s, 3))((c0, t0))
    for other in (looped, plain, jitted):
        np.testing.assert_array_equal(np.asarray(other[0]), np.asarray(c0))
        np.testing.assert_allclose(np.asarray(other[1]), np.asarray(state[1]),
                                   rtol=1e-6, atol=1e-6)
    want = np.asarray(t0)
    for _ in range(3):
        want = want - np.asarray(c0) * (want - np.roll(want, 1, 0))
    np.testing.assert_allclose(np.asarray(looped[1]), want, rtol=1e-5, atol=1e-6)
    # no static field: the old rule, state[len(outs):] + outs
    assert api.rotate((1, 2, 3), (4,)) == (2, 3, 4)
    assert api.rotate((9, 1, 2), (4,), static=1) == (9, 2, 4)


def test_static_field_metadata_is_checked():
    prog = _static_prog()
    with pytest.raises(ValueError, match="not a field"):
        Program(prog.func, field_names=prog.field_names, static_fields=("nope",))
    with pytest.raises(ValueError, match="is stored to"):
        Program(prog.func, field_names=prog.field_names, static_fields=("t'",))
    again = Program(prog.func, boundary="periodic", field_names=prog.field_names)
    assert again.fingerprint != prog.fingerprint   # static is part of the identity


@pytest.mark.parametrize("knobs,match", [
    ({"exchange_every": 2}, "exchange_every=2"),
    ({"backend": "pallas", "fused_epoch": True}, "fused_epoch=True"),
])
def test_static_fields_refused_where_not_carried(knobs, match):
    with pytest.raises(TargetError, match=f"static fields.*{match}"):
        api.compile(_static_prog(), Target(**knobs))


def test_static_fields_refused_by_slot_pools_serving_and_resilience():
    from repro.resilience import ResilientLoop
    from repro.serve.stencil.engine import StencilEngine

    import jax
    from jax.sharding import Mesh

    prog = _static_prog()
    slot = Target(mesh=Mesh(np.array(jax.devices()[:1]), ("slot",)), slot_axis="slot")
    with pytest.raises(TargetError, match="static fields.*slot_axis='slot'"):
        api.compile(prog, slot)
    state = (np.zeros((16, 16), np.float32),) * 2
    with pytest.raises(TargetError, match="ResilientLoop does not carry"):
        ResilientLoop(prog, None, state, 2)
    with pytest.raises(TargetError, match="serve engine does not carry"):
        StencilEngine().submit(prog, state, 2)


def test_index_is_refused_under_a_distributed_target():
    import jax
    from jax.sharding import Mesh

    from repro.core.builder import Expr
    from repro.core.dialects import stencil

    p = ProgramBuilder("idx", (16, 16))
    u = p.input("u")
    out = p.output("out")
    r = p.apply([p.load(u)], lambda b, u: u.at(0, 0)
                + Expr(b, b.insert(stencil.IndexOp(1)).results[0]))
    p.store(r, out)
    slot = Target(mesh=Mesh(np.array(jax.devices()[:1]), ("slot",)), slot_axis="slot")
    with pytest.raises(TargetError, match="rank-local index"):
        api.compile(p.finish(), slot)


def test_halo_pad_census():
    assert api.compile(_jacobi_prog()).kernel_dispatches["halo_pad"] == 1


@pytest.mark.parametrize(
    "backend,boundary,mesh,copies,unroll",
    [
        ("jnp", "zero", False, 1, 1),
        ("pallas", "zero", False, 0, 2),
        ("pallas", "zero", True, 0, 2),
        ("pallas", "periodic", False, 1, 1),
    ],
    ids=["jnp", "pallas-zero", "pallas-zero-1x1-mesh", "pallas-periodic"],
)
def test_halo_pad_copies_census(backend, boundary, mesh, copies, unroll):
    """``halo_pad_copies`` counts the pads that still copy: every pad on
    the jnp path, the wrapped copy at a periodic boundary, and none where
    only Pallas kernels read a zero halo on one device (a mesh whose
    axes have size 1 exchanges only zeros).  Where a kernel then reads
    the level in place, ``time_loop`` runs two epochs an iteration."""
    target = api.Target(backend=backend,
                        mesh=_one_device_mesh() if mesh else None)
    c = api.compile(_jacobi_prog(boundary=boundary), target)
    assert c.kernel_dispatches["halo_pad"] == 1
    assert c.kernel_dispatches["halo_pad_copies"] == copies
    assert c.loop_unroll == unroll


def test_time_loop_unroll_keeps_the_answer():
    """Two epochs an iteration (``loop_unroll``) over an odd number of
    steps gives the one-epoch loop's answer bitwise."""
    import jax

    c = api.compile(_jacobi_prog(boundary="zero"),
                    api.Target(backend="pallas", pallas_interpret=True))
    assert c.loop_unroll == 2
    u0 = (np.random.default_rng(3).standard_normal((16, 16)).astype(np.float32),)
    got = jax.jit(lambda s: c.time_loop(s, 5))(u0)
    want = jax.jit(lambda s: c.time_loop(s, 5, unroll=1))(u0)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))

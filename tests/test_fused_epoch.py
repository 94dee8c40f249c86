"""Fused-epoch Pallas megakernel: one kernel dispatch per deep-halo epoch.

The acceptance harness for the fuse-epoch-kernel lowering: random
programs (rank, chained applies, either boundary) at exchange_every ∈
{1, 2, 4} must be *bitwise-identical* between ``fused_epoch=True`` (one
``pl.pallas_call`` per epoch) and the unfused interpreted per-step
oracle — plus dispatch-counter proofs that the epoch really is one
kernel, Target-surface validation, and the interpret-flag plumbing.
"""
import numpy as np
import pytest

from _hypothesis_compat import example, given, settings
from _strategies import build_program, exchange_everys, program_descriptors

from repro import api, kernels
from repro.api import Target, TargetError
from repro.core.dialects import stencil
from repro.core.passes.temporal import epoch_halo


def _fused(k: int, **kw) -> Target:
    return Target(
        backend="pallas",
        exchange_every=k,
        fused_epoch=True,
        pallas_interpret=True,
        **kw,
    )


def _unfused(k: int, **kw) -> Target:
    return Target(
        backend="pallas",
        exchange_every=k,
        pallas_interpret=True,
        **kw,
    )


def _heat(shape=(16, 16), boundary="periodic", name="heat_fe"):
    from repro.frontends.oec_like import ProgramBuilder

    p = ProgramBuilder(name, shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: u.at(0, 0) * 0.5
        + (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.125,
    )
    p.store(r, out)
    return p.finish(boundary=boundary)


# -------------------------------------------------------------------------
# the property: fused epoch == unfused interpreted steps, bitwise
# -------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(descriptor=program_descriptors, k=exchange_everys)
# 1-D, two chained applies, zero boundary: the fused kernel once differed
# from the unfused one by 1 ulp at two interior points
@example(descriptor=(427241, 1, 2, "zero"), k=4)
def test_fused_epoch_equals_unfused_bitwise(descriptor, k):
    """One megakernel per epoch is bitwise-equal to the unfused
    interpreted path (one pallas dispatch per time step, same k) for
    random programs (≥50 per run).  Both targets are jitted: the unfused
    epoch then traces its k per-step kernels into one XLA module — the
    very module the fused kernel emits — so equality is exact.  (Eagerly
    the unfused path is one XLA module *per step* and XLA CPU's
    per-module FMA contraction drifts ~1ulp; see epoch_kernel.py.)"""
    seed, rank, n_applies, boundary = descriptor
    prog = build_program(seed, rank, n_applies, boundary)
    shape = prog.field_args[0].type.bounds.shape
    lo, hi = epoch_halo(prog.func, k)
    if any(max(l, h) > n for l, h, n in zip(lo, hi, shape)):
        with pytest.raises(TargetError, match="deep halo"):
            api.compile(prog, _fused(k))
        return
    oracle = api.compile(prog, _unfused(k))
    fused = api.compile(prog, _fused(k))
    rng = np.random.default_rng(seed + 1)
    u0 = rng.standard_normal(shape).astype(np.float32)
    want = got = u0
    for _ in range(2):  # two epochs: exercises epoch-to-epoch rotation too
        want = np.asarray(oracle(want, np.zeros_like(u0))[0])
        got = np.asarray(fused(got, np.zeros_like(u0))[0])
    np.testing.assert_array_equal(want, got)


# -------------------------------------------------------------------------
# one dispatch per epoch, counter-asserted
# -------------------------------------------------------------------------


def test_fused_epoch_is_one_dispatch():
    """Target(exchange_every=4, fused_epoch=True): the compiled epoch
    step issues exactly ONE pallas_call — the trace counter says so, and
    the static IR census (kernel_dispatches) agrees."""
    prog = _heat()
    fused = api.compile(prog, _fused(4))
    assert fused.kernel_dispatches == {
        "fused_epoch": 1, "apply": 0, "pallas_apply": 0, "halo_pad": 1,
        "halo_pad_copies": 1, "pinned_send_strips": 0,
        "total": 1,
    }
    u0 = np.random.default_rng(0).standard_normal((16, 16)).astype(np.float32)
    kernels.reset_dispatch_stats()
    fused(u0, np.zeros_like(u0))
    stats = kernels.dispatch_stats()
    assert stats.fused_epoch_calls == 1
    assert stats.apply_calls == 0
    assert stats.pallas_calls == 1


def test_unfused_epoch_is_k_dispatches():
    prog = _heat()
    unfused = api.compile(prog, _unfused(4))
    assert unfused.kernel_dispatches == {
        "fused_epoch": 0, "apply": 4, "pallas_apply": 4, "halo_pad": 1,
        "halo_pad_copies": 1, "pinned_send_strips": 0,
        "total": 4,
    }
    u0 = np.random.default_rng(0).standard_normal((16, 16)).astype(np.float32)
    kernels.reset_dispatch_stats()
    unfused(u0, np.zeros_like(u0))
    assert kernels.dispatch_stats().pallas_calls == 4


def test_fused_epoch_ir_has_single_fused_op():
    """The lowered local IR holds ONE FusedEpochOp wrapping the k cloned
    applies (and the zero-BC masks); no top-level applies survive."""
    prog = _heat(boundary="zero")
    fused = api.compile(prog, _fused(4))
    ops = list(fused.local_ir.body.ops)
    fused_ops = [op for op in ops if isinstance(op, stencil.FusedEpochOp)]
    assert len(fused_ops) == 1
    assert not any(isinstance(op, stencil.ApplyOp) for op in ops)
    inner = [op.name for op in fused_ops[0].body.ops]
    assert inner.count("stencil.apply") == 4
    assert fused_ops[0].k == 4
    assert inner[-1] == "stencil.fused_yield"


def test_fused_epoch_with_explicit_tile_matches():
    """An explicit dividing pallas_tile routes through the tiled (grid)
    kernel mode and stays bitwise-equal to the whole-shard mode."""
    prog = _heat((32, 32))
    u0 = np.random.default_rng(2).standard_normal((32, 32)).astype(np.float32)
    whole = api.compile(prog, _fused(2))
    tiled = api.compile(prog, _fused(2, pallas_tile=(16, 32)))
    a = np.asarray(whole(u0, np.zeros_like(u0))[0])
    b = np.asarray(tiled(u0, np.zeros_like(u0))[0])
    np.testing.assert_array_equal(a, b)


# -------------------------------------------------------------------------
# Target surface
# -------------------------------------------------------------------------


def test_fused_epoch_requires_pallas_backend():
    with pytest.raises(TargetError, match="backend='pallas'"):
        Target(backend="jnp", fused_epoch=True)


def test_fused_epoch_incompatible_with_overlap():
    with pytest.raises(TargetError, match="overlap"):
        Target(backend="pallas", fused_epoch=True, overlap=True)


def test_fused_epoch_explicit_pipeline_must_match():
    spec = Target(backend="pallas", fused_epoch=True).pipeline_spec()
    assert spec.endswith("fuse-epoch-kernel")
    # spec says fused but the flag does not (and vice versa) → reject
    with pytest.raises(TargetError, match="fuse-epoch-kernel"):
        Target(backend="pallas", pipeline=spec, fused_epoch=False)
    no_fuse = Target(backend="pallas").pipeline_spec()
    with pytest.raises(TargetError, match="fuse-epoch-kernel"):
        Target(backend="pallas", pipeline=no_fuse, fused_epoch=True)


def test_fused_epoch_changes_fingerprint():
    a = Target(backend="pallas", exchange_every=2)
    b = Target(backend="pallas", exchange_every=2, fused_epoch=True)
    assert a.fingerprint != b.fingerprint


def test_pallas_interpret_resolves_at_construction():
    t = Target(backend="pallas")
    assert t.pallas_interpret == kernels.default_interpret()
    assert isinstance(t.pallas_interpret, bool)
    forced = Target(backend="pallas", pallas_interpret=True)
    assert forced.pallas_interpret is True
    assert forced.fingerprint != Target(
        backend="pallas", pallas_interpret=False
    ).fingerprint


def test_ops_default_interpret_follows_env(monkeypatch):
    """Interpret mode follows the backend alone: native kernels on a TPU,
    the interpreter elsewhere.  No environment variable can switch a
    chip run into interpret mode."""
    import jax

    for value in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert kernels.default_interpret() is (jax.default_backend() != "tpu")


def test_kernel_ops_single_flag_source():
    """kernels.ops entry points no longer hardcode interpret=True: the
    default resolves through kernels.default_interpret (the backend), and
    an explicit value is honored."""
    import inspect

    from repro.kernels import ops

    for fn in (ops.star_stencil, ops.laplacian, ops.heat_step, ops.wave_step):
        assert inspect.signature(fn).parameters["interpret"].default is None
    u = np.random.default_rng(3).standard_normal((12, 12)).astype(np.float32)
    a = np.asarray(ops.laplacian(u, interpret=True))
    b = np.asarray(ops.laplacian(u))  # CPU default resolves to interpret
    np.testing.assert_array_equal(a, b)


def test_whole_shard_epoch_over_vmem_fails_at_compile():
    """A fused epoch that cannot be tiled (wave's carried escape has grown
    bounds) runs whole-shard only where it fits VMEM; at 2048² it does
    not, and compile() says so with the sizes — before any trace."""
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    u = TimeFunction(name="u", grid=Grid(shape=(2048, 2048)), space_order=2,
                     time_order=2)
    prog = Operator(Eq(u.dt2, u.laplace), dt=1e-3, boundary="zero").program
    with pytest.raises(TargetError, match=r"VMEM.*\(2048, 2048\)|\(2048, 2048\).*VMEM"):
        api.compile(prog, _fused(2))

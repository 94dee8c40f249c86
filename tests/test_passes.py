"""Compiler-pass tests: halo inference, decomposition (dmp.swap
insertion), redundant-swap elimination, fusion, CSE — the paper's §4.2
pass pipeline, validated structurally AND semantically."""
import numpy as np
import pytest

from repro.core import ir
from repro.core.builder import build_apply
from repro.core.dialects import dmp, stencil
from repro.core.passes import (
    cse_apply_bodies,
    dce,
    decompose_stencil,
    eliminate_redundant_swaps,
    fuse_applies,
    infer_apply_halo,
)
from repro.core.passes.decompose import (
    make_strategy_1d,
    make_strategy_2d,
    make_strategy_3d,
)
from repro import api
from repro.api import Program, Target
from repro.frontends.oec_like import ProgramBuilder


def _count(func, kind):
    return sum(1 for op in func.body.ops if isinstance(op, kind))


def _jacobi_prog(shape=(32, 32)):
    p = ProgramBuilder("jacobi", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
    )
    p.store(r, out)
    return p.build_func()


# -------------------------------------------------------------------------
# halo inference (paper: "minimal halo derived from stencil.access offsets")
# -------------------------------------------------------------------------


@pytest.mark.parametrize(
    "offsets,expect_lo,expect_hi",
    [
        ([(-1, 0), (1, 0), (0, -1), (0, 1)], (-1, -1), (1, 1)),
        ([(-4, 0), (0, 2)], (-4, 0), (0, 2)),
        ([(0, 0)], (0, 0), (0, 0)),
    ],
)
def test_halo_inference_minimal(offsets, expect_lo, expect_hi):
    core = stencil.Bounds.from_shape((16, 16))
    func = ir.FuncOp("h", [stencil.FieldType(core), stencil.FieldType(core)])
    load = func.body.add_op(stencil.LoadOp(func.body.args[0]))

    def body(b, u):
        acc = None
        for off in offsets:
            t = u.at(*off)
            acc = t if acc is None else acc + t
        return acc

    apply_op = build_apply(func.body, [load.results[0]], core, body)
    func.body.add_op(stencil.StoreOp(apply_op.results[0], func.body.args[1], core))
    func.body.add_op(ir.ReturnOp([]))
    lo, hi = infer_apply_halo(apply_op)[0]
    assert lo == expect_lo and hi == expect_hi


# -------------------------------------------------------------------------
# decomposition (dmp.swap insertion)
# -------------------------------------------------------------------------


def test_decompose_inserts_swap_with_correct_halo():
    func = _jacobi_prog((32, 32))
    local = decompose_stencil(func, make_strategy_2d((4, 2)))
    swaps = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    assert len(swaps) == 1
    sw = swaps[0]
    assert sw.halo_widths() == ((1, 1), (1, 1))
    # local domain is the global domain divided by the rank grid
    assert sw.temp.type.bounds.shape == (8, 16)
    # 4 axis-aligned exchanges for a star stencil (no corners)
    assert len(sw.exchanges) == 4
    ir.verify_module(local)


def test_decompose_local_shapes_3d():
    p = ProgramBuilder("j3", (32, 32, 64))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0, 0) + u.at(1, 0, 0) + u.at(0, 0, -2)) * 0.5,
    )
    p.store(r, out)
    func = p.build_func()
    local = decompose_stencil(func, make_strategy_3d((2, 2, 4)))
    (sw,) = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    assert sw.temp.type.bounds.shape == (16, 16, 16)
    assert sw.halo_widths() == ((1, 0, 2), (1, 0, 0))


def test_exchange_decls_match_paper_model():
    """Each exchange declares send/recv rectangles + neighbor offset
    (paper fig. 3)."""
    func = _jacobi_prog((32, 32))
    local = decompose_stencil(func, make_strategy_1d(4, dim=0))
    (sw,) = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    exs = sw.exchanges
    assert len(exs) == 2  # up + down neighbors in 1-D
    for ex in exs:
        # full-width slabs of thickness 1; width spans the undecomposed
        # dim's locally-filled halo (32 + 2·1) so corners need no 2nd round
        assert ex.numel() == 1 * 34
        assert ex.is_axis_aligned()


def test_decompose_1d_strategy_on_dim1():
    func = _jacobi_prog((32, 64))
    local = decompose_stencil(func, make_strategy_1d(4, dim=1))
    (sw,) = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    assert sw.temp.type.bounds.shape == (32, 16)
    # full stencil halo on both dims (undecomposed dim 0 is filled
    # locally by boundary handling) — but exchanges run only along dim 1
    assert sw.halo_widths() == ((1, 1), (1, 1))
    assert all(ex.neighbor[0] != 0 for ex in sw.exchanges)
    assert len(sw.exchanges) == 2


# -------------------------------------------------------------------------
# redundant swap elimination (paper: SSA dataflow pass removes dup swaps)
# -------------------------------------------------------------------------


def _two_apply_prog(shape=(32, 32)):
    """load → apply(center only) → apply(star): first apply's swap is
    redundant since its result is only read at offset 0... but the second
    needs one.  Construct the redundant case directly: two swaps of the
    same temp."""
    p = ProgramBuilder("two", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    a = p.apply([t], lambda b, u: u.at(0, 0) * 2.0)
    r = p.apply(
        [a],
        lambda b, v: (v.at(-1, 0) + v.at(1, 0) + v.at(0, -1) + v.at(0, 1)) * 0.25,
    )
    p.store(r, out)
    return p.build_func()


def test_swap_count_after_elimination():
    func = _two_apply_prog()
    local = decompose_stencil(func, make_strategy_2d((2, 2)))
    n_before = _count(local, dmp.SwapOp)
    eliminate_redundant_swaps(local)
    n_after = _count(local, dmp.SwapOp)
    assert n_after <= n_before
    # the center-only apply's input swap must be gone; the star apply's stays
    assert n_after == 1
    ir.verify_module(local)


def test_elimination_preserves_results():
    prog = Program(_two_apply_prog((16, 16)), boundary="periodic")

    rng = np.random.default_rng(3)
    u0 = rng.standard_normal((16, 16)).astype(np.float32)
    out0 = np.zeros((16, 16), np.float32)

    # single-rank periodic reference
    ref = api.compile(prog, Target(fuse=False, cse=False))(u0, out0)
    got = api.compile(prog, Target(fuse=True, cse=True))(u0, out0)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=1e-6)


# -------------------------------------------------------------------------
# fusion (paper §6.2: PW advection fuses 3 stencils → 1 region)
# -------------------------------------------------------------------------


def _three_stencil_prog(shape=(24, 24)):
    """Three chained applies, fusable into one (PW-advection shape)."""
    p = ProgramBuilder("pw", shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    a = p.apply([t], lambda b, u: (u.at(-1, 0) + u.at(1, 0)) * 0.5)
    c = p.apply([t, a], lambda b, u, a: u.at(0, 0) + a.at(0, 0) * 0.1)
    p.store(c, out)
    return p.build_func()


def test_fusion_reduces_apply_count():
    func = _three_stencil_prog()
    n0 = _count(func, stencil.ApplyOp)
    fuse_applies(func)
    dce(func)
    n1 = _count(func, stencil.ApplyOp)
    assert n1 < n0
    assert n1 == 1
    ir.verify_module(func)


def test_fusion_preserves_semantics():
    rng = np.random.default_rng(1)
    u0 = rng.standard_normal((24, 24)).astype(np.float32)
    out0 = np.zeros_like(u0)
    prog = Program(_three_stencil_prog(), boundary="periodic")

    r_unfused = api.compile(prog, Target(fuse=False, cse=False))(u0, out0)
    r_fused = api.compile(prog, Target(fuse=True, cse=False))(u0, out0)
    np.testing.assert_allclose(np.asarray(r_unfused), np.asarray(r_fused), rtol=1e-6)


def test_fusion_grows_halo_of_consumer():
    """Fusing apply(shift) into apply(star) widens the fused access set."""
    func = _three_stencil_prog()
    fuse_applies(func)
    dce(func)
    local = decompose_stencil(func, make_strategy_2d((2, 2)))
    (sw,) = [op for op in local.body.ops if isinstance(op, dmp.SwapOp)]
    # fused stencil reads u at (-1,0),(1,0),(0,0) through `a` = halo 1 on dim 0
    lo, hi = sw.halo_widths()
    assert lo[0] >= 1 and hi[0] >= 1


# -------------------------------------------------------------------------
# CSE
# -------------------------------------------------------------------------


def test_cse_dedupes_accesses():
    core = stencil.Bounds.from_shape((8, 8))
    func = ir.FuncOp("c", [stencil.FieldType(core), stencil.FieldType(core)])
    load = func.body.add_op(stencil.LoadOp(func.body.args[0]))

    def body(b, u):
        # u.at(1,0) appears twice; constant 2.0 appears twice
        return u.at(1, 0) * 2.0 + u.at(1, 0) * 2.0

    apply_op = build_apply(func.body, [load.results[0]], core, body)
    func.body.add_op(stencil.StoreOp(apply_op.results[0], func.body.args[1], core))
    func.body.add_op(ir.ReturnOp([]))

    n_access_before = sum(
        1 for op in apply_op.body.ops if isinstance(op, stencil.AccessOp)
    )
    cse_apply_bodies(func)
    dce(func)
    n_access_after = sum(
        1 for op in apply_op.body.ops if isinstance(op, stencil.AccessOp)
    )
    assert n_access_before == 2
    assert n_access_after == 1
    ir.verify_module(func)


# -------------------------------------------------------------------------
# beyond-paper rewrites keep semantics (overlap / diagonal)
# -------------------------------------------------------------------------


# "pipeline" replaces the removed comm_dialect flag: the canonical spec
# written out explicitly must match the flag-denoted default pipeline.
@pytest.mark.parametrize(
    "kw",
    [
        {"overlap": True},
        {"diagonal": True},
        {"pipeline": "fuse,cse,dce,decompose,swap-elim,lower-comm"},
    ],
    ids=["overlap", "diagonal", "pipeline"],
)
def test_beyond_paper_rewrites_preserve_semantics(kw):
    rng = np.random.default_rng(7)
    u0 = rng.standard_normal((16, 16)).astype(np.float32)
    out0 = np.zeros_like(u0)
    prog = Program(_jacobi_prog((16, 16)), boundary="periodic")

    base = api.compile(prog, Target())(u0, out0)
    opt_result = api.compile(prog, Target(**kw))(u0, out0)
    np.testing.assert_allclose(np.asarray(base), np.asarray(opt_result), rtol=1e-6)

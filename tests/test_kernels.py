"""Pallas kernel allclose sweeps vs the pure-jnp oracles (ref.py).

Kernels run in interpret=True mode (CPU container; TPU is the target).
Hypothesis drives shape/radius/coefficient sweeps; fixed parametrized
cases cover the paper's benchmark configurations (SDO 2/4/8 × 2D/3D).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.fd import laplacian_star, radius
from repro.kernels import ops, ref
from repro.kernels.stencil_apply import choose_tile


def _rand(shape, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(dtype)


# -------------------------------------------------------------------------
# fixed paper-configuration sweeps: SDO × rank
# -------------------------------------------------------------------------


@pytest.mark.parametrize("order", [2, 4, 8])
@pytest.mark.parametrize("rank", [1, 2, 3])
def test_laplacian_matches_ref(order, rank):
    h = radius(order)
    core = {1: (128,), 2: (32, 64), 3: (8, 16, 32)}[rank]
    x = _rand(tuple(c + 2 * h for c in core), seed=order * 10 + rank)
    got = ops.laplacian(jnp.asarray(x), order=order)
    want = ref.star_stencil_ref(jnp.asarray(x), laplacian_star(rank, order), (h,) * rank)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", [2, 4, 8])
def test_heat_step_matches_ref(order):
    h = radius(order)
    x = _rand((24 + 2 * h, 48 + 2 * h), seed=order)
    got = ops.heat_step(jnp.asarray(x), 0.1, order=order)
    want = ref.heat_step_ref(jnp.asarray(x), 0.1, order, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", [2, 4, 8])
def test_wave_step_matches_ref(order):
    h = radius(order)
    u_t = _rand((16 + 2 * h, 16 + 2 * h), seed=order + 1)
    u_tm1 = _rand((16 + 2 * h, 16 + 2 * h), seed=order + 2)
    core = tuple(slice(h, s - h) for s in u_t.shape)
    got = ops.wave_step(jnp.asarray(u_t), jnp.asarray(u_tm1[core]), 0.25, order=order)
    want = ref.wave_step_ref(jnp.asarray(u_t), jnp.asarray(u_tm1), 0.25, order, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------------------
# hypothesis property sweeps
# -------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    nx=st.integers(4, 40),
    ny=st.integers(4, 40),
    halo=st.integers(1, 3),
    seed=st.integers(0, 2**16),
)
def test_star_stencil_random_shapes(nx, ny, halo, seed):
    """Arbitrary core shapes/halos: kernel == oracle."""
    rng = np.random.default_rng(seed)
    coeffs = {}
    for d in range(2):
        for o in (-halo, halo):
            off = tuple(o if k == d else 0 for k in range(2))
            coeffs[off] = float(rng.standard_normal())
    coeffs[(0, 0)] = float(rng.standard_normal())
    x = rng.standard_normal((nx + 2 * halo, ny + 2 * halo)).astype(np.float32)
    got = ops.star_stencil(jnp.asarray(x), coeffs, (halo, halo))
    want = ref.star_stencil_ref(jnp.asarray(x), coeffs, (halo, halo))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(8, 64),
    order=st.sampled_from([2, 4, 8]),
    dtype=st.sampled_from([np.float32, np.float64]),
    seed=st.integers(0, 2**16),
)
def test_laplacian_dtype_sweep_1d(n, order, dtype, seed):
    h = radius(order)
    x = np.random.default_rng(seed).standard_normal(n + 2 * h).astype(dtype)
    got = ops.laplacian(jnp.asarray(x), order=order)
    want = ref.star_stencil_ref(jnp.asarray(x), laplacian_star(1, order), (h,))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    shape=st.tuples(st.integers(2, 12), st.integers(2, 12), st.integers(2, 12)),
    seed=st.integers(0, 2**16),
)
def test_box_stencil_3d(shape, seed):
    """Box (corner-reading) stencils — the diagonal-exchange case."""
    rng = np.random.default_rng(seed)
    coeffs = {
        (1, 1, 0): 0.5,
        (-1, -1, 0): -0.25,
        (0, 1, -1): 1.5,
        (0, 0, 0): 1.0,
    }
    halo = (1, 1, 1)
    x = rng.standard_normal(tuple(s + 2 for s in shape)).astype(np.float32)
    got = ops.star_stencil(jnp.asarray(x), coeffs, halo)
    want = ref.star_stencil_ref(jnp.asarray(x), coeffs, halo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------------------
# explicit tiling: the BlockSpec grid path (tile ≠ full array)
# -------------------------------------------------------------------------


@pytest.mark.parametrize("tile", [(8, 256), (16, 128), (32, 128)])
def test_explicit_tiles_agree(tile):
    """Different VMEM tilings must not change results (overlap windows);
    every tile here obeys the (8, 128) rule."""
    x = _rand((64 + 2, 256 + 2), seed=11)
    star = laplacian_star(2, 2)
    from repro.kernels.stencil_apply import run_apply_pallas
    from repro.kernels.ops import _star_apply_ir

    apply_op, ob = _star_apply_ir(star, (64, 256), (1, 1))
    from repro.core.dialects import stencil

    rb = stencil.Bounds.from_shape((64, 256))
    (got,) = run_apply_pallas(
        apply_op, [jnp.asarray(x)], [ob.lb], rb, tile=tile, interpret=True
    )
    want = ref.star_stencil_ref(jnp.asarray(x), star, (1, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_choose_tile_respects_budget_and_divisibility():
    """The chosen tile divides the shape, obeys the (8, 128) rule, and its
    whole-kernel VMEM (windows + outputs double-buffered + temporaries)
    fits the budget; a budget nothing fits raises naming the sizes."""
    from repro.kernels import KernelPlanError
    from repro.kernels.stencil_apply import is_legal_tile, vmem_bytes

    shape = (512, 1024)
    spans = [(8, 8)]
    budget = 2 * 1024 * 1024
    tile = choose_tile(shape, spans, budget=budget)
    assert is_legal_tile(shape, tile)
    assert tile[0] % 8 == 0 and tile[1] % 128 == 0
    assert vmem_bytes(tile, spans, 1) <= budget
    with pytest.raises(KernelPlanError, match="VMEM budget"):
        choose_tile(shape, spans, budget=16 * 1024)


def test_kernel_backend_equals_jnp_backend_end_to_end():
    """Same stencil program through lowering w/ jnp vs pallas backends."""
    from repro.api import Target, compile as api_compile
    from repro.frontends.oec_like import ProgramBuilder

    def build():
        p = ProgramBuilder("j", shape=(32, 32))
        u = p.input("u")
        out = p.output("out")
        t = p.load(u)
        r = p.apply(
            [t],
            lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25
            - u.at(0, 0) * 0.1,
        )
        p.store(r, out)
        return p.finish(boundary="periodic")

    u0 = _rand((32, 32), seed=13)
    out0 = np.zeros_like(u0)
    r_jnp = api_compile(build(), Target(backend="jnp"))(u0, out0)
    r_pal = api_compile(build(), Target(backend="pallas"))(u0, out0)
    np.testing.assert_allclose(
        np.asarray(r_jnp[0]), np.asarray(r_pal[0]), rtol=1e-5, atol=1e-6
    )


# -------------------------------------------------------------------------
# window slack: the halo pad allocates what the last window overhangs
# -------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,order,tile,boundary",
    [
        (1000, 8, (64, 256), "zero"),
        (200, 8, None, "zero"),
        (300, 4, (64, 128), "zero"),
        (96, 2, None, "periodic"),
    ],
)
def test_windows_read_the_padded_level_in_place(n, order, tile, boundary):
    """Shapes whose last window overhangs the halo-padded operand: the
    ``comm.halo_pad`` allocates the slack, the kernel reads it in place
    (no ``window_source`` copy), and 4 jitted steps are bitwise equal to
    the jnp lowering's.  A window clamped at the array's end instead
    (the pad left out, nothing allocated) differs at 1000² so8."""
    from repro import api, kernels
    from repro.api import Target
    from repro.core.dialects import stencil
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
    from repro.kernels.stencil_apply import high_slack, plan_apply

    u = TimeFunction(name="u", grid=Grid(shape=(n, n)), space_order=order)
    prog = Operator(Eq(u.dt, u.laplace), dt=0.1, boundary=boundary).program
    fast = api.compile(prog, Target(backend="pallas", pallas_tile=tile,
                                    pallas_interpret=True))
    (apply_op,) = [
        op for op in fast.local_ir.body.ops if isinstance(op, stencil.ApplyOp)
    ]
    rb = apply_op.result_bounds
    (slack,) = high_slack(apply_op, rb, plan_apply(apply_op, rb, tile))
    assert any(slack)
    u0 = (jnp.asarray(_rand((n, n), seed=n)),)
    want = jax.jit(
        lambda s: api.compile(prog, Target(backend="jnp")).time_loop(s, 4)
    )(u0)
    kernels.reset_dispatch_stats()
    got = jax.jit(lambda s: fast.time_loop(s, 4))(u0)
    assert kernels.dispatch_stats().apply_calls == 1
    assert kernels.dispatch_stats().window_copies == 0
    np.testing.assert_array_equal(np.asarray(got[-1]), np.asarray(want[-1]))

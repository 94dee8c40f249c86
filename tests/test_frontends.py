"""Frontend tests: three DSL inputs, one shared stack (paper fig. 1b).

Validates each frontend against independent numpy oracles, and the
*cross-frontend* property that the same mathematical stencil expressed in
all three DSLs produces identical results through the shared pipeline.
The oracle tests run each program on both backends: the jnp lowering and
the Pallas window kernel (interpreted off the chip), which is what
``Target()`` picks on a TPU.
"""
import numpy as np
import pytest

from repro.api import Target, compile as api_compile, time_loop
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.frontends.oec_like import ProgramBuilder
from repro.frontends.psyclone_like import RecognitionError, recognize


# -------------------------------------------------------------------------
# backends
# -------------------------------------------------------------------------

BACKENDS = pytest.mark.parametrize("backend", ["jnp", "pallas"])


def _target(backend):
    return Target(backend=backend, pallas_interpret=True)


def _compiled(prog, backend):
    """``prog`` compiled for ``backend``, with its pad census checked: on
    the jnp path every ``comm.halo_pad`` copies; on the Pallas path a zero
    halo is built inside the kernel (no copy) while a periodic halo holds
    wrapped data and keeps its copy."""
    c = api_compile(prog, _target(backend))
    census = c.kernel_dispatches
    assert census["pallas_apply"] == (census["apply"] if backend == "pallas" else 0)
    folds = backend == "pallas" and prog.boundary == "zero"
    assert census["halo_pad_copies"] == (0 if folds else census["halo_pad"])
    return c


def _apply(op, state, timesteps, backend):
    """``Operator.apply`` on ``backend``, the pad census checked."""
    _compiled(op.program, backend)
    return op.apply(state, timesteps=timesteps, target=_target(backend))


# -------------------------------------------------------------------------
# numpy oracles
# -------------------------------------------------------------------------


def np_jacobi(u, boundary="zero"):
    if boundary == "periodic":
        return 0.25 * (
            np.roll(u, 1, 0) + np.roll(u, -1, 0) + np.roll(u, 1, 1) + np.roll(u, -1, 1)
        )
    p = np.pad(u, 1)
    return 0.25 * (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:])


def np_heat(u, alpha, dt, h, order=2, boundary="zero"):
    from repro.core.fd import laplacian_star

    star = laplacian_star(2, order, spacing=h)
    out = np.zeros_like(u)
    for off, c in star.items():
        if boundary == "periodic":
            out += c * np.roll(np.roll(u, -off[0], 0), -off[1], 1)
        else:
            r = max(abs(o) for offs in star for o in offs)
            p = np.pad(u, r)
            out += c * p[
                r + off[0] : r + off[0] + u.shape[0],
                r + off[1] : r + off[1] + u.shape[1],
            ]
    return u + dt * alpha * out


# -------------------------------------------------------------------------
# Devito-like (paper listing 5)
# -------------------------------------------------------------------------


@BACKENDS
@pytest.mark.parametrize("order", [2, 4, 8])
@pytest.mark.parametrize("boundary", ["zero", "periodic"])
def test_devito_heat_matches_numpy(order, boundary, backend):
    shape = (32, 32)
    g = Grid(shape=shape, extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=order)
    dt = 1e-5
    op = Operator(Eq(u.dt, 0.7 * u.laplace), dt=dt, boundary=boundary)

    rng = np.random.default_rng(0)
    u0 = rng.standard_normal(shape).astype(np.float32)
    (got,) = _apply(op, [u0], 3, backend)

    want = u0.copy().astype(np.float64)
    for _ in range(3):
        want = np_heat(want, 0.7, dt, g.spacing[0], order=order, boundary=boundary)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-6)


@BACKENDS
def test_devito_wave_equation_second_order_time(backend):
    """u.dt2 = c²∇²u — the paper's acoustic benchmark shape (3 time slots)."""
    shape = (24, 24)
    g = Grid(shape=shape, extent=(1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=4, time_order=2)
    dt = 1e-4
    op = Operator(Eq(u.dt2, 1.5 * u.laplace), dt=dt, boundary="zero")

    rng = np.random.default_rng(1)
    um1 = rng.standard_normal(shape).astype(np.float32)
    u0 = rng.standard_normal(shape).astype(np.float32)
    state = op.zero_state()
    assert len(state) == 2  # needs t-1 and t
    got = _apply(op, [um1, u0], 1, backend)[-1]  # newest buffer

    from repro.core.fd import laplacian_star

    star = laplacian_star(2, 4, spacing=g.spacing[0])
    lap = np.zeros(shape)
    r = 2
    p = np.pad(u0.astype(np.float64), r)
    for off, c in star.items():
        lap += c * p[r + off[0]: r + off[0] + 24, r + off[1]: r + off[1] + 24]
    want = 2 * u0 - um1 + dt**2 * 1.5 * lap
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-6)


@BACKENDS
def test_devito_3d(backend):
    g = Grid(shape=(12, 12, 12), extent=(1.0, 1.0, 1.0))
    u = TimeFunction(name="u", grid=g, space_order=2)
    dt = 1e-6
    op = Operator(Eq(u.dt, u.laplace), dt=dt)
    u0 = np.random.default_rng(2).standard_normal((12, 12, 12)).astype(np.float32)
    (got,) = _apply(op, [u0], 2, backend)
    assert np.asarray(got).shape == (12, 12, 12)
    h = g.spacing[0]
    want = u0.astype(np.float64)
    for _ in range(2):
        p = np.pad(want, 1)
        lap = sum(
            np.roll(p, s, d)[1:-1, 1:-1, 1:-1] for d in range(3) for s in (1, -1)
        ) - 6 * want
        want = want + dt * lap / h**2
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=1e-5)


@BACKENDS
def test_devito_coupled_fields(backend):
    """Two coupled equations (v reads u) — multiple updates per step."""
    g = Grid(shape=(16, 16))
    u = TimeFunction(name="u", grid=g, space_order=2)
    v = TimeFunction(name="v", grid=g, space_order=2)
    op = Operator(
        [Eq(u.forward, u + 0.1 * v), Eq(v.forward, v.laplace)],
        boundary="periodic",
    )
    rng = np.random.default_rng(3)
    u0 = rng.standard_normal((16, 16)).astype(np.float32)
    v0 = rng.standard_normal((16, 16)).astype(np.float32)
    state = _apply(op, [u0, v0], 1, backend)
    got_u, got_v = [np.asarray(s) for s in state]
    np.testing.assert_allclose(got_u, u0 + 0.1 * v0, rtol=1e-5)
    h = g.spacing[0]
    lap = (np.roll(v0, 1, 0) + np.roll(v0, -1, 0) + np.roll(v0, 1, 1)
           + np.roll(v0, -1, 1) - 4 * v0) / h**2
    np.testing.assert_allclose(got_v, lap, rtol=1e-4, atol=1e-2)


def test_devito_refuses_an_lhs_it_cannot_step():
    g = Grid(shape=(8, 8))
    u = TimeFunction(name="u", grid=g, space_order=2)
    with pytest.raises(ValueError, match="LHS must be u.forward, u.dt or u.dt2"):
        Operator(Eq(u.laplace, u), dt=0.1)


def test_devito_refuses_a_target_and_a_mesh_together():
    g = Grid(shape=(8, 8))
    u = TimeFunction(name="u", grid=g, space_order=2)
    op = Operator(Eq(u.dt, u.laplace), dt=0.1)
    with pytest.raises(ValueError, match="either target= or mesh/strategy"):
        op.apply(op.zero_state(), 1, mesh=object(), target=Target())


# -------------------------------------------------------------------------
# PSyclone-like (stencil recognition from loop code, paper §5.2)
# -------------------------------------------------------------------------


@BACKENDS
def test_psyclone_recognizes_jacobi(backend):
    def kern(u, out):
        out[i, j] = 0.25 * (u[i - 1, j] + u[i + 1, j] + u[i, j - 1] + u[i, j + 1])

    prog = recognize(kern, shape=(20, 20), boundary="periodic")
    rng = np.random.default_rng(4)
    u0 = rng.standard_normal((20, 20)).astype(np.float32)
    (got,) = _compiled(prog, backend)(u0, np.zeros_like(u0))
    np.testing.assert_allclose(np.asarray(got), np_jacobi(u0, "periodic"), rtol=1e-5)


@BACKENDS
def test_psyclone_multi_statement_dependency(backend):
    """Intermediate arrays create apply chains (tracer-advection shape)."""
    def kern(u, flux, out):
        flux[i, j] = 0.5 * (u[i + 1, j] - u[i - 1, j])
        out[i, j] = u[i, j] - 0.1 * (flux[i + 1, j] - flux[i, j])

    prog = recognize(kern, shape=(16, 16), boundary="periodic")
    rng = np.random.default_rng(5)
    u0 = rng.standard_normal((16, 16)).astype(np.float32)
    flux0 = np.zeros_like(u0)
    out0 = np.zeros_like(u0)
    results = _compiled(prog, backend)(u0, flux0, out0)
    got_flux, got_out = [np.asarray(r) for r in results]

    want_flux = 0.5 * (np.roll(u0, -1, 0) - np.roll(u0, 1, 0))
    want_out = u0 - 0.1 * (np.roll(want_flux, -1, 0) - want_flux)
    np.testing.assert_allclose(got_flux, want_flux, rtol=1e-5)
    np.testing.assert_allclose(got_out, want_out, rtol=1e-5, atol=1e-6)


def test_psyclone_rejects_non_stencil():
    def bad(u, out):
        out[i, j] = u[j, i]  # a transposed access — not recognizable

    with pytest.raises(RecognitionError):
        recognize(bad, shape=(8, 8))


@BACKENDS
def test_psyclone_3d_kernel(backend):
    def kern(u, out):
        out[i, j, k] = (u[i, j, k - 1] + u[i, j, k + 1]) * 0.5

    prog = recognize(kern, shape=(8, 8, 8), boundary="periodic")
    u0 = np.random.default_rng(6).standard_normal((8, 8, 8)).astype(np.float32)
    (got,) = _compiled(prog, backend)(u0, np.zeros_like(u0))
    got = np.asarray(got)
    want = 0.5 * (np.roll(u0, 1, 2) + np.roll(u0, -1, 2))
    np.testing.assert_allclose(got, want, rtol=1e-5)


# -------------------------------------------------------------------------
# OEC-like (direct stencil IR)
# -------------------------------------------------------------------------


@BACKENDS
def test_oec_builder_jacobi(backend):
    p = ProgramBuilder("jacobi", shape=(20, 20))
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
    )
    p.store(r, out)
    prog = p.finish(boundary="zero")
    rng = np.random.default_rng(7)
    u0 = rng.standard_normal((20, 20)).astype(np.float32)
    (got,) = _compiled(prog, backend)(u0, np.zeros_like(u0))
    np.testing.assert_allclose(np.asarray(got), np_jacobi(u0, "zero"), rtol=1e-5)


# -------------------------------------------------------------------------
# cross-frontend equivalence: one math, three DSLs, one result
# -------------------------------------------------------------------------


@BACKENDS
def test_three_frontends_agree(backend):
    shape = (24, 24)
    rng = np.random.default_rng(8)
    u0 = rng.standard_normal(shape).astype(np.float32)

    # 1. OEC
    p = ProgramBuilder("j", shape=shape)
    uf = p.input("u")
    of = p.output("out")
    t = p.load(uf)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1)) * 0.25,
    )
    p.store(r, of)
    r_oec = np.asarray(
        _compiled(p.finish(boundary="periodic"), backend)(u0, np.zeros_like(u0))[0]
    )

    # 2. PSyclone-like
    def kern(u, out):
        out[i, j] = 0.25 * (u[i - 1, j] + u[i + 1, j] + u[i, j - 1] + u[i, j + 1])

    r_psy = np.asarray(
        _compiled(recognize(kern, shape=shape, boundary="periodic"), backend)(
            u0, np.zeros_like(u0)
        )[0]
    )

    # 3. Devito-like: u.forward = jacobi average — express directly via taps
    g = Grid(shape=shape, extent=shape)  # spacing 1
    u = TimeFunction(name="u", grid=g, space_order=2)
    expr = (
        u.shifted(0, -1) + u.shifted(0, 1) + u.shifted(1, -1) + u.shifted(1, 1)
    ) * 0.25
    op = Operator(Eq(u.forward, expr), boundary="periodic")
    (r_dev,) = _apply(op, [u0], 1, backend)
    r_dev = np.asarray(r_dev)

    np.testing.assert_allclose(r_oec, r_psy, rtol=1e-6)
    np.testing.assert_allclose(r_oec, r_dev, rtol=1e-6)


def test_time_loop_rotation():
    """time_loop rotates buffers oldest→newest (paper's time-buffering)."""
    import jax.numpy as jnp

    def step(a, b):
        return (a + b,)

    out = time_loop(step, (jnp.array(1.0), jnp.array(1.0)), 5)
    # fibonacci: after 5 steps state = (f5, f6) = (8, 13)
    assert float(out[0]) == 8.0 and float(out[1]) == 13.0


# -------------------------------------------------------------------------
# PSyclone-like: intrinsics, temporaries, index ranges, offset stores,
# index names, fields updated in place, and what stays unrecognizable
# -------------------------------------------------------------------------


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _outputs(prog, backend, *arrays):
    return [np.asarray(o) for o in _compiled(prog, backend)(*arrays)]


@BACKENDS
def test_psyclone_intrinsics(backend):
    def kern(a, b, c, out):
        out[i, j] = (sign(0.5, a[i, j]) + min(a[i, j], b[i, j])
                     * max(b[i, j], c[i, j], a[i, j]) - abs(c[i, j])
                     + min(a[i, j], b[i, j], c[i, j]))

    shape = (8, 130)
    a, b, c = (_rand(shape, s) for s in (1, 2, 3))
    a[0, :3] = (0.0, -0.0, -1e-30)  # sign(., b) is |.| where b >= 0, -0 too
    (got,) = _outputs(recognize(kern, shape=shape), backend, a, b, c,
                      np.zeros(shape, np.float32))
    want = (np.where(a >= 0, 0.5, -0.5) + np.minimum(a, b) * np.maximum(np.maximum(b, c), a)
            - np.abs(c) + np.minimum(np.minimum(a, b), c))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@BACKENDS
def test_psyclone_scalar_temporaries_are_inlined(backend):
    def kern(u, out):
        d = u[i + 1, j] - u[i - 1, j]
        d2 = d * d
        d = 0.5 * d
        out[i, j] = d2 + d

    u = _rand((16, 16), 4)
    prog = recognize(kern, shape=(16, 16), boundary="periodic")
    assert sum(op.name == "stencil.apply" for op in prog.func.body.ops) == 1
    (got,) = _outputs(prog, backend, u, np.zeros_like(u))
    d = np.roll(u, -1, 0) - np.roll(u, 1, 0)
    np.testing.assert_allclose(got, d * d + 0.5 * d, rtol=1e-5, atol=1e-5)


@BACKENDS
def test_psyclone_ranges_keep_the_previous_value(backend):
    """Inside a range the statement applies; outside, the array keeps what
    an earlier statement wrote, and a local array not yet written is 0."""
    def kern(u, out):
        tmp[i, j] = 2.0 * u[i, j]
        with ranges(i=(1, -1), j=(2, None)):
            tmp[i, j] = u[i - 1, j] + u[i + 1, j]
        with ranges(i=(0, 2)):
            z[i, j] = u[i, j]
        out[i, j] = tmp[i, j] + z[i, j]

    shape = (12, 140)
    u = _rand(shape, 5)
    prog = recognize(kern, shape=shape)
    assert prog.field_names == ("u", "out") and prog.static_fields == ()
    (got,) = _outputs(prog, backend, u, np.zeros_like(u))
    tmp = 2.0 * u
    tmp[1:-1, 2:] = u[:-2, 2:] + u[2:, 2:]
    z = np.zeros_like(u)
    z[:2] = u[:2]
    np.testing.assert_allclose(got, tmp + z, rtol=1e-6)


@BACKENDS
def test_psyclone_offset_store_shifts_the_statement(backend):
    """``out[i + 1, j] = f(i)`` over ``0 <= i < n-1`` writes rows 1..n-1;
    a field written over part of the grid is updated in place, so row 0
    keeps the value it came in with."""
    def kern(u, out):
        with ranges(i=(0, -1)):
            out[i + 1, j] = u[i, j] * 3.0 + u[i + 1, j]

    shape = (10, 20)
    u, out0 = _rand(shape, 6), _rand(shape, 7)
    prog = recognize(kern, shape=shape)
    assert prog.field_names == ("u", "out", "out'")
    (got,) = _outputs(prog, backend, u, out0, np.zeros_like(u))
    want = out0.copy()
    want[1:] = 3.0 * u[:-1] + u[1:]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@BACKENDS
def test_psyclone_index_names_per_dim(backend):
    def kern(a, out):
        out[k, j, i] = a[k, j, i + 1] - a[k - 1, j, i]

    a = _rand((4, 8, 130), 8)
    prog = recognize(kern, shape=a.shape, boundary="periodic", indices=("k", "j", "i"))
    (got,) = _outputs(prog, backend, a, np.zeros_like(a))
    np.testing.assert_allclose(got, np.roll(a, -1, 2) - np.roll(a, 1, 0), rtol=1e-6)


@BACKENDS
def test_psyclone_in_place_field_is_one_time_level(backend):
    """A field read and then written is state: its old level goes in, its
    new level comes out; the fields only read are static, handed to every
    step of the time loop unchanged."""
    def kern(c, t):
        t[i, j] = t[i, j] + c[i, j] * (t[i + 1, j] - t[i, j])

    prog = recognize(kern, shape=(16, 16), boundary="periodic")
    assert prog.field_names == ("c", "t", "t'")
    assert prog.static_fields == ("c",)
    compiled = _compiled(prog, backend)
    assert compiled.input_indices == (0, 1)
    c0 = np.abs(_rand((16, 16), 9)) * 0.2
    t0 = _rand((16, 16), 10)
    c1, t3 = compiled.time_loop((c0, t0), 3)
    want = t0
    for _ in range(3):
        want = want + c0 * (np.roll(want, -1, 0) - want)
    np.testing.assert_array_equal(np.asarray(c1), c0)
    np.testing.assert_allclose(np.asarray(t3), want, rtol=1e-5, atol=1e-6)


def _bad_unknown_call(u, out):
    out[i, j] = sqrt(u[i, j])


def _bad_sign_arity(u, out):
    out[i, j] = sign(u[i, j])


def _bad_min_arity(u, out):
    out[i, j] = min(u[i, j], u[i, j], u[i, j], u[i, j])


def _bad_undefined_scalar(u, out):
    out[i, j] = z * u[i, j]


def _bad_index_as_value(u, out):
    out[i, j] = i * u[i, j]


def _bad_stale_temporary(u, out):
    d = u[i, j] * 2.0
    u[i, j] = u[i, j] + 1.0
    out[i, j] = d


def _bad_range_name(u, out):
    with ranges(q=(0, 1)):
        out[i, j] = u[i, j]


def _bad_range_bound(u, out):
    with ranges(i=(0, n)):
        out[i, j] = u[i, j]


def _bad_empty_range(u, out):
    with ranges(i=(3, 3)):
        out[i, j] = u[i, j]


def _bad_with(u, out):
    with open(u):
        out[i, j] = u[i, j]


def _bad_store_outside(u, out):
    out[i + 1, j] = u[i, j]


def _bad_store_index(u, out):
    out[i * 2, j] = u[i, j]


def _bad_loop(u, out):
    for n in range(3):
        out[i, j] = u[i, j]


def _bad_range_pair(u, out):
    with ranges(i=3):
        out[i, j] = u[i, j]


def _bad_assign_index(u, out):
    i = u[i, j]
    out[i, j] = i


def _bad_intrinsic_array(u, out):
    out[i, j] = u[i, j] + abs[i, j]


def _bad_unknown_array(u, out):
    out[i, j] = v[i, j]


def _bad_attribute_store(u, out):
    out.data = u[i, j]


def _bad_index_count(u, out):
    out[i, j] = u[i]


def _bad_index_expr(u, out):
    out[i, j] = u[i + j, j]


def _bad_operator(u, out):
    out[i, j] = u[i, j] ** 2


def _bad_expression(u, out):
    out[i, j] = u[i, j] > 0.0


_bad_lambda = lambda u, out: None  # noqa: E731


@pytest.mark.parametrize("kern,match", [
    (_bad_unknown_call, "unsupported call"),
    (_bad_sign_arity, "takes 2 arguments"),
    (_bad_min_arity, "takes 2 to 3 arguments"),
    (_bad_undefined_scalar, "not a defined scalar"),
    (_bad_index_as_value, "not a defined scalar"),
    (_bad_stale_temporary, "written after"),
    (_bad_range_name, "not one of the loop indices"),
    (_bad_range_bound, "integer or None"),
    (_bad_empty_range, "empty or outside"),
    (_bad_with, "must be 'ranges"),
    (_bad_store_outside, "reaches outside"),
    (_bad_store_index, "index must be"),
    (_bad_loop, "only single-target assignments"),
    (_bad_range_pair, r"must be a \(lo, hi\) pair"),
    (_bad_assign_index, "cannot assign to 'i'"),
    (_bad_intrinsic_array, "'abs' is a loop index or an intrinsic"),
    (_bad_unknown_array, "unknown array 'v'"),
    (_bad_attribute_store, "not an array access"),
    (_bad_index_count, "'u' has 1 indices, expected 2"),
    (_bad_index_expr, "'u': unrecognizable index"),
    (_bad_operator, "unsupported operator Pow"),
    (_bad_expression, "unsupported expression"),
    (_bad_lambda, "expected a function definition"),
])
def test_psyclone_recognition_errors(kern, match):
    with pytest.raises(RecognitionError, match=match):
        recognize(kern, shape=(8, 8))


def test_psyclone_index_names_must_match_the_rank():
    def kern(u, out):
        out[i, j] = u[i, j]

    with pytest.raises(RecognitionError, match="name each of the 2 dims"):
        recognize(kern, shape=(8, 8), indices=("i",))
    with pytest.raises(RecognitionError, match="where 'j' expected"):
        recognize(kern, shape=(8, 8), indices=("j", "i"))

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


@pytest.mark.parametrize(
    "tile,match",
    [
        ((64,), r"tile \(64,\) has 1 dims, shape \(512, 1024\) has 2"),
        ((60, 128), "dim 0 extent 60 must be a multiple of 8 below 512"),
        ((64, 100), "dim 1 extent 100 must be a multiple of 128 below 1024"),
    ],
    ids=["rank", "sublane", "lane"],
)
def test_check_tile_refuses_illegal_tiles(tile, match):
    """An explicit tile that breaks the (8, 128) block rule, or has the
    wrong rank, is refused before any kernel is built."""
    from repro.kernels import KernelPlanError
    from repro.kernels.stencil_apply import check_tile

    assert check_tile((512, 1024), (64, 128)) == (64, 128)
    with pytest.raises(KernelPlanError, match=match):
        check_tile((512, 1024), tile)


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
# windows over the unpadded operand: Mosaic high padding for the last
# window's overhang, and the zero halo built in the kernel
# -------------------------------------------------------------------------


def _heat_program(shape, order, boundary="zero"):
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    u = TimeFunction(name="u", grid=Grid(shape=shape), space_order=order)
    return Operator(Eq(u.dt, u.laplace), dt=0.1, boundary=boundary).program


def _wave_program(shape, order):
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    w = TimeFunction(name="w", grid=Grid(shape=shape), space_order=order,
                     time_order=2)
    return Operator(Eq(w.dt2, w.laplace), dt=1e-3, boundary="zero").program


def _pallas_matches_jnp(prog, state, tile, steps=4):
    """4 jitted steps of the Pallas kernels (interpreted) against the jnp
    lowering's, bitwise; returns the Pallas artifact's census.  The
    dispatch counters count traces, and ``api.compile`` shares artifacts
    between equal programs, so JAX's caches are cleared on both sides:
    this test traces its kernels, and leaves none traced for the next."""
    from repro import api, kernels
    from repro.api import Target

    jax.clear_caches()
    try:
        fast = api.compile(prog, Target(backend="pallas", pallas_tile=tile,
                                        pallas_interpret=True))
        want = jax.jit(
            lambda s: api.compile(prog, Target(backend="jnp")).time_loop(s, steps)
        )(state)
        kernels.reset_dispatch_stats()
        got = jax.jit(lambda s: fast.time_loop(s, steps))(state)
        n = fast.kernel_dispatches
        assert kernels.dispatch_stats().apply_calls == n["pallas_apply"]
        assert n["pallas_apply"] == n["apply"]
        assert kernels.dispatch_stats().window_copies == 0
    finally:
        jax.clear_caches()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    return n


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
    """Shapes whose last window overhangs the operand array: the overhang
    is Mosaic high padding of the window (``Window.pad``), not slack a
    pad allocates, so the kernel reads its operand in place (no
    ``window_source`` copy) — the unpadded level at a zero boundary,
    whose pad lowers to nothing, and the one wrapped copy at a periodic
    one — and 4 jitted steps are bitwise equal to the jnp lowering's.
    The interpreter fills the padding with NaN, so a halo point read from
    it would show; a window clamped at the array's end instead differs
    at 1000² so8."""
    from repro import api
    from repro.api import Target
    from repro.core.dialects import stencil
    from repro.core.lowering import halo_source, pad_folds
    from repro.kernels.stencil_apply import plan_apply, plan_windows

    prog = _heat_program((n, n), order, boundary)
    fast = api.compile(prog, Target(backend="pallas", pallas_tile=tile,
                                    pallas_interpret=True))
    (apply_op,) = [
        op for op in fast.local_ir.body.ops if isinstance(op, stencil.ApplyOp)
    ]
    pad = halo_source(apply_op.operands[0])
    folds = pad_folds(pad.op, "pallas")
    assert folds == (boundary == "zero")
    sources = [(pad.op.temp.type.bounds if folds else pad.type.bounds, folds)]
    rb = apply_op.result_bounds
    (window,) = plan_windows(apply_op, rb, plan_apply(apply_op, rb, tile, sources),
                             sources)
    assert any(window.pad)
    census = _pallas_matches_jnp(prog, (jnp.asarray(_rand((n, n), seed=n)),), tile)
    assert census["halo_pad_copies"] == (0 if folds else 1)


def _nemo_case(tile):
    from repro.frontends import nemo_traadv as nt

    shape = (6, 20, 260)
    return nt.program(shape), nt.make_state(jax.random.key(7), shape), tile


_ZERO_HALO_CASES = {
    "wave-2d-so4": lambda: (
        _wave_program((200, 300), 4),
        tuple(jnp.asarray(_rand((200, 300), seed=s)) for s in (1, 2)), (64, 128)),
    "heat-3d-so4-one-tile": lambda: (
        _heat_program((24, 24, 24), 4),
        (jnp.asarray(_rand((24, 24, 24), seed=3)),), None),
    "heat-3d-so4-tiled": lambda: (
        _heat_program((40, 48, 64), 4),
        (jnp.asarray(_rand((40, 48, 64), seed=4)),), (8, 16, 64)),
    "nemo-chooser": lambda: _nemo_case(None),
    "nemo-2x8x128": lambda: _nemo_case((2, 8, 128)),
}


@pytest.mark.parametrize("case", sorted(_ZERO_HALO_CASES))
def test_zero_halo_is_built_in_the_kernel(case):
    """Every ``comm.halo_pad`` of a zero-boundary program on one device
    lowers to nothing (``halo_pad_copies`` 0): the Pallas kernels read
    the unpadded operands, build the zero halo in VMEM at edge tiles, and
    4 jitted steps are bitwise equal to the jnp lowering's — for a
    second time level (wave), split and unsplit leading dims (3-D), and
    NEMO's chain of applies over temporaries, with the chooser's tiles
    and with (2, 8, 128)."""
    prog, state, tile = _ZERO_HALO_CASES[case]()
    census = _pallas_matches_jnp(prog, state, tile)
    assert census["halo_pad"] >= 1
    assert census["halo_pad_copies"] == 0


def test_folded_apply_under_vmap_matches_jnp():
    """``jax.vmap`` of a step whose pad folds (the serve engine's slot
    pools batch it so) adds a grid dim to the kernel; the edge tiles
    still read the spatial program ids, and every member matches jnp."""
    from repro import api
    from repro.api import Target

    prog = _heat_program((200, 300), 4)
    fast = api.compile(prog, Target(backend="pallas", pallas_tile=(64, 128),
                                    pallas_interpret=True))
    assert fast.kernel_dispatches["halo_pad_copies"] == 0
    ref = api.compile(prog, Target(backend="jnp"))
    u = jnp.stack([jnp.asarray(_rand((200, 300), seed=s)) for s in (5, 6, 7)])
    got = jax.jit(jax.vmap(fast.step()))(u)
    want = jax.jit(jax.vmap(ref.step()))(u)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -------------------------------------------------------------------------
# stencil.index inside a tiled kernel reads the grid's index, not the tile's
# -------------------------------------------------------------------------


def _index_program(shape):
    from repro.core.builder import Expr
    from repro.core.dialects import stencil
    from repro.frontends.oec_like import ProgramBuilder

    p = ProgramBuilder("index_probe", shape)
    u = p.input("u")
    out = p.output("out")

    def body(b, u):
        val = u.at(*([0] * len(shape)))
        for d in range(len(shape)):
            idx = Expr(b, b.insert(stencil.IndexOp(d)).results[0])
            val = val + idx * float(1000 ** (len(shape) - 1 - d))
        return val

    p.store(p.apply([p.load(u)], body), out)
    return p.finish()


@pytest.mark.parametrize("shape,tile", [((16, 256), (8, 128)), ((4, 16, 256), (2, 8, 128))])
def test_stencil_index_under_tiles(shape, tile):
    """The Pallas kernel (interpreted) over tiles smaller than the grid
    gives ``stencil.index`` the grid's index, as the jnp lowering does:
    at a (16, 256) grid in (8, 128) tiles, columns 126..129 read
    126..129, not 126, 127, 0, 1."""
    from repro import kernels
    from repro.api import Target, compile as api_compile

    prog = _index_program(shape)
    u = np.zeros(shape, np.float32)
    want = np.asarray(api_compile(prog, Target(backend="jnp"))(u, u)[0])
    kernels.reset_dispatch_stats()
    got = np.asarray(api_compile(prog, Target(
        backend="pallas", pallas_tile=tile, pallas_interpret=True))(u, u)[0])
    assert kernels.dispatch_stats().apply_calls == 1
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got.reshape(-1, shape[-1])[0, 126:130],
                                  [126.0, 127.0, 128.0, 129.0])


def test_stencil_index_in_a_fused_epoch():
    """The fused-epoch kernel keeps a body that reads ``stencil.index`` in
    its whole-shard mode, whose blocks carry the grid's coordinates."""
    from repro.api import Target, compile as api_compile

    prog = _index_program((16, 256))
    u = np.zeros((16, 256), np.float32)
    want = np.asarray(api_compile(prog, Target(backend="jnp"))(u, u)[0])
    fused = api_compile(prog, Target(backend="pallas", fused_epoch=True,
                                     pallas_interpret=True))
    assert fused.kernel_dispatches["fused_epoch"] == 1
    np.testing.assert_array_equal(np.asarray(fused(u, u)[0]), want)

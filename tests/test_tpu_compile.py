"""Compile rehearsals for a TPU v5e: the main path's kernels at the real
grid sizes, compiled for a described ``v5e:2x2`` topology with no chip
attached.  Nothing runs; what the chip's compiler refuses (a block that
breaks the (8, 128) rule, VMEM over its limit, a program over HBM)
fails here first.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import api
from repro.api import Target
from repro.core.passes.decompose import make_strategy_2d
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.frontends.oec_like import ProgramBuilder
from repro.frontends.psyclone_like import recognize

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _heat(n: int, order: int):
    grid = Grid(shape=(n, n))
    u = TimeFunction(name="u", grid=grid, space_order=order)
    return Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


def _compile_one_chip(program, target, one_chip, n: int):
    step = api.compile(program, target).step()
    x = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    return jax.jit(step).lower(x).compile()


def test_jnp_step_16384_so8(one_chip):
    compiled = _compile_one_chip(_heat(16384, 8), Target(), one_chip, 16384)
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("n,order", [(2048, 2), (16384, 8)])
@pytest.mark.parametrize(
    "knobs",
    [
        {},
        {"exchange_every": 4},
        {"exchange_every": 4, "fused_epoch": True},
    ],
    ids=["per-apply", "per-apply-k4", "fused-epoch-k4"],
)
def test_pallas_step(one_chip, n, order, knobs):
    """Per-apply kernels (one step, and the grown applies of an unfused
    k=4 epoch) and the fused-epoch megakernel compile natively."""
    target = Target(backend="pallas", pallas_interpret=False, **knobs)
    compiled = _compile_one_chip(_heat(n, order), target, one_chip, n)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize(
    "knobs,scope",
    [({}, "stencil.apply"),
     ({"exchange_every": 4, "fused_epoch": True}, "stencil.fused_epoch")],
    ids=["per-apply", "fused-epoch-k4"],
)
def test_pallas_kernel_is_named_by_its_ir_op(one_chip, knobs, scope):
    """A Pallas kernel takes its IR op's scope as its name: the TPU
    custom call, and so the op on the device trace, is ``%<scope>.<n>``
    under ``jit(<program>.step)/<scope>``, not an XLA-generated name."""
    prog = _heat(2048, 2)
    target = Target(backend="pallas", pallas_interpret=False, **knobs)
    text = _compile_one_chip(prog, target, one_chip, 2048).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        assert f"%{scope}." in line.split(" = ", 1)[0]
        assert f'jit({prog.name}.step)/{scope}/' in line


def _level_copies(text: str, shape: str) -> list:
    return [line for line in text.splitlines()
            if " copy(" in line and shape in line.split(" = ", 1)[-1][:24]]


def test_pallas_step_16384_so8_copies_no_level(one_chip):
    """The zero halo pad lowers to nothing: the Pallas kernel reads the
    unpadded level, its last windows' overhang is Mosaic high padding,
    and the step holds no ``pad`` and no level-sized ``copy``."""
    target = Target(backend="pallas", pallas_interpret=False)
    step = api.compile(_heat(16384, 8), target)
    assert step.kernel_dispatches["halo_pad_copies"] == 0
    x = jax.ShapeDtypeStruct((16384, 16384), jnp.float32, sharding=one_chip)
    text = jax.jit(step.step()).lower(x).compile().as_text()
    assert "tpu_custom_call" in text
    assert not [line for line in text.splitlines() if " pad(" in line]
    assert not _level_copies(text, "f32[16384,16384]")


def test_pallas_loop_16384_so8_copies_no_level_a_step(one_chip):
    """A kernel that reads the level in place cannot write the next
    level over it: the jitted 64-step loop runs two steps an iteration
    (``loop_unroll``), so the two level buffers alternate and the loop
    body copies nothing; the one level copy left is the call's input."""
    target = Target(backend="pallas", pallas_interpret=False)
    compiled = api.compile(_heat(16384, 8), target)
    assert compiled.loop_unroll == 2
    x = jax.ShapeDtypeStruct((16384, 16384), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda s: compiled.time_loop(s, 64)).lower((x,)).compile().as_text()
    assert len(_level_copies(text, "f32[16384,16384]")) == 1
    assert sum("tpu_custom_call" in line for line in text.splitlines()) == 2
    assert not [line for line in text.splitlines() if " pad(" in line]


def test_whole_shard_epoch_step(one_chip):
    """Wave's carried escape keeps a fused epoch untiled: at 1024² its
    whole shard fits the VMEM budget and compiles as one block."""
    w = TimeFunction(name="w", grid=Grid(shape=(1024, 1024)), space_order=2,
                     time_order=2)
    prog = Operator(Eq(w.dt2, w.laplace), dt=1e-3, boundary="zero").program
    target = Target(backend="pallas", pallas_interpret=False, exchange_every=2,
                    fused_epoch=True)
    step = api.compile(prog, target).step()
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _wave(n: int, order: int):
    w = TimeFunction(name="w", grid=Grid(shape=(n, n)), space_order=order,
                     time_order=2)
    return Operator(Eq(w.dt2, w.laplace), dt=1e-3, boundary="zero").program


def _heat3d(n: int, order: int):
    u = TimeFunction(name="u", grid=Grid(shape=(n, n, n)), space_order=order)
    return Operator(Eq(u.dt, u.laplace), dt=0.1, boundary="zero").program


def _oec_hyperdiffusion(n: int):
    """The OEC-like frontend's two-apply chain: a Laplacian, then the
    Laplacian of that temporary (4th-order horizontal diffusion), so the
    second kernel reads a temporary at offsets."""
    def lap(b, u):
        return u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1) - u.at(0, 0) * 4.0

    p = ProgramBuilder("hyperdiffusion", (n, n))
    u, out = p.input("u"), p.output("out")
    t = p.load(u)
    l1 = p.apply([t], lap)
    r = p.apply([t, p.apply([l1], lap)],
                lambda b, u, l2: u.at(0, 0) - l2.at(0, 0) * 0.05)
    p.store(r, out)
    return p.finish(boundary="zero")


def _psyclone_3d(shape):
    def kern(u, out):
        out[i, j, k] = (u[i, j, k - 1] + u[i, j, k + 1]) * 0.5

    return recognize(kern, shape=shape)


@pytest.mark.parametrize(
    "build,shape",
    [
        (lambda: _wave(16384, 8), (16384, 16384)),
        (lambda: _heat3d(1024, 8), (1024, 1024, 1024)),
        (lambda: _oec_hyperdiffusion(8192), (8192, 8192)),
        (lambda: _psyclone_3d((75, 1021, 1442)), (75, 1021, 1442)),
    ],
    ids=["devito-wave2d-so8-16384", "devito-heat3d-so8-1024",
         "oec-hyperdiffusion-8192", "psyclone-3d-orca025"],
)
def test_pallas_step_program_families(one_chip, build, shape):
    """The program families no cell runs, on ``Target()``'s TPU path:
    every apply is a Pallas kernel, every zero halo is built inside it
    (no pad copies a field), and one step compiles and fits a chip."""
    prog = build()
    step = api.compile(prog, Target(backend="pallas", pallas_interpret=False))
    n = step.kernel_dispatches
    assert n["pallas_apply"] == n["apply"] >= 1
    assert n["halo_pad_copies"] == 0
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    levels = len(step.input_indices)
    compiled = jax.jit(step.step()).lower(*(x,) * levels).compile()
    text = compiled.as_text()
    assert sum("tpu_custom_call" in line for line in text.splitlines()) == n["apply"]
    assert not [line for line in text.splitlines() if " pad(" in line]
    assert _device_bytes(compiled) < HBM_BYTES


def test_2x2_jnp_step_k4(topo):
    """The decomposed path on the 2x2 host: halo exchanges become
    collective-permutes, and each device's share fits its HBM."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("x", "y"))
    target = Target(mesh=mesh, strategy=make_strategy_2d((2, 2)), exchange_every=4)
    step = api.compile(_heat(16384, 8), target).step()
    x = jax.ShapeDtypeStruct(
        (16384, 16384), jnp.float32, sharding=NamedSharding(mesh, P("x", "y"))
    )
    compiled = jax.jit(step).lower(x).compile()
    assert "collective-permute" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def _peak_2x2_loop(topo, backend: str) -> int:
    """``memory_analysis()`` peak of the 2x2 cell's program: 65536²
    so8 heat decomposed 2x2, a jitted 16-step ``time_loop``."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("x", "y"))
    target = Target(mesh=mesh, strategy=make_strategy_2d((2, 2)),
                    backend=backend, pallas_interpret=False)
    compiled = api.compile(_heat(65536, 8), target)
    x = jax.ShapeDtypeStruct(
        (65536, 65536), jnp.float32, sharding=NamedSharding(mesh, P("x", "y"))
    )
    exe = jax.jit(lambda s: compiled.time_loop(s, 16)).lower((x,)).compile()
    return exe.memory_analysis().peak_memory_in_bytes


def test_2x2_pallas_loop_fits_like_jnp(topo):
    """The Pallas loop on 32768² shards holds no second padded copy: its
    peak stays within 1% of the jnp loop's, inside one chip's HBM."""
    pallas = _peak_2x2_loop(topo, "pallas")
    assert pallas <= 1.01 * _peak_2x2_loop(topo, "jnp")
    assert pallas < HBM_BYTES


def test_traadv_orca025_loop_fits_one_chip(topo):
    """NEMO tracer advection on the global ORCA025-L75 grid, a jitted
    16-step ``time_loop``: every apply compiles as a Pallas kernel and the
    program fits one chip's HBM.  The chip keeps ``(75, 1021, 1442)`` with
    its two minor dims swapped, so the step runs the permuted program and
    XLA copies no field from one layout to the other: the only full-size
    copies are the loop's tracer and the static fields ``jit`` returns.
    No temporary is padded: the kernels build every zero halo, and the
    peak falls below the 10.83 GB the 12 pads' copies took."""
    from repro.frontends import nemo_traadv

    shape = (75, 1021, 1442)
    prog = nemo_traadv.program(shape)
    target = Target(mesh=Mesh(np.array(topo.devices[:1]), ("x",)),
                    backend="pallas", pallas_interpret=False)
    assert api.layout_perm(prog, target) == (0, 2, 1)
    step = api.compile(prog, target)
    n = step.kernel_dispatches
    assert n["pallas_apply"] == n["apply"]
    assert n["halo_pad"] == 12 and n["halo_pad_copies"] == 0
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    exe = jax.jit(lambda s: step.time_loop(s, 16)).lower((x,) * 8).compile()
    # 10.83 GB while the 12 pads copied their temporaries
    assert exe.memory_analysis().peak_memory_in_bytes < 10.83e9
    lines = exe.as_text().splitlines()
    assert sum("tpu_custom_call" in line for line in lines) == (
        n["apply"] * step.loop_unroll)
    assert not [line for line in lines if " pad(" in line]
    copies = [line for line in lines
              if " copy(" in line and "f32[75,1021,1442]" in line.split(" = ", 1)[-1][:20]]
    assert len(copies) == 8
    assert all("{1,2,0" in line for line in copies)

"""Multi-pod stencil dry-run: the paper's strong-scaling configuration on
the production mesh — 512 virtual devices, 2 pods × (16×16).

Lowers a 3-D so8 acoustic-wave stencil decomposed 8×8×8 over 512 ranks,
compiles it (proving the halo-exchange collectives schedule), and prints
the memory/cost/collective analysis.

    PYTHONPATH=src python examples/multipod_stencil.py
"""
import os

# a CPU-only tool: virtual CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import re  # noqa: E402

import numpy as np  # noqa: E402


def main() -> None:
    import jax
    from jax.sharding import Mesh

    from repro import api, compile_cache
    from repro.core.passes.decompose import SlicingStrategy
    from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction

    compile_cache.enable()

    assert len(jax.devices()) == 512, len(jax.devices())
    mesh = Mesh(
        np.array(jax.devices()).reshape(8, 8, 8), ("x", "y", "z")
    )
    strategy = SlicingStrategy((8, 8, 8), ("x", "y", "z"), (0, 1, 2))

    shape = (512, 512, 512)
    g = Grid(shape=shape, extent=(1.0,) * 3)
    u = TimeFunction(name="u", grid=g, space_order=8, time_order=2)
    op = Operator(Eq(u.dt2, 1.0 * u.laplace), dt=1e-7, boundary="zero")

    target = api.Target(mesh=mesh, strategy=strategy, overlap=True)
    artifact = api.compile(op.program, target)
    lowered = artifact.lower()
    compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per program
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    n_permute = len(re.findall(r"collective-permute", hlo))
    print(f"mesh: 8x8x8 = {mesh.size} devices; grid {shape} so8 wave")
    print(f"compile OK; per-device args "
          f"{mem.argument_size_in_bytes/2**20:.1f} MiB, "
          f"temps {mem.temp_size_in_bytes/2**20:.1f} MiB")
    print(f"per-device flops {cost.get('flops', 0):.3e}, "
          f"bytes {cost.get('bytes accessed', 0):.3e}")
    print(f"collective-permute ops in HLO: {n_permute} "
          "(halo exchanges, 3 axes x 2 dirs x radius batches)")
    # the canonical comm-level IR: overlap is visible as starts → interior
    # apply → wait → frame applies (artifact.local_ir)
    local = artifact.local_ir
    from repro.core.dialects import comm

    print(f"pipeline: {artifact.pipeline_report.spec}")
    print("comm IR : " + " -> ".join(_rle(o.name for o in local.body.ops)))
    starts = [o for o in local.body.ops
              if isinstance(o, comm.ExchangeStartOp)]
    halo_bytes = sum(int(np.prod(s.size)) for s in starts) * 4
    print(f"comm model: {len(starts)} exchange_start(s), "
          f"{halo_bytes/2**20:.2f} MiB halo/rank/step "
          f"-> {halo_bytes/50e9*1e6:.0f} µs on 50 GB/s ICI")


def _rle(names):
    """['a','a','b'] → ['a x2', 'b'] — compact op-sequence printing."""
    out: list = []
    for n in names:
        short = n.split(".", 1)[-1]
        if out and out[-1][0] == short:
            out[-1][1] += 1
        else:
            out.append([short, 1])
    return [f"{n} x{c}" if c > 1 else n for n, c in out]


if __name__ == "__main__":
    main()

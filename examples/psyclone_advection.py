"""PSyclone-path example: stencils *recognized* from loop-style code
(the paper's Fortran-frontend story), then fused and decomposed by the
shared stack.

    PYTHONPATH=src python examples/psyclone_advection.py
"""
import numpy as np


# Loop-style kernels, as a scientist would write them (paper §5.2: the
# PSyclone backend identifies stencils from Fortran loops; here from
# Python loop bodies with i/j/k index conventions).


def pw_advection(u, v, w, su, sv, sw):
    su[i, j, k] = 0.5 * (
        u[i, j, k] * (v[i, j, k] + v[i + 1, j, k])
        - u[i - 1, j, k] * (v[i - 1, j, k] + v[i, j, k])
    )
    sv[i, j, k] = 0.5 * (
        v[i, j, k] * (w[i, j, k] + w[i, j + 1, k])
        - v[i, j - 1, k] * (w[i, j - 1, k] + w[i, j, k])
    )
    sw[i, j, k] = 0.5 * (
        w[i, j, k] * (u[i, j, k] + u[i, j, k + 1])
        - w[i, j, k - 1] * (u[i, j, k - 1] + u[i, j, k])
    )


def main() -> None:
    import jax.numpy as jnp

    from repro import api, compile_cache
    from repro.core.dialects import stencil
    from repro.core.passes import cse_apply_bodies, dce, fuse_applies
    from repro.frontends.psyclone_like import build_stencil_func

    compile_cache.enable()
    shape = (64, 64, 32)
    func = build_stencil_func(pw_advection, shape)
    n_raw = sum(1 for op in func.body.ops if isinstance(op, stencil.ApplyOp))

    fuse_applies(func)
    cse_apply_bodies(func)
    dce(func)
    n_fused = sum(1 for op in func.body.ops if isinstance(op, stencil.ApplyOp))
    print(f"recognized {n_raw} stencil computations -> fused into {n_fused} "
          f"region(s)   (paper fig. 10: PW advection 3 -> 1)")

    prog = api.Program(func, boundary="periodic")
    print("\n--- fused stencil IR (what the fingerprint hashes) ---")
    print("\n".join(prog.ir_text().splitlines()[:20]) + "\n  ...")

    step = api.compile(prog, api.Target())
    rng = np.random.default_rng(0)
    args = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for _ in prog.field_args]
    outs = step(*args)
    print(f"\nran fused kernel: {len(outs)} output fields, "
          f"all finite: {all(bool(jnp.isfinite(o).all()) for o in outs)}")


if __name__ == "__main__":
    main()

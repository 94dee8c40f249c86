"""Context-parallelism correctness worker (run in a SUBPROCESS with 8
virtual devices, tests/test_context_parallel.py):

    python tests/cp_worker.py <scenario>

Asserts that ``repro.dist.context_parallel`` — the sequence-dimension
halo exchange routed through the shared ``dmp``/``comm`` stencil
machinery — produces results **bitwise identical** to the single-device
reference, the same guarantee tests/dist_worker.py asserts for stencil
programs.  Exit 0 = all assertions passed.
"""
import os
import sys

# a CPU-only tool: virtual CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.dist.context_parallel import (  # noqa: E402
    SeqHaloSpec,
    causal_conv_cp,
    comm_ir_text,
    seq_halo_exchange,
    sliding_window_attention_cp,
)


def _mesh(n, axis="seq"):
    return Mesh(np.array(jax.devices()[:n]), (axis,))


def check(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if not np.array_equal(got, want):
        print(
            f"MISMATCH in {name}: max abs diff {np.abs(got - want).max():.3e}"
        )
        sys.exit(1)
    print(f"ok: {name}")


def scenario_exchange(boundary):
    """The raw exchange: distributed halos == numpy slicing of the global
    array (bitwise — the exchange only moves data)."""
    from jax.sharding import PartitionSpec as P

    B, S, C = 2, 64, 6
    n, lo, hi = 8, 3, 2
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    spec = SeqHaloSpec(axis="seq", n_shards=n, halo_lo=lo, halo_hi=hi,
                       seq_dim=1, boundary=boundary)
    mesh = _mesh(n)

    def local(x_loc):
        return seq_halo_exchange(x_loc, spec, distributed=True)

    got = jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P(None, "seq"),
            out_specs=P(None, "seq"), check_vma=False,
        )
    )(x)  # [B, n*(lo + S/n + hi), C] concatenated per-shard halo blocks
    S_loc = S // n
    got = np.asarray(got).reshape(B, n, lo + S_loc + hi, C)

    xp = np.asarray(x)
    if boundary == "periodic":
        pad = np.concatenate([xp[:, -lo:], xp, xp[:, :hi]], axis=1)
    else:
        pad = np.pad(xp, ((0, 0), (lo, hi), (0, 0)))
    for r in range(n):
        want = pad[:, r * S_loc : r * S_loc + lo + S_loc + hi]
        check(f"exchange-{boundary}-shard{r}", got[:, r], want)


def scenario_conv():
    """Distributed Mamba causal conv == single-device _causal_conv,
    bitwise (fp32; the halo is the conv's stitching state)."""
    from repro.models.mamba import _causal_conv

    B, S, C, K = 2, 64, 16, 4
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((B, S, C)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, C)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((C,)), jnp.float32)

    want = jax.jit(lambda x, w, b: _causal_conv(x, w, b)[0])(x, w, b)
    got = jax.jit(
        lambda x, w, b: causal_conv_cp(x, w, b, _mesh(8), "seq")
    )(x, w, b)
    check("causal-conv-8-ranks", got, want)


def scenario_window_attention():
    """Sequence-parallel sliding-window attention == the same window
    kernel on one device (bitwise: per-query arithmetic is independent of
    the decomposition)."""
    B, S, H, D, W = 2, 64, 2, 8, 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    want = jax.jit(
        lambda q, k, v: sliding_window_attention_cp(q, k, v, W, _mesh(1), "x")
    )(q, k, v)
    got = jax.jit(
        lambda q, k, v: sliding_window_attention_cp(q, k, v, W, _mesh(8), "seq")
    )(q, k, v)
    check("window-attention-8-ranks", got, want)


def scenario_window_vs_dense():
    """The window kernel agrees with the dense masked reference (tight
    tolerance — different reduction shapes, so not bitwise)."""
    B, S, H, D, W = 2, 64, 2, 8, 8
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    s = np.einsum("bthd,bshd->bhts", np.asarray(q), np.asarray(k)) / np.sqrt(D)
    pos = np.arange(S)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - W)
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bhts,bshd->bthd", p, np.asarray(v))

    got = jax.jit(
        lambda q, k, v: sliding_window_attention_cp(q, k, v, W, _mesh(8), "seq")
    )(q, k, v)
    if not np.allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5):
        print(f"MISMATCH vs dense: {np.abs(np.asarray(got) - want).max():.3e}")
        sys.exit(1)
    print("ok: window-vs-dense-reference")


def scenario_comm_ir():
    """The exchange really lowers through the comm dialect (halo_pad +
    exchange_start/wait), not a bespoke path."""
    spec = SeqHaloSpec(axis="seq", n_shards=8, halo_lo=3, halo_hi=0)
    ops = comm_ir_text((2, 8, 6), spec)
    assert "comm.halo_pad" in ops, ops
    assert "comm.exchange_start" in ops, ops
    assert "comm.wait" in ops, ops
    print("ok: comm-dialect-ir")


SCENARIOS = {
    "exchange-zero": lambda: scenario_exchange("zero"),
    "exchange-periodic": lambda: scenario_exchange("periodic"),
    "conv": scenario_conv,
    "window-attention": scenario_window_attention,
    "window-vs-dense": scenario_window_vs_dense,
    "comm-ir": scenario_comm_ir,
}


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    for n in list(SCENARIOS) if which == "all" else [which]:
        SCENARIOS[n]()
    print("ALL OK")

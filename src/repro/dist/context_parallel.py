"""Sequence-dimension context parallelism via the stencil halo stack.

The paper's thesis — distributed-memory abstractions as *shared
infrastructure* — applied to the model layer: a Mamba causal conv reads
``[t-(K-1), t]`` and sliding-window attention reads ``[t-(W-1), t]``;
both are **stencils on the sequence axis** (DESIGN.md §4).  Under
sequence parallelism their shard-boundary reads are therefore halo
exchanges, and this module expresses them through exactly the machinery
the stencil DSLs use, instead of a bespoke ring path:

1. declare the exchange as a ``dmp.swap`` over a **1-D GridAttr whose
   grid axis is the sequence dimension** (``_build_swap_func``);
2. lower it with the shared ``lower_dmp_to_comm`` pass — the *canonical*
   dmp → comm (≈ MPI) step every stencil program takes — yielding
   ``comm.halo_pad`` + ``comm.exchange_start`` + ``comm.wait`` ops;
3. execute those comm ops with the shared comm-level executor
   (``run_func_dataflow`` / ``StencilInterpreter``) inside
   ``shard_map``, which turns each ``exchange_start`` into a
   ``lax.ppermute`` whose pairs come from the one shared
   ``comm.permute_pairs`` construction.

One exchange abstraction drives stencil *and* model parallelism — the
distribution-correctness guarantees of ``tests/test_distributed.py``
transfer to the LM layers by construction.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import api
from repro.core import ir
from repro.core.dialects import dmp, stencil
from repro.core.lowering import run_func_dataflow
from repro.core.passes.decompose import make_strategy_1d


@dataclasses.dataclass(frozen=True)
class SeqHaloSpec:
    """Declarative description of one sequence-halo exchange.

    ``halo_lo`` elements arrive from the left (earlier-sequence)
    neighbour, ``halo_hi`` from the right; ``boundary`` fills physical
    sequence edges ("zero" = causal start-of-sequence state).
    """

    axis: str
    n_shards: int
    halo_lo: int
    halo_hi: int = 0
    seq_dim: int = 1
    boundary: str = "zero"


def _build_swap_func(local_shape: tuple, spec: SeqHaloSpec) -> ir.FuncOp:
    """IR for the exchange: a temp of local core bounds flowing through a
    ``dmp.swap`` whose grid is 1-D over the sequence axis.

    This is the same declarative payload a decomposed stencil program
    carries (GridAttr + ExchangeDecls), built by the same strategy
    object (``make_strategy_1d``) — not a re-implementation.
    """
    strategy = make_strategy_1d(spec.n_shards, axis=spec.axis, dim=spec.seq_dim)
    core = stencil.Bounds.from_shape(local_shape)
    lo = tuple(spec.halo_lo if d == spec.seq_dim else 0
               for d in range(len(local_shape)))
    hi = tuple(spec.halo_hi if d == spec.seq_dim else 0
               for d in range(len(local_shape)))
    decls, schedule = strategy.exchanges(core, lo, hi, corners=False)
    func = ir.FuncOp("seq_halo", [stencil.TempType(core)])
    swap = dmp.SwapOp(
        func.body.args[0],
        strategy.grid,
        decls,
        result_bounds=core.grow(lo, hi),
        boundary=spec.boundary,
        schedule=schedule,
    )
    func.body.add_op(swap)
    func.body.add_op(ir.ReturnOp([swap.results[0]]))
    return func


@lru_cache(maxsize=128)
def _comm_func(local_shape: tuple, spec: SeqHaloSpec) -> ir.FuncOp:
    """The exchange after the shared dmp→comm lowering (paper fig. 4):
    ``comm.halo_pad`` + per-round ``comm.exchange_start``/``comm.wait``.

    Lowered through ``repro.api``'s process-wide fingerprint-keyed cache
    — the same cache stencil compiles use, visible in
    ``repro.api.cache_stats()`` — with a thin shape-keyed lru memo on
    top so the per-trace hot path skips even the IR build + hash."""
    return api.lower_ir(
        _build_swap_func(local_shape, spec), "lower-comm", boundary=spec.boundary
    )


def comm_ir_text(local_shape: tuple, spec: SeqHaloSpec) -> str:
    """Printable comm-dialect IR of the exchange (debug / DESIGN.md)."""
    func = _comm_func(tuple(local_shape), spec)
    return "\n".join(op.name for op in func.body.ops)


def seq_halo_exchange(x_loc, spec: SeqHaloSpec, *, distributed: bool = True):
    """Halo-grow one rank's sequence shard.

    ``x_loc``: the local shard (called inside ``shard_map`` when
    ``distributed``); returns the shard grown by (halo_lo, halo_hi)
    along ``seq_dim``, halos filled by neighbour exchange (``ppermute``)
    or the boundary condition at physical edges.

    With ``distributed=False`` the exchange runs in local-emulation mode
    (the single-rank path the stencil lowering uses for meshless
    compiles): zero-BC halos stay zero, periodic halos wrap locally.
    """
    func = _comm_func(tuple(x_loc.shape), spec)
    (out,) = run_func_dataflow(
        func,
        [x_loc],
        axis_sizes={spec.axis: spec.n_shards},
        distributed=distributed,
    )
    return out


def context_parallel(
    fn: Callable,
    mesh: Mesh,
    spec: SeqHaloSpec,
    *,
    out_seq_dim: Optional[int] = None,
) -> Callable:
    """Lift a *local window function* to a sequence-parallel global one.

    ``fn(x_halo, shard_start, *rest)`` receives the halo-grown local
    shard plus the global sequence offset of its core's first element,
    and returns the core-shaped local output.  The wrapper shard_maps it
    over ``spec.axis`` with the halo exchange (dmp/comm machinery)
    prepended; ``rest`` operands are replicated (weights).
    """
    out_dim = spec.seq_dim if out_seq_dim is None else out_seq_dim

    def global_fn(x, *rest):
        n = spec.n_shards
        S = x.shape[spec.seq_dim]
        assert S % n == 0, (S, n)
        in_entries = [None] * x.ndim
        in_entries[spec.seq_dim] = spec.axis
        x_spec = P(*in_entries)

        def local(x_loc, *rest_loc):
            xh = seq_halo_exchange(x_loc, spec, distributed=n > 1)
            start = jax.lax.axis_index(spec.axis) * (S // n)
            return fn(xh, start, *rest_loc)

        if n <= 1:
            # meshless / single-rank reference path — same code, local
            # emulation of the exchange (mirrors the stencil lowering)
            return fn(seq_halo_exchange(x, spec, distributed=False),
                      jnp.int32(0), *rest)

        local_in = jax.ShapeDtypeStruct(
            tuple(s // n if d == spec.seq_dim else s
                  for d, s in enumerate(x.shape)),
            x.dtype,
        )
        out_shape = jax.eval_shape(
            lambda xl, *r: fn(
                seq_halo_exchange(xl, spec, distributed=False),
                jnp.int32(0), *r,
            ),
            local_in,
            *rest,
        )

        def out_spec_of(s):
            entries = [None] * len(s.shape)
            if out_dim < len(s.shape):
                entries[out_dim] = spec.axis
            return P(*entries)

        out_specs = jax.tree.map(out_spec_of, out_shape)
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(x_spec,) + tuple(P() for _ in rest),
            out_specs=out_specs,
            check_vma=False,
        )(x, *rest)

    return global_fn


# --------------------------------------------------------------------------
# Concrete context-parallel layers
# --------------------------------------------------------------------------


def causal_conv_cp(x, w, b, mesh: Mesh, axis: str):
    """Sequence-parallel Mamba causal conv (``models.mamba._causal_conv``
    distributed over ``axis``).

    The conv reads ``[t-(K-1), t]`` — halo K-1, one-sided — so the left
    halo *is* the conv's stitching state: the local kernel is literally
    the single-device ``_causal_conv`` with the exchanged halo passed as
    its ``state``.  x: [B, S, C] (global), w: [K, C], b: [C].
    """
    from repro.models.mamba import _causal_conv

    K = w.shape[0]
    spec = SeqHaloSpec(
        axis=axis, n_shards=int(mesh.shape.get(axis, 1)),
        halo_lo=K - 1, halo_hi=0, seq_dim=1, boundary="zero",
    )

    def local(xh, start, w_l, b_l):
        state, core = xh[:, : K - 1], xh[:, K - 1:]
        y, _ = _causal_conv(core, w_l, b_l, state)
        return y

    return context_parallel(local, mesh, spec)(x, w, b)


def sliding_window_attention_cp(q, k, v, window: int, mesh: Mesh, axis: str):
    """Sequence-parallel sliding-window self-attention.

    q/k/v: [B, S, H, D] (MHA; global arrays).  Each query attends the
    causal window ``[t-W+1, t]`` — a radius-(W-1) one-sided sequence
    stencil — so K/V need a left halo of W-1 and *no* score entry ever
    crosses more than one shard boundary.  The windows are gathered
    explicitly ([B, S_loc, W] score blocks), making the arithmetic per
    query independent of the decomposition — distributed equals
    single-device bitwise, the same guarantee the stencil tests assert.
    """
    W = int(window)
    n = int(mesh.shape.get(axis, 1))

    def local(kv_h, start, q_l):
        k_h, v_h = kv_h[0], kv_h[1]
        B, S_loc = q_l.shape[0], q_l.shape[1]
        D = q_l.shape[-1]
        # window gather: win[t, w] = halo-extended seq index t + w,
        # i.e. absolute position (start + t) - (W-1) + w
        idx = jnp.arange(S_loc)[:, None] + jnp.arange(W)[None, :]
        kw = jnp.take(k_h, idx, axis=1)   # [B, S_loc, W, H, D]
        vw = jnp.take(v_h, idx, axis=1)
        s = jnp.einsum("bthd,btwhd->bthw", q_l, kw) / jnp.sqrt(
            jnp.float32(D)
        ).astype(q_l.dtype)
        abs_kv = (start + jnp.arange(S_loc))[:, None] - (W - 1) + jnp.arange(W)
        s = jnp.where(abs_kv[None, :, None, :] >= 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bthw,btwhd->bthd", p, vw)

    # k and v share one exchange (stacked leading dim)
    kv = jnp.stack([k, v], axis=0)
    kv_spec = SeqHaloSpec(axis=axis, n_shards=n, halo_lo=W - 1, halo_hi=0,
                          seq_dim=2, boundary="zero")

    if n <= 1:
        kv_h = seq_halo_exchange(kv, kv_spec, distributed=False)
        return local(kv_h, jnp.int32(0), q)

    S = q.shape[1]
    assert S % n == 0, (S, n)

    def shard_local(kv_loc, q_loc):
        kv_h = seq_halo_exchange(kv_loc, kv_spec, distributed=True)
        start = jax.lax.axis_index(axis) * (S // n)
        return local(kv_h, start, q_loc)

    return jax.shard_map(
        shard_local,
        mesh=mesh,
        in_specs=(P(None, None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )(kv, q)


def mamba_conv_exchange_bytes(cfg, B: int, seq_shards: int) -> int:
    """Wire bytes per layer for the Mamba conv halo under sequence
    parallelism — the roofline-table hook (DESIGN.md §7): (K-1) steps ×
    d_inner channels × batch, once per direction boundary."""
    d_inner = cfg.ssm_expand * cfg.d_model
    return 4 * B * (cfg.ssm_conv_width - 1) * d_inner * max(seq_shards - 1, 0)

"""Top-k token-choice MoE with capacity-based dispatch and **explicit
expert parallelism** (all-to-all under shard_map).

Why not GShard one-hot dispatch einsums: with few experts and long
sequences (olmoe: E=64, 1M tokens/batch) the [tokens, E, capacity]
dispatch tensor is astronomically large — the dispatch-matrix formulation
only works when capacity is tiny.  The production formulation is
scatter-based:

  1. each (data, model) rank takes its 1/|model| slice of the local
     tokens (activations are model-replicated),
  2. routes them into a [E, C, D] send buffer (scatter, capacity C per
     (source-rank, expert) — overflow drops to the residual),
  3. ``all_to_all`` over the *model* axis re-buckets by expert owner
     (E/|model| experts per rank),
  4. dense per-expert SwiGLU on [E_loc, |model|·C, D] (MXU-friendly),
  5. reverse all_to_all, gather+gate-combine, psum over the model axis
     (each rank contributed a disjoint token slice).

Without a mesh the same code runs the P=1 path (no collectives) — used
by the CPU smoke tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import active_mesh, active_rules, shard
from repro.models.layers import dense_init


def moe_init(key, cfg):
    assert cfg.moe is not None
    E = cfg.moe.num_experts
    d, dff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, E, scale=0.02),
        "wi": jax.random.normal(ks[1], (E, d, dff), jnp.float32) / (d**0.5),
        "wu": jax.random.normal(ks[2], (E, d, dff), jnp.float32) / (d**0.5),
        "wo": jax.random.normal(ks[3], (E, dff, d), jnp.float32) / (dff**0.5),
    }


# -- core (runs per-rank inside shard_map, or whole-array without a mesh) ----


def _route(p, xt, cfg, dtype):
    """xt: [n, D] → (gate_vals [n,K], gate_idx [n,K], aux)."""
    mcfg = cfg.moe
    logits = jnp.einsum(
        "nd,de->ne", xt.astype(dtype), p["router"].astype(dtype),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, mcfg.top_k)
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)
    density = jnp.zeros(mcfg.num_experts).at[gate_idx.reshape(-1)].add(1.0)
    density = density / gate_idx.size
    lb_loss = mcfg.num_experts * jnp.sum(density * probs.mean(0))
    z_loss = mcfg.router_z_loss * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1))
    )
    return gate_vals, gate_idx, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}


def _dispatch_scatter(xt, gate_idx, E: int, C: int):
    """Scatter tokens into [E, C, D]; returns (buffer, slot_of [n,K], kept)."""
    n, K = gate_idx.shape
    flat_e = gate_idx.reshape(-1)                       # [n*K]
    # rank of each assignment within its expert bucket
    onehot_pos = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot_pos, axis=0) - 1            # [n*K, E]
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    kept = slot < C
    dest = jnp.where(kept, flat_e * C + slot, E * C)    # overflow → dropped row
    buf = jnp.zeros((E * C + 1, xt.shape[1]), xt.dtype)
    buf = buf.at[dest].add(jnp.repeat(xt, K, axis=0) * kept[:, None].astype(xt.dtype))
    return buf[: E * C].reshape(E, C, xt.shape[1]), dest, kept


def _expert_ffn(p, h_in, dtype):
    """h_in: [E_loc, T, D] → [E_loc, T, D] through each expert's SwiGLU."""
    wi, wu, wo = p["wi"], p["wu"], p["wo"]
    g = jnp.einsum("etd,edf->etf", h_in.astype(dtype), wi.astype(dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("etd,edf->etf", h_in.astype(dtype), wu.astype(dtype),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dtype)
    return jnp.einsum("etf,efd->etd", h, wo.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def _combine(buf_out, dest, kept, gate_vals, n: int, K: int, D: int, dtype):
    flat = buf_out.reshape(-1, D)
    flat = jnp.concatenate([flat, jnp.zeros((1, D), flat.dtype)], axis=0)
    per_assignment = flat[dest]                          # [n*K, D]
    w = (gate_vals.reshape(-1) * kept).astype(dtype)
    return (per_assignment * w[:, None]).reshape(n, K, D).sum(axis=1)


def moe_apply(p, x, cfg, dtype, ep_axis: str = "model"):
    """x: [B,S,D] → ([B,S,D], aux).  Uses EP over ``ep_axis`` when a mesh
    with that axis is active and E % axis_size == 0."""
    mesh = active_mesh()
    B, S, D = x.shape
    E, K = cfg.moe.num_experts, cfg.moe.top_k
    cf = cfg.moe.capacity_factor

    if mesh is not None and ep_axis in mesh.shape and E % mesh.shape[ep_axis] == 0 and mesh.shape[ep_axis] > 1:
        rules = active_rules()
        batch_spec = rules.physical("batch") if rules else ("data",)
        n_ep = mesh.shape[ep_axis]
        # batch too small for the batch axes (decode / long-context)?
        # replicate it instead of sharding.
        from repro.dist.sharding import _valid_spec

        x_spec = _valid_spec(mesh, P(batch_spec, None, None), x.shape)
        b_axes = x_spec[0]
        n_b = 1
        for a in (b_axes if isinstance(b_axes, tuple) else (b_axes,)) or ():
            n_b *= mesh.shape.get(a, 1) if a else 1
        tokens_per_shard = (B // max(n_b, 1)) * S
        small = tokens_per_shard % n_ep != 0

        def ep_block_small(params, xl):
            """Decode-friendly EP: routing is model-replicated; each rank
            runs only its resident experts and psums the combined output.
            No all_to_all — the token count is tiny (one step per request),
            so the [n,D] psum is cheaper than re-bucketing."""
            b, s, d = xl.shape
            xt = xl.reshape(b * s, d)
            gate_vals, gate_idx, aux = _route(params, xt, cfg, dtype)
            C = max(1, -(-(b * s * K) // E))  # ceil; no drops at decode
            buf, dest, kept = _dispatch_scatter(xt.astype(dtype), gate_idx, E, C)
            e_loc = E // n_ep
            ridx = jax.lax.axis_index(ep_axis)
            buf_loc = jax.lax.dynamic_slice_in_dim(buf, ridx * e_loc, e_loc, 0)
            out_loc = _expert_ffn(params, buf_loc, dtype)
            out = jnp.zeros((E, C, d), out_loc.dtype)
            out = jax.lax.dynamic_update_slice_in_dim(out, out_loc, ridx * e_loc, 0)
            yt = _combine(out, dest, kept, gate_vals, b * s, K, d, dtype)
            yt = jax.lax.psum(yt, ep_axis)
            return yt.reshape(b, s, d), aux

        def ep_block(params, xl):
            # xl: [b_loc, S, D] (model-replicated); take this rank's slice
            b, s, d = xl.shape
            xt = xl.reshape(b * s, d)
            n_total = b * s
            assert n_total % n_ep == 0, (n_total, n_ep)
            n_loc = n_total // n_ep
            ridx = jax.lax.axis_index(ep_axis)
            xt_slice = jax.lax.dynamic_slice_in_dim(xt, ridx * n_loc, n_loc, 0)
            gate_vals, gate_idx, aux = _route(params, xt_slice, cfg, dtype)
            C = max(1, int(n_loc * K * cf) // E)
            buf, dest, kept = _dispatch_scatter(
                xt_slice.astype(dtype), gate_idx, E, C
            )
            # all_to_all: expert dim split across ranks, contributions concat
            buf = jax.lax.all_to_all(
                buf, ep_axis, split_axis=0, concat_axis=1, tiled=True
            )  # [E/n_ep, n_ep*C, D]
            out = _expert_ffn(params, buf, dtype)  # params carry local experts
            out = jax.lax.all_to_all(
                out, ep_axis, split_axis=1, concat_axis=0, tiled=True
            )  # back to [E, C, D]
            yt = _combine(out, dest, kept, gate_vals, n_loc, K, d, dtype)
            # reassemble full token set over the model axis
            full = jnp.zeros((n_total, d), dtype)
            full = jax.lax.dynamic_update_slice_in_dim(full, yt, ridx * n_loc, 0)
            full = jax.lax.psum(full, ep_axis)
            aux = {k: jax.lax.pmean(v, ep_axis) for k, v in aux.items()}
            return full.reshape(b, s, d), aux

        # expert weights enter sharded over their expert dim (EP-resident);
        # the router is replicated.
        param_specs = {
            "router": P(None, None),
            "wi": P(ep_axis, None, None),
            "wu": P(ep_axis, None, None),
            "wo": P(ep_axis, None, None),
        }
        y, aux = jax.shard_map(
            ep_block_small if small else ep_block,
            mesh=mesh,
            in_specs=(param_specs, x_spec),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(p, x)
        return y.astype(dtype), aux

    # ---- single-rank path (no mesh / EP not possible) ----
    xt = x.reshape(B * S, D)
    gate_vals, gate_idx, aux = _route(p, xt, cfg, dtype)
    C = max(1, int(B * S * K * cf) // E)
    C = min(C, B * S)
    buf, dest, kept = _dispatch_scatter(xt.astype(dtype), gate_idx, E, C)
    out = _expert_ffn(p, buf, dtype)
    yt = _combine(out, dest, kept, gate_vals, B * S, K, D, dtype)
    return yt.reshape(B, S, D).astype(dtype), aux

"""GQA attention: chunked-flash training/prefill, cached decode.

Covers the per-arch variants: RoPE, QKV bias (qwen2), attention-logit
softcap (gemma2), sliding-window local attention (gemma2 local layers —
*a stencil on the sequence axis*, see DESIGN.md §4), and cross-attention
(seamless decoder).

The training/prefill path is chunked over queries (lax.scan) so the
S×S score matrix never materializes — the pure-JAX flash formulation the
Pallas kernel (repro.kernels.sliding_attention) replaces on real TPUs.

Decode supports two cache shardings (picked by the framework per config):
heads-sharded (kv_heads % model_axis == 0) or sequence-sharded (the
paper's domain-decomposition idea applied to the KV domain; XLA turns the
softmax/PV reductions over the sharded axis into small all-reduces).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import active_mesh, kv_cache_layout, shard
from repro.models.layers import apply_rope, dense_init, matmul, softcap
from repro.models.flags import scan_unroll_arg

NEG_INF = -1e30


def attn_init(key, cfg):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, (h, hd)),
        "wk": dense_init(ks[1], d, (kh, hd)),
        "wv": dense_init(ks[2], d, (kh, hd)),
        "wo": dense_init(ks[3], h * hd, d) .reshape(h, hd, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), jnp.float32)
        p["bk"] = jnp.zeros((kh, hd), jnp.float32)
        p["bv"] = jnp.zeros((kh, hd), jnp.float32)
    return p


def _project_qkv(p, x, xkv, cfg, dtype, q_positions, kv_positions):
    """x: [B,S,D] queries source; xkv: [B,T,D] key/value source."""
    wq = shard(p["wq"], "embed", "q_heads_p", None)
    wk = shard(p["wk"], "embed", "kv_heads_p", None)
    wv = shard(p["wv"], "embed", "kv_heads_p", None)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(dtype), wq.astype(dtype),
                   preferred_element_type=jnp.float32)
    k = jnp.einsum("btd,dhk->bthk", xkv.astype(dtype), wk.astype(dtype),
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("btd,dhk->bthk", xkv.astype(dtype), wv.astype(dtype),
                   preferred_element_type=jnp.float32)
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if q_positions is not None:  # rope (self-attention only)
        q = apply_rope(q.astype(dtype), q_positions, cfg.rope_theta)
        k = apply_rope(k.astype(dtype), kv_positions, cfg.rope_theta)
    q = shard(q.astype(dtype), "batch", "seq", "heads", None)
    k = shard(k.astype(dtype), "batch", "seq", "kv_heads", None)
    v = shard(v.astype(dtype), "batch", "seq", "kv_heads", None)
    return q, k, v


def _out_proj(p, o, cfg, dtype):
    wo = shard(p["wo"], "q_heads_p", None, "embed")
    out = jnp.einsum("bshk,hkd->bsd", o.astype(dtype), wo.astype(dtype),
                     preferred_element_type=jnp.float32)
    return shard(out.astype(dtype), "batch", "seq", "embed_act")


def chunked_attention(
    q, k, v, *,
    causal: bool,
    window: int = 0,
    attn_softcap: float = 0.0,
    q_chunk: int = 1024,
    q_offset: int = 0,
    kv_len: Optional[jnp.ndarray] = None,
    dtype=jnp.bfloat16,
):
    """q: [B,S,H,D], k/v: [B,T,Kh,D] → [B,S,H,D].

    Scans over query chunks; scores per step are [B, C, H, T] so peak
    memory is C/S of the naive product.  ``window > 0`` restricts to a
    causal sliding window (local attention).  ``kv_len`` masks a partially
    filled cache.
    """
    B, S, H, D = q.shape
    T, Kh = k.shape[1], k.shape[2]
    G = H // Kh
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, S)
    n_chunks = S // q_chunk if S % q_chunk == 0 else 1
    if S % q_chunk != 0:
        q_chunk = S

    qg = q.reshape(B, S, Kh, G, D)
    kv_pos = jnp.arange(T)

    def one_chunk(ci, qc):
        # qc: [B,C,Kh,G,D]
        s = jnp.einsum("bckgd,btkd->bckgt", qc.astype(dtype), k.astype(dtype),
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, attn_softcap)
        qpos = q_offset + ci * q_chunk + jnp.arange(q_chunk)
        mask = jnp.ones((q_chunk, T), bool)
        if causal:
            mask &= kv_pos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kv_pos[None, :] > qpos[:, None] - window
        if kv_len is not None:
            mask &= kv_pos[None, :] < kv_len
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bckgt,btkd->bckgd", p.astype(dtype), v.astype(dtype),
                       preferred_element_type=jnp.float32)
        return o.astype(dtype)

    if n_chunks == 1:
        out = one_chunk(0, qg)
    else:
        qs = qg.reshape(B, n_chunks, q_chunk, Kh, G, D).transpose(1, 0, 2, 3, 4, 5)

        def body(_, x):
            ci, qc = x
            return None, one_chunk(ci, qc)

        _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qs), unroll=scan_unroll_arg())
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Kh, G, D)
    return out.reshape(B, S, H, D)


def self_attention(
    p, x, cfg, *, kind: str, dtype, positions=None, q_chunk: int = 1024
):
    """Training/prefill self-attention; returns [B,S,D] plus (k, v) for
    cache writes."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _project_qkv(p, x, x, cfg, dtype, positions, positions)
    window = cfg.local_window if kind == "attn_local" else 0
    o = chunked_attention(
        q, k, v,
        causal=True,
        window=window,
        attn_softcap=cfg.attn_softcap,
        q_chunk=q_chunk,
        dtype=dtype,
    )
    return _out_proj(p, o, cfg, dtype), (k, v)


def cross_attention(p, x, memory, cfg, *, dtype):
    """Decoder cross-attention over encoder output (no rope, no mask)."""
    q, k, v = _project_qkv(p, x, memory, cfg, dtype, None, None)
    o = chunked_attention(q, k, v, causal=False, dtype=dtype)
    return _out_proj(p, o, cfg, dtype)


def project_cross_kv(p, memory, cfg, dtype):
    """Cross-attention K/V of the encoder memory (cached at prefill)."""
    k = jnp.einsum("btd,dhk->bthk", memory.astype(dtype), p["wk"].astype(dtype),
                   preferred_element_type=jnp.float32)
    v = jnp.einsum("btd,dhk->bthk", memory.astype(dtype), p["wv"].astype(dtype),
                   preferred_element_type=jnp.float32)
    if cfg.qkv_bias:
        k = k + p["bk"]
        v = v + p["bv"]
    return k.astype(dtype), v.astype(dtype)


def cross_decode_attention(p, x, ck, cv, cfg, *, dtype):
    """One-token cross-attention against cached encoder K/V."""
    B = x.shape[0]
    wq = shard(p["wq"], "embed", "q_heads_p", None)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(dtype), wq.astype(dtype),
                   preferred_element_type=jnp.float32)
    if cfg.qkv_bias:
        q = q + p["bq"]
    Kh = ck.shape[2]
    H = q.shape[2]
    G = H // Kh
    qg = q.reshape(B, 1, Kh, G, q.shape[-1]).astype(dtype)
    s = jnp.einsum("bckgd,btkd->bckgt", qg, ck.astype(dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bckgt,btkd->bckgd", pattn.astype(dtype), cv.astype(dtype),
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H, q.shape[-1]).astype(dtype)
    return _out_proj(p, o, cfg, dtype)


def decode_self_attention(
    p, x, cache_k, cache_v, pos, cfg, *, kind: str, dtype
):
    """One-token decode.  x: [B,1,D]; cache_k/v: [B,T,Kh,D]; pos: scalar
    current position.  Returns (out [B,1,D], new_k, new_v).

    Local layers use a *rolling* cache of size window (position mod W) —
    the sequence-stencil footprint bounds the state, exactly the halo
    argument from DESIGN.md §4.
    """
    B = x.shape[0]
    T = cache_k.shape[1]
    mesh = active_mesh()
    layout = (
        kv_cache_layout(B, T, cache_k.shape[2], mesh)
        if mesh is not None and mesh.shape.get("model", 1) > 1 else "flat"
    )
    pos = jnp.asarray(pos)
    per_seq = pos.ndim == 1  # continuous batching: one position per slot
    positions = pos[:, None] if per_seq else jnp.full((B, 1), pos)
    q, k, v = _project_qkv(p, x, x, cfg, dtype, positions, positions)
    slot = jnp.where(jnp.asarray(T > 0), positions[:, 0] % T, 0)  # [B]
    if per_seq:
        upd = jax.vmap(
            lambda c, kv, s: jax.lax.dynamic_update_slice(c, kv, (s, 0, 0))
        )
        cache_k = upd(cache_k, k.astype(cache_k.dtype), slot)
        cache_v = upd(cache_v, v.astype(cache_v.dtype), slot)
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, slot[0], 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, slot[0], 0, 0))
    # constrain the updated cache to the SAME layout the spec builder
    # chose (kv_cache_layout) — a mismatched constraint here (e.g. a
    # blanket "replicated along T") makes GSPMD all-gather the whole
    # cache every layer (measured: +4.8 GiB/layer/device for yi-9b
    # decode_32k; EXPERIMENTS.md §Perf A3)
    cache_k = _constrain_cache(cache_k, layout, mesh)
    cache_v = _constrain_cache(cache_v, layout, mesh)

    window = cfg.local_window if kind == "attn_local" else 0
    # valid entries: rolling cache holds [max(0,pos-T+1), pos]
    kv_pos = jnp.arange(T)[None, :]                               # [1,T]
    posb = positions                                              # [B,1]
    slotb = slot[:, None]                                         # [B,1]
    # reconstruct absolute position of each slot in the rolling cache
    abs_pos = jnp.where(
        kv_pos <= slotb, posb - (slotb - kv_pos), posb - (slotb + T - kv_pos)
    )                                                             # [B,T]
    valid = (abs_pos >= 0) & (abs_pos <= posb)
    if window > 0:
        valid &= abs_pos > posb - window

    Kh = cache_k.shape[2]
    H = q.shape[2]
    G = H // Kh
    hd = q.shape[-1]
    qg = q.reshape(B, Kh, G, hd)

    if layout in ("seq", "seq_all"):
        # distributed flash-decode: the cache is *sequence-sharded* over
        # the model axis (dmp-style domain decomposition of the KV
        # domain).  Each shard reduces its local slice with an online
        # softmax; shards combine via an LSE-weighted psum of (denom,
        # accum) — O(B·H·hd) bytes on the wire instead of gathering the
        # O(B·T·Kh·hd) cache.
        o = _flash_decode_sharded(
            qg, cache_k, cache_v, valid, cfg, dtype, mesh, layout
        )
    elif T > DECODE_KV_CHUNK and T % DECODE_KV_CHUNK == 0:
        # flash-style decode: online softmax over KV chunks, so the f32
        # score tensor is [B,Kh,G,chunk] instead of [...,T] — bounds peak
        # memory for 32k+ caches (yi-9b decode_32k: 25.7 → <16 GiB/dev)
        o = _online_softmax_decode(qg, cache_k, cache_v, valid, cfg, dtype)
    else:
        s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(dtype),
                       cache_k.astype(dtype),
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(hd)
        s = softcap(s, cfg.attn_softcap)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        pattn = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", pattn.astype(dtype),
                       cache_v.astype(dtype),
                       preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, H, hd).astype(dtype)
    return _out_proj(p, o, cfg, dtype), cache_k, cache_v


DECODE_KV_CHUNK = 4096


def _constrain_cache(c, layout: str, mesh):
    """Pin a [B,T,Kh,hd] cache to the layout from ``kv_cache_layout``."""
    if mesh is None or layout == "flat":
        return c
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.sharding import _valid_spec, active_rules, default_rules

    rules = active_rules() or default_rules("pod" in mesh.axis_names)
    batch_ax = rules.physical("batch")
    if layout == "heads":
        spec = P(batch_ax, None, "model", None)
    elif layout == "seq":
        spec = P(batch_ax, "model", None, None)
    elif layout == "seq_all":
        axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
        spec = P(None, tuple(a for a in axes if a) + ("model",), None, None)
    else:  # "batch"
        spec = P(batch_ax, None, None, None)
    return jax.lax.with_sharding_constraint(
        c, NamedSharding(mesh, _valid_spec(mesh, spec, tuple(c.shape)))
    )


def _flash_decode_sharded(qg, cache_k, cache_v, valid, cfg, dtype, mesh, layout):
    """qg: [B,Kh,G,hd] (seq-replicated); cache_k/v: [B,T,Kh,hd] with T
    sharded — over "model" (layout "seq") or over every axis (layout
    "seq_all", tiny-batch long context); valid: [B,T].  Returns o
    [B,Kh,G,hd].  Per-shard online softmax + cross-shard LSE combine
    (flash-decoding / tree attention).  The in_specs mirror
    ``launch.steps.kv_cache_spec`` exactly (same ``kv_cache_layout``)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import _valid_spec, active_rules, default_rules

    rules = active_rules() or default_rules("pod" in mesh.axis_names)
    batch_ax = rules.physical("batch")
    B, T = valid.shape
    hd = qg.shape[-1]
    scale = 1.0 / math.sqrt(hd)

    if layout == "seq":
        seq_axes: tuple = ("model",)
        kv_spec = _valid_spec(mesh, P(batch_ax, "model", None, None),
                              tuple(cache_k.shape))
        q_spec = _valid_spec(mesh, P(batch_ax, None, None, None),
                             tuple(qg.shape))
    else:  # "seq_all": batch too small to shard — everything on T
        axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
        seq_axes = tuple(a for a in axes if a) + ("model",)
        kv_spec = _valid_spec(mesh, P(None, seq_axes, None, None),
                              tuple(cache_k.shape))
        q_spec = P(None, None, None, None)
    v_spec = _valid_spec(mesh, P(q_spec[0], kv_spec[1]), (B, T))

    def block(qg_l, k_l, v_l, ok_l):
        s = jnp.einsum("bkgd,btkd->bkgt", qg_l.astype(dtype), k_l.astype(dtype),
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cfg.attn_softcap)
        s = jnp.where(ok_l[:, None, None, :], s, NEG_INF)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(-1)
        acc = jnp.einsum("bkgt,btkd->bkgd", p.astype(dtype), v_l.astype(dtype),
                         preferred_element_type=jnp.float32)
        # LSE combine across sequence shards
        ax = kv_spec[1]
        ax = ax if isinstance(ax, tuple) else (ax,)
        g = jax.lax.pmax(m, ax)
        r = jnp.exp(m - g)
        l = jax.lax.psum(l * r, ax)
        acc = jax.lax.psum(acc * r[..., None], ax)
        return acc / jnp.maximum(l, 1e-30)[..., None]

    return jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, v_spec),
        out_specs=q_spec,
        check_vma=False,
    )(qg, cache_k, cache_v, valid)


def _online_softmax_decode(qg, cache_k, cache_v, valid, cfg, dtype):
    """qg: [B,Kh,G,hd]; cache_k/v: [B,T,Kh,hd]; valid: [B,T] →
    o [B,Kh,G,hd].  Running (max, denom, acc) over KV chunks."""
    B, Kh, G, hd = qg.shape
    T = cache_k.shape[1]
    C = DECODE_KV_CHUNK
    n = T // C
    scale = 1.0 / math.sqrt(hd)

    kc = cache_k.reshape(B, n, C, Kh, hd).transpose(1, 0, 2, 3, 4)
    vc = cache_v.reshape(B, n, C, Kh, hd).transpose(1, 0, 2, 3, 4)
    vm = valid.reshape(B, n, C).transpose(1, 0, 2)

    def body(carry, inp):
        m, l, acc = carry
        k, v, ok = inp
        s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(dtype), k.astype(dtype),
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cfg.attn_softcap)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        r = jnp.exp(m - m_new)
        l_new = l * r + p.sum(-1)
        acc_new = acc * r[..., None] + jnp.einsum(
            "bkgt,btkd->bkgd", p.astype(dtype), v.astype(dtype),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Kh, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kh, G), jnp.float32)
    a0 = jnp.zeros((B, Kh, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, vm), unroll=scan_unroll_arg()
    )
    return acc / jnp.maximum(l, 1e-30)[..., None]

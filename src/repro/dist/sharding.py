"""Mesh context + logical→physical sharding rules.

The model layers annotate tensors with *logical* axis names ("batch",
"embed", "mlp", ...) via ``shard``; a ``ShardingRules`` table maps those
to physical mesh axes.  This mirrors how the stencil stack separates the
declarative decomposition (``dmp.GridAttr``: which array dim maps to
which mesh axis) from its lowering — one rules table serves every
architecture, and moving a deployment from a (data, model) mesh to a
(pod, data, model) mesh is a rules swap, not a model edit.

``shard`` is a no-op without an active mesh, so the same model code runs
on single-device CPU tests and 512-chip pods unchanged.

Every constraint goes through ``_valid_spec``, which drops mesh axes
that do not divide the corresponding array dimension — the moral
equivalent of the stencil decomposition's divisibility check, applied
permissively (replicate instead of erroring) because model shapes vary
per architecture.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Mapping, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: A physical mapping for one logical axis: a mesh axis name, a tuple of
#: mesh axis names (sharded over their product), or None (replicated).
Physical = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis → physical-mesh-axis table."""

    table: Mapping[str, Physical]

    def physical(self, logical: Optional[str]) -> Physical:
        if logical is None:
            return None
        return self.table.get(logical)

    def replace(self, **updates: Physical) -> "ShardingRules":
        return ShardingRules({**self.table, **updates})


def default_rules(multi_pod: bool = False) -> ShardingRules:
    """The production rules: batch over the data axes (FSDP-style), every
    contracted model dimension over "model" (megatron-style TP).

    Multi-pod runs add a leading "pod" axis to the batch group — DCN
    traffic stays data-parallel only (gradient all-reduce), ICI carries
    the TP collectives.
    """
    batch: Physical = ("pod", "data") if multi_pod else "data"
    return ShardingRules(
        {
            # activations
            "batch": batch,
            "seq": None,
            "embed_act": None,
            "mlp_act": "model",
            "vocab_act": "model",
            "heads": "model",
            "kv_heads": "model",
            # weights
            "embed": None,
            "vocab": "model",
            "q_heads_p": "model",
            "kv_heads_p": "model",
            "mlp": "model",
            "expert": "model",
        }
    )


# --------------------------------------------------------------------------
# mesh context
# --------------------------------------------------------------------------

_STATE = threading.local()


def _stack() -> list:
    if not hasattr(_STATE, "stack"):
        _STATE.stack = []
    return _STATE.stack


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[ShardingRules] = None):
    """Activate ``mesh``/``rules`` for every ``shard`` call in scope.

    Entered *inside* the jitted step function (the context only needs to
    cover tracing), mirroring how the stencil lowering scopes its
    ``shard_map`` to one compiled program.
    """
    rules = rules or default_rules(multi_pod="pod" in mesh.axis_names)
    _stack().append((mesh, rules))
    try:
        yield mesh
    finally:
        _stack().pop()


def active_mesh() -> Optional[Mesh]:
    s = _stack()
    return s[-1][0] if s else None


def active_rules() -> Optional[ShardingRules]:
    s = _stack()
    return s[-1][1] if s else None


# --------------------------------------------------------------------------
# spec validation
# --------------------------------------------------------------------------


def _valid_spec(mesh: Mesh, spec: P, shape: tuple) -> P:
    """Clamp ``spec`` to what ``shape`` supports on ``mesh``.

    Per dimension, mesh axes are kept (in order) only while the product
    of their sizes still divides the dimension; axes unknown to the mesh
    or already used by an earlier dimension are dropped.  The result is
    always a legal NamedSharding spec — the permissive counterpart of the
    stencil decomposition's hard divisibility error.
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used: set = set()
    out = []
    for dim, entry in zip(shape, entries[: len(shape)]):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for a in axes:
            if a is None or a not in mesh.shape or a in used:
                continue
            size = mesh.shape[a]
            if dim % (prod * size) == 0:
                kept.append(a)
                prod *= size
                used.add(a)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def factor_slot_mesh(
    mesh: Mesh,
    slots: int = 1,
    axis: str = "slot",
    devices=None,
) -> Mesh:
    """Extend a spatial ``mesh`` with a leading slot axis of size
    ``slots`` factored out of the device inventory.

    The slot axis carries a *batch* dimension (pooled serving slots, or
    ensemble members), not an array dimension: collectives keep binding
    the spatial axis names, so each slot block of ``slots × spatial``
    devices runs the exact solo exchange pattern.  ``slots == 1`` reuses
    the mesh's own devices (shard_map over ``(slot=1, *spatial)`` — the
    vmap inside still pools the batch); ``slots > 1`` takes the first
    ``slots * spatial`` devices of ``devices`` (default: the process
    inventory), slot-major, so slot block 0 is the original mesh's
    device prefix.
    """
    import numpy as np

    if int(slots) != slots or slots < 1:
        raise ValueError(f"slots must be a positive integer, got {slots!r}")
    slots = int(slots)
    if axis in mesh.axis_names:
        raise ValueError(
            f"slot axis {axis!r} collides with mesh axes "
            f"{tuple(mesh.axis_names)}"
        )
    spatial_shape = tuple(mesh.shape[a] for a in mesh.axis_names)
    names = (axis,) + tuple(mesh.axis_names)
    if slots == 1:
        devs = mesh.devices.reshape((1,) + spatial_shape)
        return Mesh(devs, names)
    n_spatial = int(np.prod(spatial_shape))
    pool = list(devices) if devices is not None else jax.devices()
    need = slots * n_spatial
    if need > len(pool):
        raise ValueError(
            f"slot axis of {slots} over a {n_spatial}-rank spatial mesh "
            f"needs {need} devices, have {len(pool)}"
        )
    devs = np.array(pool[:need]).reshape((slots,) + spatial_shape)
    return Mesh(devs, names)


def reshard(arrays, mesh: Optional[Mesh], specs) -> tuple:
    """Place host arrays onto ``mesh`` with one ``PartitionSpec`` each —
    the elastic-restore path: state checkpointed under one mesh
    factorization is ``device_put`` under a *different* one (or none),
    so a killed 4-rank run resumes onto 2 ranks unchanged.  ``mesh`` is
    ``None`` for a single-device restore (plain device_put)."""
    arrays = tuple(arrays)
    if mesh is None:
        return tuple(jax.device_put(a) for a in arrays)
    if len(arrays) != len(tuple(specs)):
        raise ValueError(
            f"{len(arrays)} arrays for {len(tuple(specs))} partition specs"
        )
    return tuple(
        jax.device_put(a, NamedSharding(mesh, spec))
        for a, spec in zip(arrays, specs)
    )


def shard(x, *logical: Optional[str]):
    """Constrain ``x`` to the active rules' layout for ``logical`` axes.

    No-op without an active mesh — model code is annotation-transparent
    on single-device runs.  Entries may be logical names or ``None``.
    """
    mesh = active_mesh()
    if mesh is None:
        return x
    rules = active_rules() or default_rules(multi_pod="pod" in mesh.axis_names)
    entries = tuple(
        rules.physical(a) if isinstance(a, str) else a for a in logical
    )
    spec = _valid_spec(mesh, P(*entries), tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# KV-cache layout policy
# --------------------------------------------------------------------------


def _batch_axis_size(mesh: Mesh, rules: ShardingRules) -> int:
    batch_ax = rules.physical("batch")
    axes = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
    return math.prod(mesh.shape.get(a, 1) for a in axes if a)


def kv_cache_layout(
    B: int, T: int, Kh: int, mesh: Optional[Mesh],
    rules: Optional[ShardingRules] = None,
) -> str:
    """Pick the decode-cache layout for a [B, T, Kh, hd] cache.

    Policy (DESIGN.md §6):

    - ``"heads"``   — KV heads divide the model axis: classic TP.
    - ``"seq"``     — they don't; shard the *sequence* dim over "model"
      instead — the paper's domain decomposition applied to the KV
      domain (decode softmax/PV reductions become small all-reduces).
    - ``"seq_all"`` — tiny-batch long-context: batch can't shard, so the
      sequence dim is spread over every available axis.
    - ``"batch"``   — no model axis (or nothing else fits) but batch
      divides the data axes.
    - ``"flat"``    — replicate (single device / nothing divides).
    """
    if mesh is None:
        return "flat"
    rules = rules or default_rules(multi_pod="pod" in mesh.axis_names)
    n_b = _batch_axis_size(mesh, rules)
    model = mesh.shape.get("model", 1)
    batch_ok = n_b <= 1 or B % n_b == 0
    if model > 1:
        if Kh % model == 0 and batch_ok:
            return "heads"
        if batch_ok and n_b > 1 and T % model == 0:
            return "seq"
        if T % (max(n_b, 1) * model) == 0:
            return "seq_all"
    if n_b > 1 and B % n_b == 0:
        return "batch"
    return "flat"

"""Kernel layer: Pallas code generation for stencil compute hot-spots.

Shared here (imported by ``api``, ``tune`` and the kernels themselves):

- :func:`default_backend` — what ``Target(backend=None)`` resolves to:
  the Pallas kernels on a TPU, the jnp lowering (XLA fusions) on any
  other backend.
- :func:`default_interpret` — what ``Target(pallas_interpret=None)``
  resolves to: interpret mode exactly when JAX's default backend is not a
  TPU (the CPU test oracle), native Mosaic kernels on a TPU.  Every
  kernel entry point takes the resolved value explicitly; none defaults
  to interpret mode.
- :class:`KernelPlanError` — a kernel that cannot be planned for the
  chip (illegal tile, VMEM budget exceeded); raised at ``api.compile``.
- :func:`dispatch_stats` — trace-time kernel-dispatch counters.  Every
  ``pl.pallas_call`` the backend traces bumps a counter, so a test can
  assert "one epoch == ONE kernel dispatch" by resetting, tracing one
  epoch, and reading the deltas (under ``jit`` the counters move at trace
  time, once per compilation, which is exactly the dispatch count of the
  compiled program).  ``window_copies`` counts the operands a kernel
  could not read in place and had copied into a re-based, padded window
  source first.
"""
from __future__ import annotations

import dataclasses


class KernelPlanError(ValueError):
    """A Pallas kernel that cannot be laid out for the TPU: a tile that
    breaks the (8, 128) rule or a working set over the VMEM budget.  The
    message names the sizes."""


def default_backend() -> str:
    """Resolved default for ``Target.backend=None``: ``"pallas"`` on a
    TPU, ``"jnp"`` on any other backend."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def default_interpret() -> bool:
    """Resolved default for ``Target.pallas_interpret=None``: native
    Pallas on a TPU, interpret mode on any other backend."""
    import jax

    return jax.default_backend() != "tpu"


@dataclasses.dataclass
class DispatchStats:
    """Counts of Pallas kernels *traced* since the last reset."""

    apply_calls: int = 0        # per-apply kernels (kernels/stencil_apply.py)
    fused_epoch_calls: int = 0  # epoch megakernels (kernels/epoch_kernel.py)
    window_copies: int = 0      # operands copied by stencil_apply.window_source

    @property
    def pallas_calls(self) -> int:
        return self.apply_calls + self.fused_epoch_calls

    def as_dict(self) -> dict:
        return {
            "apply_calls": self.apply_calls,
            "fused_epoch_calls": self.fused_epoch_calls,
            "pallas_calls": self.pallas_calls,
            "window_copies": self.window_copies,
        }


_DISPATCH = DispatchStats()


def dispatch_stats() -> DispatchStats:
    return _DISPATCH


def reset_dispatch_stats() -> None:
    _DISPATCH.apply_calls = 0
    _DISPATCH.fused_epoch_calls = 0
    _DISPATCH.window_copies = 0

"""Three-term roofline model of one compiled executable.

Terms, with the per-chip peaks of ``PEAKS[device_kind]``:

    compute    = HLO_FLOPs_per_device   / peak_FLOPs
    memory     = HLO_bytes_per_device   / HBM_bw
    collective = collective_bytes_per_device / link_bw

XLA's ``compiled.cost_analysis()`` for an SPMD module reports the
partitioned per-device program, so each term is per-chip seconds
directly.  The modeled step time is ``max(terms)`` with perfect overlap
and ``sum(terms)`` without.  ``CompiledStencil.cost()`` and the
autotuner (``repro.tune``) read it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""

    flops: float           # bf16 FLOP/s
    hbm_bytes_s: float     # HBM bytes/s
    link_bytes_s: float    # ICI bytes/s per link
    source: str


V5E = "TPU v5 lite"  # jax Device.device_kind of a TPU v5e chip

# Keyed by ``jax.Device.device_kind``.  A kind missing here is an error,
# never a default: a model of the wrong chip is worse than none.
PEAKS = {
    V5E: DevicePeaks(
        flops=197e12,
        hbm_bytes_s=819e9,
        link_bytes_s=50e9,  # 1,600 Gbit/s per chip over 4 ICI links
        source='Google Cloud documentation, "TPU v5e"',
    ),
}

LINK_LATENCY = 2e-6   # modelled per-message launch latency (not published)


def device_peaks(device_kind: Optional[str]) -> DevicePeaks:
    """The peaks of ``device_kind``; ``ValueError`` for any kind not in
    ``PEAKS`` (the CPU included: a CPU caller that models a chip names
    it, e.g. ``V5E``)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); name the modelled chip explicitly"
        ) from None

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum operand bytes of every collective in the (optimized) HLO."""
    out: dict[str, float] = {}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")

    def shape_bytes(sig: str) -> float:
        total = 0.0
        for m in shape_re.finditer(sig):
            dt, dims = m.group(1), m.group(2)
            sz = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                  "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8}.get(dt)
            if sz is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * sz
        return total

    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        kind = m.group(1)
        # operand bytes: shapes on the RHS of the op name
        rhs = line.split("=", 1)[1]
        # result shape is the first shape on the RHS; operands follow in parens
        paren = rhs.find("(")
        operand_sig = rhs[paren:] if paren >= 0 else rhs
        out[kind] = out.get(kind, 0.0) + shape_bytes(operand_sig)
    return out


@dataclass
class RooflineTerms:
    """Generic three-term roofline of one compiled executable — the
    ``CompiledStencil.cost()`` payload (per-device quantities in, per-chip
    seconds out).

    The optional temporal-tiling terms describe the message-count vs
    redundant-compute tradeoff of deep-halo epochs
    (``Target(exchange_every=k)``): ``messages_per_epoch`` exchanges fire
    *once* per epoch regardless of depth (their per-message launch latency
    amortizes as 1/k), while every non-final step of the epoch computes a
    shrinking frame of redundant boundary points
    (``redundant_compute_factor``).  ``recommend_exchange_every`` picks
    the k that minimizes the modeled per-step time, subject to the deep
    halo fitting the shard."""

    flops: float
    bytes_accessed: float
    collectives: dict = field(default_factory=dict)
    exchange_every: int = 1
    messages_per_epoch: int = 0
    step_halo: tuple = ()     # per-dim per-step halo width (max of lo/hi)
    local_shape: tuple = ()   # local shard core extents
    # the chip whose peaks turn flops/bytes into seconds (a PEAKS key);
    # the structural terms (feasibility, redundancy) need none
    device_kind: Optional[str] = None

    def __post_init__(self) -> None:
        self.flops = float(self.flops)
        self.bytes_accessed = float(self.bytes_accessed)
        self.collectives = dict(self.collectives)
        self.exchange_every = int(self.exchange_every)
        self.messages_per_epoch = int(self.messages_per_epoch)
        self.step_halo = tuple(self.step_halo)
        self.local_shape = tuple(self.local_shape)

    @property
    def collective_bytes(self) -> float:
        return float(sum(self.collectives.values()))

    @property
    def peaks(self) -> DevicePeaks:
        return device_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / self.peaks.hbm_bytes_s

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.link_bytes_s

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_overlapped(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def t_serial(self) -> float:
        return self.t_compute + self.t_memory + self.t_collective

    # -- temporal-tiling tradeoff (message latency vs redundant compute) --
    @property
    def t_latency(self) -> float:
        """Per-step exchange launch latency: one message volley per epoch,
        amortized over the epoch's steps."""
        return (
            self.messages_per_epoch * LINK_LATENCY
            / max(self.exchange_every, 1)
        )

    def redundant_compute_factor(self, k: Optional[int] = None) -> float:
        """Mean compute volume of an epoch's steps relative to the core:
        step j of k computes ``prod(n_d + 2·(k-j)·w_d)`` points, so the
        factor is 1.0 at k=1 and grows with depth (surface/volume)."""
        k = self.exchange_every if k is None else int(k)
        if k <= 1 or not self.step_halo or not self.local_shape:
            return 1.0
        core = 1.0
        for n in self.local_shape:
            core *= n
        if core == 0:
            return 1.0
        total = 0.0
        for j in range(k):  # j = remaining growth steps (k-1 … 0)
            vol = 1.0
            for n, w in zip(self.local_shape, self.step_halo):
                vol *= n + 2.0 * j * w
            total += vol
        return total / (k * core)

    def feasible_exchange_every(self, k: int) -> bool:
        """Deep halo of depth k must come out of the neighbour's core."""
        if not self.step_halo or not self.local_shape:
            return k == 1
        return all(
            w * k <= n for w, n in zip(self.step_halo, self.local_shape) if w
        )

    def step_time(self, k: int) -> float:
        """Modeled per-step seconds at epoch depth ``k``, extrapolated from
        this artifact's terms: work scales by the redundant-compute factor,
        exchange *bytes* per step stay ~constant (k× deeper, 1/k as often),
        exchange *latency* amortizes as 1/k.

        The measured terms describe one *call* — a whole epoch of
        ``self.exchange_every`` steps (its flops carry that depth's
        redundancy, its collective bytes the depth-K halo) — so they are
        normalized back to one clean step before extrapolating to k."""
        depth = max(self.exchange_every, 1)
        per_step_work = max(self.t_compute, self.t_memory) / (
            depth * max(self.redundant_compute_factor(depth), 1.0)
        )
        t_lat = self.messages_per_epoch * LINK_LATENCY / max(k, 1)
        return (
            per_step_work * self.redundant_compute_factor(k)
            + t_lat
            + self.t_collective / depth
        )

    def ranked_exchange_every(self, max_k: int = 8) -> list:
        """Every feasible epoch depth with its modeled per-step seconds,
        best first (ties resolve to the shallower epoch).  ``[(1,
        step_time(1))]`` when the tiling terms are unavailable — the
        ranking the autotuner (``repro.tune``) prints."""
        if not self.step_halo or not self.local_shape or not any(self.step_halo):
            return [(1, self.step_time(1))]
        pairs = [(1, self.step_time(1))] + [
            (k, self.step_time(k))
            for k in range(2, max(int(max_k), 1) + 1)
            if self.feasible_exchange_every(k)
        ]
        return sorted(pairs, key=lambda kt: (kt[1], kt[0]))

    def recommend_exchange_every(self, max_k: int = 8) -> int:
        """The epoch depth minimizing the modeled per-step time; 1 when
        tiling cannot win (or the terms are not available)."""
        return self.ranked_exchange_every(max_k)[0][0]

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "t_latency": self.t_latency,
            "t_overlapped": self.t_overlapped,
            "t_serial": self.t_serial,
            "dominant": self.dominant,
            "exchange_every": self.exchange_every,
            "messages_per_epoch": self.messages_per_epoch,
            "redundant_compute_factor": self.redundant_compute_factor(),
            "recommended_exchange_every": self.recommend_exchange_every(),
            "device_kind": self.device_kind,
        }

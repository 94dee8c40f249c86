"""Three-term roofline analysis from the dry-run's compiled artifacts.

    PYTHONPATH=src python -m repro.launch.roofline [--outdir results/dryrun]
                                                   [--markdown]

Terms, with the per-chip peaks of ``PEAKS[device_kind]``:

    compute    = HLO_FLOPs_per_device   / peak_FLOPs
    memory     = HLO_bytes_per_device   / HBM_bw
    collective = collective_bytes_per_device / link_bw

NOTE on units: XLA's ``compiled.cost_analysis()`` for an SPMD module
reports the *partitioned per-device* program (verified: doubling the mesh
halves reported FLOPs), so each term is per-chip seconds directly — no
further division by chip count.  MODEL_FLOPS (6·N·D, active params for
MoE) is a *global* quantity; the useful-compute ratio therefore compares
against HLO_FLOPs × n_devices.

The modeled step time is ``max(terms)`` with perfect overlap and
``sum(terms)`` without; the dominant term is the bottleneck the §Perf
loop iterates on.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
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
        ranking the autotuner (``repro.tune``) and the fig8 ``--tune``
        sweep print."""
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



SHAPE_TOKENS = {
    "train_4k": 4_096 * 256,
    "prefill_32k": 32_768 * 32,
    "decode_32k": 128,          # one token per sequence
    "long_500k": 1,
}
TRAIN_MULT = {"train_4k": 3.0}  # fwd+bwd ≈ 3× forward FLOPs

_DIMS_CACHE: dict = {}


def _arch_dims(arch: str) -> tuple:
    if arch not in _DIMS_CACHE:
        try:
            from repro.configs import get_config

            cfg = get_config(arch)
            _DIMS_CACHE[arch] = (cfg.d_model, cfg.n_layers)
        except Exception:
            _DIMS_CACHE[arch] = (4096, 32)
    return _DIMS_CACHE[arch]


# the LM dry-run compiles on CPU for a v5e production mesh
_CELL_PEAKS = device_peaks(V5E)


@dataclass
class Cell:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops: float
    bytes_accessed: float
    collective_bytes: float
    collectives: dict
    params: int
    active_params: int
    arg_bytes: float = 0.0  # per-device resident args (params + caches)

    @property
    def t_compute(self) -> float:
        return self.flops / _CELL_PEAKS.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / _CELL_PEAKS.hbm_bytes_s

    @property
    def t_memory_analytic(self) -> float:
        """Algorithmic minimum HBM traffic (per device), used for
        bottleneck classification.  The HLO-derived ``t_memory`` is kept
        for completeness but the CPU backend inflates it 10–50×
        (bf16 ops emulated via f32 copies, unfused elementwise chains,
        gathers billed at full-operand size) — measured in EXPERIMENTS.md
        §Roofline 'bytes fidelity'.

        train:   3 passes over the params at 4 B (fwd read, bwd read,
                 update r/w of param+m+v ≈ 12 B) + layer activation
                 checkpoints (2 B, written fwd + read bwd) + logits.
        prefill: params once (2 B) + activations once + KV cache write.
        decode:  resident state once (params + caches ≈ arg_bytes).
        """
        d_model, n_layers = _arch_dims(self.arch)
        toks = SHAPE_TOKENS.get(self.shape, 0) / self.n_devices
        if self.shape.startswith("train"):
            # params spread by FSDP(data)×TP(model): the whole mesh shares one copy
            param_traffic = self.active_params * 24.0 / self.n_devices
            act_traffic = 4.0 * toks * 2.0 * d_model * n_layers
            return (param_traffic + act_traffic) / _CELL_PEAKS.hbm_bytes_s
        if self.shape.startswith("prefill"):
            p_dev = 2.0 * self.active_params / 16  # bf16, TP-sharded; DP replicates
            act_traffic = 4.0 * toks * 2.0 * d_model * n_layers
            return (p_dev + act_traffic) / _CELL_PEAKS.hbm_bytes_s
        return max(self.arg_bytes, 1.0) / _CELL_PEAKS.hbm_bytes_s

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / _CELL_PEAKS.link_bytes_s

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory_analytic,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_overlapped(self) -> float:
        return max(self.t_compute, self.t_memory_analytic, self.t_collective)

    @property
    def t_serial(self) -> float:
        return self.t_compute + self.t_memory_analytic + self.t_collective

    @property
    def model_flops(self) -> float:
        tokens = SHAPE_TOKENS.get(self.shape, 0)
        mult = TRAIN_MULT.get(self.shape, 1.0)
        return 2.0 * self.active_params * tokens * mult  # 2ND/token fwd

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips) — how much compiled compute
        is 'useful'.  <1 ⇒ remat/recompute overhead; >1 ⇒ HLO under-counts
        (e.g. fused ops) or model-FLOPs overestimates (MoE drops)."""
        total_hlo = self.flops * self.n_devices
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def is_decode(self) -> bool:
        return self.shape.startswith(("decode", "long"))

    @property
    def roofline_fraction(self) -> float:
        """Fraction of modeled (overlapped) step time that is *irreducible*
        on this hardware — the score.

        train/prefill (compute-limited regime): ideal = useful model FLOPs
        at peak MXU throughput.  decode/long (bandwidth-limited regime):
        ideal = one read of the resident state (params + caches) at full
        HBM bandwidth — FLOPs are immaterial at batch-per-chip ≤ 1."""
        if self.t_overlapped == 0:
            return 0.0
        if self.is_decode:
            if not self.arg_bytes:
                return 0.0
            # ideal = one read of the resident state; score against the
            # HLO-memory-based modeled time (conservative: the CPU
            # backend inflates HLO bytes — see §Roofline bytes-fidelity)
            t_ideal = self.arg_bytes / _CELL_PEAKS.hbm_bytes_s
            t_model = max(self.t_compute, self.t_memory, self.t_collective)
        else:
            t_ideal = self.model_flops / self.n_devices / _CELL_PEAKS.flops
            t_model = self.t_overlapped
        return min(1.0, t_ideal / t_model)


def advice(c: Cell) -> str:
    if c.dominant == "collective":
        kinds = sorted(c.collectives, key=c.collectives.get, reverse=True)
        top = kinds[0] if kinds else "?"
        return (f"cut {top} volume (resharding/fusion of collectives, "
                "overlap with compute)")
    if c.dominant == "memory":
        if c.shape.startswith("decode") or c.shape.startswith("long"):
            return "KV/state residency: smaller cache dtype, fused decode reads"
        return "remat policy / fusion to cut HBM round-trips"
    return "MXU utilization: larger per-chip matmul tiles, less padding"


def load_cells(outdir: str, delta_dir: str = None) -> list:
    """Load dry-run records; when a delta-extrapolation record exists for
    the same cell (exact scan-corrected FLOPs/collectives — see
    ``dryrun.run_cell_delta``), its cost numbers override the scan-mode
    record's (which count while-loop bodies once)."""
    delta_dir = delta_dir or outdir.rstrip("/") + "_delta"
    overrides = {}
    for path in glob.glob(os.path.join(delta_dir, "*.json")):
        with open(path) as f:
            d = json.load(f)
        if d.get("ok"):
            overrides[(d["arch"], d["shape"], d["mesh"])] = d

    cells = []
    for path in sorted(glob.glob(os.path.join(outdir, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        if not d.get("ok"):
            continue
        key = (d["arch"], d["shape"], d["mesh"])
        src = overrides.get(key, d)
        coll = src.get("collective_bytes", {})
        mem = d.get("memory") or {}
        cells.append(
            Cell(
                arch=d["arch"],
                shape=d["shape"],
                mesh=d["mesh"],
                n_devices=d["n_devices"],
                flops=src["cost"]["flops"] or 0.0,
                bytes_accessed=src["cost"]["bytes_accessed"] or 0.0,
                collective_bytes=sum(coll.values()),
                collectives=coll,
                params=d.get("params", 0),
                active_params=d.get("active_params", 0) or d.get("params", 0),
                # memory_analysis reports the per-device partitioned module
                # (verified: 2× mesh ⇒ ½ argument bytes)
                arg_bytes=mem.get("argument_bytes") or 0.0,
            )
        )
    return cells


def fmt_s(t: float) -> str:
    if t >= 1.0:
        return f"{t:.2f}s"
    if t >= 1e-3:
        return f"{t*1e3:.1f}ms"
    return f"{t*1e6:.0f}µs"


def report(cells: list, markdown: bool = False, mesh: str = "16x16") -> str:
    rows = []
    for c in cells:
        if c.mesh != mesh:
            continue
        rows.append(
            (
                c.arch, c.shape,
                fmt_s(c.t_compute), fmt_s(c.t_memory_analytic),
                fmt_s(c.t_memory), fmt_s(c.t_collective),
                c.dominant,
                f"{c.useful_ratio:.2f}",
                f"{c.roofline_fraction*100:.0f}%",
                advice(c),
            )
        )
    headers = ["arch", "shape", "t_comp", "t_mem", "t_mem(hlo)", "t_coll",
               "dominant", "useful", "roofline", "to improve"]
    if markdown:
        out = ["| " + " | ".join(headers) + " |",
               "|" + "|".join("---" for _ in headers) + "|"]
        out += ["| " + " | ".join(str(x) for x in r) + " |" for r in rows]
        return "\n".join(out)
    widths = [max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
              for i, h in enumerate(headers)]
    out = ["  ".join(h.ljust(w) for h, w in zip(headers, widths))]
    out += ["  ".join(str(c).ljust(w) for c, w in zip(r, widths)) for r in rows]
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--markdown", action="store_true")
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    cells = load_cells(args.outdir)
    print(report(cells, markdown=args.markdown, mesh=args.mesh))
    # summary: the three §Perf hillclimb candidates
    sp = [c for c in cells if c.mesh == args.mesh]
    if sp:
        worst = min(sp, key=lambda c: c.roofline_fraction)
        coll = max(sp, key=lambda c: c.t_collective / max(c.t_overlapped, 1e-12))
        print(f"\nworst roofline fraction : {worst.arch} × {worst.shape} "
              f"({worst.roofline_fraction*100:.0f}%)")
        print(f"most collective-bound   : {coll.arch} × {coll.shape} "
              f"(t_coll {fmt_s(coll.t_collective)})")


if __name__ == "__main__":
    main()

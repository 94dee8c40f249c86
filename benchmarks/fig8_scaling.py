"""Paper fig. 8 analogue: strong scaling of 3D so4 heat/wave kernels.

Two parts:

1. **Measured** (virtual devices, subprocess model not needed here — the
   structural signal): decompose the global stencil for rank counts
   8→1024 and report per-rank halo-exchange bytes vs per-rank compute
   points from the dmp swap declarations — the quantities that drive the
   paper's strong-scaling curves.

2. **Modeled TPU v5e step time** from the v5e entry of
   ``launch.roofline.PEAKS``: compute term (memory-bound stencils:
   bytes-limited) vs collective term (halo bytes / link bw), reported
   with and without comm/compute overlap — the paper's Devito-vs-xDSL
   gap is exactly the no-overlap penalty, and our beyond-paper overlap
   pass closes it.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import save_record, table
from repro.core.dialects import dmp, stencil
from repro.core.passes import decompose_stencil, eliminate_redundant_swaps
from repro.core.passes.decompose import make_strategy_3d
from repro.frontends.devito_like import Eq, Grid, Operator, TimeFunction
from repro.launch.roofline import LINK_LATENCY, V5E, device_peaks

# this script models a v5e chip wherever it runs
PEAKS = device_peaks(V5E)

GLOBAL = (512, 512, 512)
RANK_GRIDS = {
    8: (2, 2, 2),
    64: (4, 4, 4),
    128: (8, 4, 4),
    256: (8, 8, 4),
    512: (8, 8, 8),
    1024: (16, 8, 8),
}


def _stencil_stats(kind: str, so: int, grid_shape: tuple) -> dict:
    g = Grid(shape=GLOBAL, extent=(1.0,) * 3)
    u = TimeFunction(name="u", grid=g, space_order=so,
                     time_order=2 if kind == "wave" else 1)
    eq = Eq(u.dt2 if kind == "wave" else u.dt, 1.0 * u.laplace)
    op = Operator(eq, dt=1e-7)
    func = op.program.func
    local = decompose_stencil(func, make_strategy_3d(grid_shape))
    eliminate_redundant_swaps(local)
    swaps = [o for o in local.body.ops if isinstance(o, dmp.SwapOp)]
    halo_elems = sum(s.total_exchange_elems() for s in swaps)
    applies = [o for o in local.body.ops if isinstance(o, stencil.ApplyOp)]
    # flops per point: arithmetic ops in the apply bodies
    flop_per_pt = sum(
        sum(1 for bop in a.body.ops if type(bop).__name__ in
            ("AddOp", "SubOp", "MulOp", "DivOp"))
        for a in applies
    )
    local_pts = int(np.prod([G // r for G, r in
                             zip(GLOBAL, grid_shape)]))
    return {
        "halo_bytes": halo_elems * 4,
        "local_points": local_pts,
        "flops_per_point": flop_per_pt,
        "n_swaps": len(swaps),
    }


def _tiling_sweep(record: dict, ranks: list, exchange_every: tuple) -> list:
    """Temporal-tiling model rows: per-step time at epoch depth k =
    redundant-compute-scaled work + amortized per-epoch message latency +
    (depth-k) halo bytes once per k steps ≈ per-step bytes.

    Heat only: the wave kernel is time_order=2 (two input buffers, one
    output) — its state does not rotate closed within one epoch, so
    ``Target(exchange_every=k)`` rejects it (``TargetError``) and a
    modeled number would describe an uncompilable configuration."""
    rows = []
    for kind in ("heat",):
        for R in ranks:
            st = record[f"{kind}_r{R}"]
            local = tuple(G // r for G, r in zip(GLOBAL, RANK_GRIDS[R]))
            w = 2  # so4 taps reach ±2
            t_comp = st["t_comp"]
            t_bytes = st["halo_bytes"] / PEAKS.link_bytes_s
            n_msgs = 2 * len(local)  # one send/recv pair per face
            row = [kind, R]
            for k in exchange_every:
                if any(k * w > n for n in local):
                    row.append("-")  # deep halo outgrows the shard
                    continue
                vols = [
                    float(np.prod([n + 2 * j * w for n in local]))
                    for j in range(k)
                ]
                rcf = sum(vols) / (k * float(np.prod(local)))
                t_step = (
                    t_comp * rcf + t_bytes + n_msgs * LINK_LATENCY / k
                )
                gp = st["local_points"] * R / t_step / 1e9
                record[f"{kind}_r{R}"][f"gpts_ee{k}"] = gp
                row.append(f"{gp:.0f}")
            rows.append(tuple(row))
    return rows


def _tune_rows(record: dict, ranks: list) -> list:
    """Autotuner view of the scaling table: feed each rank count's
    modeled stats through the *shared* roofline terms
    (``launch/roofline.RooflineTerms``) and report the epoch depth the
    autotuner would pick (``recommend_exchange_every``) with its modeled
    per-step ranking — the same code path ``repro.tune`` scores live
    candidates with."""
    from repro.launch.roofline import RooflineTerms

    rows = []
    for kind in ("heat",):
        for R in ranks:
            st = record[f"{kind}_r{R}"]
            local = tuple(G // r for G, r in zip(GLOBAL, RANK_GRIDS[R]))
            terms = RooflineTerms(
                flops=st["local_points"] * st["flops_per_point"],
                bytes_accessed=st["local_points"] * 12,
                collectives={"collective-permute": st["halo_bytes"]},
                exchange_every=1,
                messages_per_epoch=2 * len(local),
                step_halo=(2,) * len(local),  # so4 taps reach ±2
                local_shape=local,
                device_kind=V5E,
            )
            ranked = terms.ranked_exchange_every(max_k=8)
            best_k, best_t = ranked[0]
            record[f"{kind}_r{R}"]["tuned_exchange_every"] = best_k
            record[f"{kind}_r{R}"]["tuned_step_time"] = best_t
            rows.append((
                kind, R, best_k, f"{best_t * 1e6:.0f}",
                " ".join(f"k{k}:{t*1e6:.0f}µs" for k, t in ranked[:3]),
            ))
    return rows


def run(fast: bool = False, overlap: str = "both",
        exchange_every: tuple = (1,), tune: bool = False) -> dict:
    """``overlap`` selects the latency-hiding regime to report: "off" is
    the paper's blocking exchange (t_comp + t_comm), "on" is the
    split-overlapped pipeline (max(t_comp, t_comm) — the IR-level
    ``split_overlapped_applies`` rewrite), "both" prints the two columns
    side by side so the win is explicit in the perf trajectory.
    ``tune=True`` appends the shared roofline model's recommended epoch
    depth per rank count (the quantity ``repro.tune`` searches for)."""
    assert overlap in ("on", "off", "both")
    record, rows = {"overlap": overlap}, []
    ranks = list(RANK_GRIDS) if not fast else [8, 64]
    for kind in ("heat", "wave"):
        for R in ranks:
            st = _stencil_stats(kind, 4, RANK_GRIDS[R])
            # memory-bound stencil: per-point bytes = read star + write ≈
            # (1 read + 1 write + reuse-miss) × 4B; use 3 streams as the
            # classic Jacobi estimate
            t_comp = max(
                st["local_points"] * st["flops_per_point"] / PEAKS.flops,
                st["local_points"] * 12 / PEAKS.hbm_bytes_s,
            )
            t_comm = st["halo_bytes"] / PEAKS.link_bytes_s
            t_nooverlap = t_comp + t_comm
            t_overlap = max(t_comp, t_comm)
            gpts_no = st["local_points"] * R / t_nooverlap / 1e9
            gpts_ov = st["local_points"] * R / t_overlap / 1e9
            record[f"{kind}_r{R}"] = dict(
                st, t_comp=t_comp, t_comm=t_comm,
                gpts_nooverlap=gpts_no, gpts_overlap=gpts_ov,
            )
            row = [kind, R, f"{st['halo_bytes']/2**20:.2f}",
                   f"{t_comp*1e6:.0f}", f"{t_comm*1e6:.0f}"]
            if overlap in ("off", "both"):
                row.append(f"{gpts_no:.0f}")
            if overlap in ("on", "both"):
                row.append(f"{gpts_ov:.0f}")
            rows.append(tuple(row))
    headers = ["kernel", "ranks", "halo MiB/rank", "t_comp µs", "t_comm µs"]
    if overlap in ("off", "both"):
        headers.append("GPts/s (paper)")
    if overlap in ("on", "both"):
        headers.append("GPts/s (+overlap)")
    print(table(
        f"fig8: strong scaling, 512³ so4 (TPU-v5e roofline model, "
        f"overlap={overlap})",
        rows, headers,
    ))
    if tuple(exchange_every) != (1,):
        tile_rows = _tiling_sweep(record, ranks, tuple(exchange_every))
        print(table(
            "fig8: temporal-tiling sweep (GPts/s per exchange_every, "
            "latency amortized 1/k vs redundant boundary compute)",
            tile_rows,
            ["kernel", "ranks"] + [f"k={k}" for k in exchange_every],
        ))
    if tune:
        print(table(
            "fig8: autotuner recommendation (RooflineTerms per rank count)",
            _tune_rows(record, ranks),
            ["kernel", "ranks", "best k", "t_step µs", "ranking"],
        ))
    # structural assertion recorded for EXPERIMENTS.md: halo bytes per
    # rank shrink as ranks grow (surface/volume)
    hb = [record[f"heat_r{R}"]["halo_bytes"] for R in ranks]
    assert all(a >= b for a, b in zip(hb, hb[1:])), hb
    save_record("fig8_scaling", record)
    return record


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--overlap", choices=["on", "off", "both"], default="both")
    ap.add_argument("--exchange-every", default="1",
                    help="comma list of epoch depths to sweep, e.g. 1,2,4,8")
    ap.add_argument("--tune", action="store_true",
                    help="append the roofline model's recommended epoch "
                         "depth per rank count")
    a = ap.parse_args()
    run(fast=a.fast, overlap=a.overlap,
        exchange_every=tuple(int(k) for k in a.exchange_every.split(",")),
        tune=a.tune)

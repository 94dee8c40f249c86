import os

# a CPU-only tool: virtual CPU devices, never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both

For each cell: ``jax.jit(step, in_shardings, out_shardings).lower(...)
.compile()`` on the production mesh; prints ``memory_analysis()`` (proves
it fits) and ``cost_analysis()`` (FLOPs/bytes for §Roofline) and appends
a JSON record to ``results/dryrun/<cell>.json``.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import LM_SHAPES, get_config, get_shape  # noqa: E402
from repro.configs.registry import ARCHS, shape_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_step  # noqa: E402

# Shared with repro.api's CompiledStencil.cost(); lives in roofline.py
# because importing this module forces the 512-device XLA flag.
from repro.launch.roofline import collective_bytes_from_hlo  # noqa: E402,F401
from repro.dist.sharding import default_rules  # noqa: E402


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             unroll: bool = False) -> dict:
    """One dry-run cell.  ``unroll=True`` unrolls the supercell/chunk
    scans at trace time so ``cost_analysis`` (which counts a while-loop
    body ONCE — verified against a hand-built loop) reports exact
    whole-model FLOPs/bytes/collectives; used for the §Roofline table.
    The default (scan) mode is the production compile path."""
    from repro.models.flags import set_unroll_scans

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod=multi_pod)
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "unrolled": unroll,
    }
    t0 = time.time()
    with set_unroll_scans(unroll):
        fn, args, in_sh, out_sh = build_step(cfg, shape, mesh, rules)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
    record["lower_s"] = round(time.time() - t0, 1)

    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    record["memory"] = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax: one dict per program
        cost = cost[0] if cost else {}
    record["cost"] = {
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "transcendentals": cost.get("transcendentals"),
    }
    t2 = time.time()
    hlo = compiled.as_text()
    record["collective_bytes"] = collective_bytes_from_hlo(hlo)
    record["hlo_analysis_s"] = round(time.time() - t2, 1)
    record["params"] = cfg.param_count()
    record["active_params"] = cfg.active_param_count()
    record["ok"] = True

    os.makedirs(outdir, exist_ok=True)
    cell = f"{arch}__{shape_name}__{record['mesh']}"
    with open(os.path.join(outdir, cell + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def run_cell_delta(arch: str, shape_name: str, multi_pod: bool, outdir: str) -> dict:
    """Exact whole-model cost analysis by supercell-delta extrapolation.

    XLA's cost analysis counts a while-loop body once, so the scan-mode
    records under-count FLOPs/collectives by the trip count.  Full
    unrolling is exact but compiles for ~15 min/cell.  Instead: lower the
    SAME step for 1-supercell and 2-supercell model variants with ALL
    scans unrolled (cheap — the supercell scan has trip count 1/2, and
    inner chunk scans unroll within one cell), then extrapolate linearly:

        cost(n) = cost(1) + (cost(2) - cost(1)) · (n - 1)

    Exact because every supercell is an identical compute/communication
    unit (verified against full unrolls in EXPERIMENTS.md §Dry-run).
    Memory analysis is NOT extrapolated — the scan-mode record (full
    model) already reports true per-device residency.
    """
    import dataclasses as dc

    from repro.models.flags import set_unroll_scans

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod=multi_pod)
    cell_len = len(cfg.block_pattern)
    n_cells = cfg.n_layers // cell_len

    def one(k: int) -> dict:
        over = {"n_layers": cell_len * k}
        if cfg.is_encoder_decoder:
            over["n_encoder_layers"] = k
        cfg_k = dc.replace(cfg, **over)
        with set_unroll_scans(True):
            fn, args, in_sh, out_sh = build_step(cfg_k, shape, mesh, rules)
            lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return {
            "flops": cost.get("flops") or 0.0,
            "bytes_accessed": cost.get("bytes accessed") or 0.0,
            "collectives": collective_bytes_from_hlo(compiled.as_text()),
        }

    t0 = time.time()
    c1 = one(1)
    c2 = one(2)

    def extrap(a, b):
        return a + (b - a) * (n_cells - 1)

    kinds = set(c1["collectives"]) | set(c2["collectives"])
    coll = {
        k: extrap(c1["collectives"].get(k, 0.0), c2["collectives"].get(k, 0.0))
        for k in kinds
    }
    # encoder layers scale with supercells only when counts match; for
    # enc-dec models n_encoder_layers is scaled alongside, so the delta
    # carries (1 decoder cell + 1 encoder layer) — exact when
    # n_encoder_layers == n_supercells (true for seamless: 24/24).
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "method": "delta-extrapolation",
        "n_supercells": n_cells,
        "analysis_s": round(time.time() - t0, 1),
        "cost": {
            "flops": extrap(c1["flops"], c2["flops"]),
            "bytes_accessed": extrap(c1["bytes_accessed"], c2["bytes_accessed"]),
        },
        "collective_bytes": coll,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "ok": True,
    }
    os.makedirs(outdir, exist_ok=True)
    cell = f"{arch}__{shape_name}__{record['mesh']}"
    with open(os.path.join(outdir, cell + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id (or --all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--multi-pod", choices=["off", "on", "both"], default="off"
    )
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument(
        "--unroll", action="store_true",
        help="unroll scans for exact cost analysis (roofline mode)",
    )
    ap.add_argument(
        "--delta", action="store_true",
        help="exact cost analysis via supercell-delta extrapolation (fast)",
    )
    ap.add_argument(
        "--skip-existing", action="store_true",
        help="resume: skip cells whose record already exists in outdir",
    )
    args = ap.parse_args()

    archs = list(ARCHS) if args.all or not args.arch else [args.arch]
    shapes = [s.name for s in LM_SHAPES] if not args.shape else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            ok, reason = shape_applicable(arch, shape_name)
            if not ok:
                print(f"SKIP  {arch} × {shape_name}: {reason}")
                continue
            for mp in pods:
                tag = f"{arch} × {shape_name} × {'2x16x16' if mp else '16x16'}"
                cell_file = os.path.join(
                    args.outdir,
                    f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}.json",
                )
                if args.skip_existing and os.path.exists(cell_file):
                    print(f"SKIP  {tag}: record exists")
                    continue
                try:
                    if args.delta:
                        rec = run_cell_delta(arch, shape_name, mp, args.outdir)
                        print(f"OK    {tag}: analysis={rec['analysis_s']}s "
                              f"flops={rec['cost']['flops']:.3e} (delta)")
                        continue
                    rec = run_cell(arch, shape_name, mp, args.outdir,
                                   unroll=args.unroll)
                    m = rec["memory"]
                    # memory_analysis reports the per-device module already
                    per_dev = (m["argument_bytes"] or 0) / 2**30
                    print(
                        f"OK    {tag}: compile={rec['compile_s']}s "
                        f"flops={rec['cost']['flops']:.3e} "
                        f"args/dev={per_dev:.2f}GiB"
                    )
                except Exception as e:
                    failures += 1
                    print(f"FAIL  {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc(limit=4)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()

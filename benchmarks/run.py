"""Benchmark driver: one harness per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast]

fig7a/b  heat / acoustic-wave throughput sweeps (Devito-like frontend)
fig8     strong-scaling model (halo bytes + roofline terms vs ranks)
fig10    PW + tracer advection (PSyclone-like frontend, fusion counts)
table1   backend comparison (jnp vs pallas; raw vs optimized pipeline)
serve    mixed-traffic serving load test (repro.serve.stencil engine)
serve_load_bursty  bursty autoscaled bucket (PoolSizer grow/shrink)
soak     fault-injected resilience soak (checkpoint overhead, recovery)
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced sizes (CI)")
    ap.add_argument("--only", default=None, help="comma-list of benches")
    ap.add_argument("--tune", action="store_true",
                    help="autotune Targets (repro.tune) in benches that "
                         "support it; records carry tuned-vs-manual "
                         "provenance")
    ap.add_argument("--fused-epoch", action="store_true",
                    help="add/time the pallas epoch-megakernel variants "
                         "in benches that support them")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        backend_compare,
        fig7_heat,
        fig7_wave,
        fig8_scaling,
        fig10_advection,
        resilience_soak,
        serve_load,
    )

    benches = {
        "fig7_heat": fig7_heat.run,
        "fig7_wave": fig7_wave.run,
        "fig8_scaling": fig8_scaling.run,
        "fig10_advection": fig10_advection.run,
        "backend_compare": backend_compare.run,
        "serve_load": serve_load.run,
        "serve_load_bursty": serve_load.run_bursty,
        "resilience_soak": resilience_soak.run,
    }
    wanted = args.only.split(",") if args.only else list(benches)
    failures = 0
    for name in wanted:
        print(f"\n=== {name} ===")
        t0 = time.time()
        kwargs = {"fast": args.fast}
        params = inspect.signature(benches[name]).parameters
        if args.tune and "tune" in params:
            kwargs["tune"] = True
        if args.fused_epoch and "fused_epoch" in params:
            kwargs["fused_epoch"] = True
        try:
            benches[name](**kwargs)
            print(f"[{name} done in {time.time()-t0:.1f}s]")
        except Exception as e:  # pragma: no cover
            failures += 1
            import traceback

            traceback.print_exc()
            print(f"[{name} FAILED: {e}]")
    return failures


if __name__ == "__main__":
    sys.exit(main())

"""Readings that a cell's limit is set from, on the chip, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed: set-up and a short window of the cell's own traffic, at
its own size, then two readings over the same answers: the program's
(max over the answers of max|answer - reference| / max|reference|), and
the control's: the reference itself computed in bfloat16, the nearest
precision below the configuration's float32, put in the program's place.
The lower reading of a limit is the largest program reading over a dozen
seeds or more; the upper is the smallest control reading.  One JSON line
per seed; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import registry  # noqa: E402
from bench.reference import relative_errors  # noqa: E402


def readings(cell_name: str, seeds, seconds: float, devices, config=None):
    """Yield one dict of readings per seed."""
    from repro import compile_cache

    compile_cache.enable()
    # every program of the run, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = registry.workload(cell_name, registry.spec())
    config = config or registry.data("configs", cell["config"])
    traffic = registry.data("traffic", cell["traffic"])
    equation = registry.code("equations", config["equation"])
    devices = list(devices)[: int(cell["chips"])]
    driver = registry.code("drivers", traffic["driver"]).Driver(
        config, traffic, equation, devices)
    advance, radius = equation.advancer(config), equation.radius(config)
    for seed in seeds:
        t0 = time.perf_counter()
        driver.prepare(seed)
        rec = driver.window(seconds, jax.profiler.TraceAnnotation)
        driver.release()
        answers = driver.answers()
        program = relative_errors(answers, advance, radius, devices)
        control = relative_errors(answers, advance, radius, devices,
                                  control_dtype=jnp.bfloat16)
        n_answers = len(answers)
        del answers  # the next seed's set-up needs the memory they hold
        yield {
            "seed": seed, "answers": n_answers, "attempted": rec["attempted"],
            "program": max(program), "control": max(control),
            "control_min_answer": min(control),
            "seconds": time.perf_counter() - t0,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in readings(args.workload, seeds, args.seconds, devices):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

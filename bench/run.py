"""Run one cell of the benchmark on the chip and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  One process per run.  It checks that the
default device is a TPU and that there are as many chips as the cell
asks for (exit 2, no result, otherwise), turns on the persistent
compilation cache (``repro.compile_cache``), builds the cell from its
configuration and traffic files, warms up, measures for ``--seconds``,
compares the window's answers with the plain reference, and prints the
result as the last line of standard output.  With ``--trace 1`` the
window runs under the profiler, and the line carries the per-layer
metrics and a breakdown instead of the end-to-end metrics.  The numbers
compared, each with its limit, are the last lines of standard error and
the last key of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import registry  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.reference import relative_errors  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def _memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(cell_name: str, seed: int, seconds: float, trace: bool, devices,
            *, config=None, driver_hook=None) -> dict:
    """One run of ``cell_name`` on ``devices``; returns the result line.

    ``config`` stands in for the cell's configuration file (tests run
    tiny sizes through here), and ``driver_hook(driver)`` may alter the
    driver before set-up."""
    benchmark = registry.spec()
    cell = registry.workload(cell_name, benchmark)
    config = config or registry.data("configs", cell["config"])
    traffic = registry.data("traffic", cell["traffic"])
    cell_file = registry.data("cells", cell_name)
    if len(devices) < int(cell["chips"]) or int(config["chips"]) != int(cell["chips"]):
        raise NoChip(f"cell {cell_name} needs {cell['chips']} chips, "
                     f"found {len(devices)}")
    devices = list(devices)[: int(cell["chips"])]
    from repro import compile_cache

    compile_cache.enable()
    # every program of the run, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    equation = registry.code("equations", config["equation"])
    driver = registry.code("drivers", traffic["driver"]).Driver(
        config, traffic, equation, devices)
    if driver_hook is not None:
        driver_hook(driver)
    warm = driver.prepare(seed)
    record = {
        "cell": cell_name, "chips": len(devices),
        "device_kind": devices[0].device_kind,
        "compile_host_s": driver.compile_host_s, **warm,
        "setup_s": time.perf_counter() - T_START,
    }

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(secs) if name == COMPILE_EVENT else None)
    annotate = jax.profiler.TraceAnnotation
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
        n_before = len(compiles)
        try:
            record.update(driver.window(seconds, annotate))
        finally:
            if trace:
                jax.profiler.stop_trace()
        compiles_in_window = len(compiles) - n_before
        memory_peak = _memory_peak(devices)

        driver.release()
        answers = driver.answers()
        errors = relative_errors(answers, equation.advancer(config),
                                 equation.radius(config), devices)
        limit = float(cell_file["limits"]["rel_err"])
        # the worst answer, NaN first; a non-finite reading prints as text
        rel_err = max(errors, key=lambda e: (math.isnan(e), e)) if errors else None
        if rel_err is not None and not math.isfinite(rel_err):
            rel_err = repr(rel_err)
        failed = sum(not (e <= limit) for e in errors)
        correct = bool(errors) and failed == 0

        if trace:
            paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
            record["trace"] = (tr.load(paths[0], (tr.WINDOW_SPAN, *driver.SPANS))
                               if paths else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(cell_name, benchmark[kind]):
        value = registry.reader("layers" if trace else "e2e", m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "compiles_in_window": compiles_in_window,
        "answers_checked": len(errors),
    }
    t = record.get("trace")
    if trace and t is not None:
        device["busy_s"] = sum(tr.busy_ns(t).values()) / len(t.ops) / 1e9
        device["window_s"] = tr.window_ns(t) / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
    result["checks"] = {"rel_err": {"value": rel_err, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (default device is {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    try:
        result = execute(args.workload, args.seed, args.seconds, bool(args.trace), devices)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

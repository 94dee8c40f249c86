"""Record a short trace of a cell's traffic at a small grid, on the chip,
for the trace tests (``data/``).

    python bench/tests/record_trace.py --workload heat2d-16384.loop \
        --grid 1024 --steps 8 --calls 3 --out <file>.xplane.pb

Sets the cell's driver up at ``--grid``² with ``--steps`` steps a call,
warms it up, then traces ``--calls`` calls, each a ``loop.call`` span
inside ``bench.window``, with the profiler options of ``bench/run.py
--trace 1``, and copies the ``.xplane.pb`` to ``--out``.  Exits 2
without a TPU."""
import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

from bench import registry, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--grid", type=int, required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU (default device is {devices[0].platform!r})", file=sys.stderr)
        return 2
    cell = registry.workload(args.workload, registry.spec())
    config = dict(registry.data("configs", cell["config"]), grid=[args.grid] * 2)
    traffic = dict(registry.data("traffic", cell["traffic"]), steps_per_call=args.steps)
    driver = registry.code("drivers", traffic["driver"]).Driver(
        config, traffic, registry.code("equations", config["equation"]), devices)
    driver.prepare(args.seed)
    state = driver.state
    annotate = jax.profiler.TraceAnnotation
    out = tempfile.mkdtemp(prefix="record-trace-")
    try:
        jax.profiler.start_trace(out, profiler_options=run._profile_options())
        with annotate("bench.window"):
            for _ in range(args.calls):
                with annotate("loop.call"):
                    state = jax.block_until_ready(driver._call(state))
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(path, args.out)
    finally:
        shutil.rmtree(out, ignore_errors=True)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Worker for ``test_faults.py``: the 2x2 cell at a tiny size on four
virtual CPU devices, once sound and once with the exchange between chips
left out (every ``ppermute`` returns zeros), with the state returned
unchanged, and with one point of the answer altered.  Prints one JSON
line mapping each of ``sound``, ``no_exchange``, ``unchanged`` and
``altered`` to the run's ``correct``, and ``control_fails`` to whether
the bfloat16 control fails the cell's limit where the program passes.  It sets the device
count before importing JAX, so it runs in a process of its own."""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import calibrate, registry  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tests.test_faults import _loop_hook  # noqa: E402

CELL = "heat2d-65536.2x2"


def main() -> None:
    out = {"sound": tiny.execute(CELL)["correct"]}
    for fault in ("unchanged", "altered"):
        out[fault] = tiny.execute(CELL, hook=_loop_hook(fault))["correct"]
    real = jax.lax.ppermute
    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    try:
        from repro import api

        api.clear_cache()
        out["no_exchange"] = tiny.execute(CELL)["correct"]
    finally:
        jax.lax.ppermute = real
        api.clear_cache()
    limit = registry.data("cells", CELL)["limits"]["rel_err"]
    lines = calibrate.readings(CELL, [3, 2**32 + 3], 1.0, jax.devices(),
                               config=tiny.config(CELL))
    out["control_fails"] = all(r["program"] <= limit < r["control"] for r in lines)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Distribution correctness: N-rank shard_map + dmp halo exchange ==
single-device, bitwise for fp32 stencils.

Each scenario runs in a subprocess with
``--xla_force_host_platform_device_count=8`` so the virtual-device flag
never leaks into this pytest process (unit tests see 1 device).
"""
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")

SCENARIOS = [
    "1d-zero",
    "1d-periodic",
    "1d-backward-zero",
    "1d-backward-periodic",
    "2d-zero",
    "2d-periodic",
    "3d",
    "box",
    "box-diagonal",
    "overlap",
    "overlap-zero",
    "overlap-periodic",
    "overlap-box-seq",
    "overlap-diagonal",
    "overlap-pallas",
    "pipeline-spec",
    "pallas",
    "wide-halo",
    "time-loop",
    "ee2-periodic",
    "ee4-zero",
    "ee4-overlap",
    "ee4-overlap-zero",
    "ee2-box-overlap",
    "ee4-pallas",
    "ee-heat-epoch",
    "tune-4rank",
    "pallas-tile-shard-error",
    "resilience-heat-k1",
    "resilience-heat-k4",
    "resilience-wave-k4",
    "tune-transfer",
    "slot-axis",
    "serve-pooled",
    "serve-autoscale",
    "scopes-2x2-k1",
    "scopes-2x2-k4",
    "pallas-2x2-received-halo",
]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_distributed_equivalence(scenario):
    proc = subprocess.run(
        [sys.executable, WORKER, scenario],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (
        f"scenario {scenario} failed:\nSTDOUT:\n{proc.stdout}\n"
        f"STDERR:\n{proc.stderr[-3000:]}"
    )

"""Shared benchmark utilities: timing, throughput, result records.

Throughput unit is GPts/s (grid points updated per second) — the paper's
fig. 7/8/10 metric.  The CPU container measures XLA-CPU absolute numbers;
the *relative* effects (fusion, CSE, decomposition overhead, backend
choice) are the reproducible signal, and the TPU roofline model
(launch/roofline.py) provides the target-hardware projection.
"""
from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Callable, Optional

import jax
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")


def _git_rev() -> Optional[str]:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(__file__),
            capture_output=True, text=True, timeout=5,
        ).stdout.strip() or None
    except Exception:
        return None


def provenance_block() -> dict:
    """Where this record came from: the hardware signature the autotuner
    keys its cache on, the git revision, and the wall-clock moment — so
    two ``results/bench`` JSONs are comparable (or visibly not)."""
    from repro.tune.cache import hardware_signature

    return {
        "hardware": hardware_signature(),
        "git_rev": _git_rev(),
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "traced": _obs_enabled(),
    }


def _obs_enabled() -> bool:
    from repro import obs

    return obs.enabled()


def time_step(fn: Callable, args, iters: int = 10, warmup: int = 2) -> float:
    """Median wall-clock seconds per call (blocked until ready)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def gpts(shape: tuple, seconds: float, timesteps: int = 1) -> float:
    pts = float(np.prod(shape)) * timesteps
    return pts / seconds / 1e9


def save_record(name: str, record: dict) -> None:
    """Write ``results/bench/<name>.json``, stamped with a provenance
    block."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    record = dict(record)
    record["provenance"] = provenance_block()
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(record, f, indent=1)


def target_record(target, provenance: str = "manual") -> dict:
    """The full ``Target`` as a JSON-able dict for results records —
    every knob plus where the config came from (``"manual"`` for a
    hand-picked target, ``"tuned"`` for an autotuner winner), so a
    benchmark number can always be traced back to the exact
    configuration that produced it."""
    from repro.tune.cache import target_to_dict

    record = target_to_dict(target)
    record["provenance"] = provenance
    return record


def table(title: str, rows: list, headers: list) -> str:
    widths = [
        max(len(str(h)), max((len(str(r[i])) for r in rows), default=0))
        for i, h in enumerate(headers)
    ]
    out = [title, "-" * len(title)]
    out.append("  ".join(str(h).ljust(w) for h, w in zip(headers, widths)))
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)

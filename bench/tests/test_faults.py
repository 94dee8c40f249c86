"""The comparison that decides ``correct`` catches each fault a cell can
have.  Each test skips the harness's look for a chip, drives the rest of
a run at a tiny size on the CPU with the timed path broken underneath,
and sees ``correct`` come out false."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))


def _poke(x):
    """One point of ``x`` moved by 1e-3 of its scale: an answer altered
    where it is produced."""
    mid = tuple(n // 2 for n in x.shape)
    return x.at[mid].add(1e-3 * jnp.max(jnp.abs(x)))


def _loop_hook(fault):
    def hook(driver):
        call = driver._call
        if fault == "unchanged":
            driver._call = jax.jit(lambda s: s)
        elif fault == "altered":
            driver._call = jax.jit(lambda s: tuple(call(s)[:-1]) + (_poke(call(s)[-1]),))
    return hook


def test_sound_runs_are_correct():
    assert tiny.execute("heat2d-16384.loop")["correct"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_loop_faults_are_not_correct(fault):
    result = tiny.execute("heat2d-16384.loop", hook=_loop_hook(fault))
    assert result["correct"] is False, result["checks"]


def test_2x2_faults_are_not_correct():
    """The 2x2 cell on four virtual CPU devices, in a process of its own:
    the exchange left out, the state unchanged, an answer altered; and
    the control fails the cell's limit."""
    proc = subprocess.run([sys.executable, os.path.join(HERE, "faults_2x2.py")],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False, "unchanged": False,
                   "altered": False, "control_fails": True}

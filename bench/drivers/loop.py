"""Traffic ``loop``: a user's long simulation.  Back-to-back
``CompiledStencil.time_loop(state, steps_per_call)`` calls, each going on
from the state the last one returned, every call blocked on.

Parameters (``traffic/<mix>.json``): ``steps_per_call``.

``time_loop`` is called under one ``jax.jit``: called eagerly it traces
and compiles its loop again on every call, and no compile may fall inside
the measured window.

Set-up makes the state from the seed on the device, then makes two calls
(the first compiles, or loads from the cache; the second is steady).  The
window goes on from that state.  The answer checked is the window's last
call: the state it was given, and the state it returned.
"""
from __future__ import annotations

import time

import jax

from bench import layout, seeds, work
from bench.reference import Answer


class Driver:
    SPANS = ("loop.call",)  # the host spans it opens inside ``bench.window``

    def __init__(self, config: dict, traffic: dict, equation, devices) -> None:
        from repro import api

        self.config, self.equation = config, equation
        self.devices = list(devices)[: int(config["chips"])]
        self.steps = int(traffic["steps_per_call"])
        program = equation.program(config)
        target, sharding = layout.target_and_sharding(config, self.devices)
        t0 = time.perf_counter()
        compiled = api.compile(program, target)
        self.compile_host_s = time.perf_counter() - t0
        steps = self.steps
        self._call = jax.jit(lambda state: compiled.time_loop(state, steps))
        self._make = equation.state_maker(config, sharding)
        self.points = work.points(config["grid"])
        self.levels_read = len(compiled.input_indices)
        self.steps_per_kernel = work.steps_per_kernel_call(
            compiled.kernel_dispatches, compiled.target.exchange_every
        )
        self.state = None
        self.answer = None

    def prepare(self, seed: int) -> dict:
        self.state = None
        self.answer = None
        state = self._make(seeds.key(seed))
        t0 = time.perf_counter()
        state = jax.block_until_ready(self._call(state))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.state = jax.block_until_ready(self._call(state))
        steady = time.perf_counter() - t0
        return {"first_call_s": first, "steady_call_s": steady}

    def window(self, seconds: float, annotate) -> dict:
        calls = 0
        prev = self.state
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with annotate("bench.window"):
            while True:
                prev = self.state
                with annotate("loop.call"):
                    self.state = jax.block_until_ready(self._call(prev))
                calls += 1
                if time.perf_counter() >= deadline:
                    break
        window_s = time.perf_counter() - t0
        self.answer = Answer(inputs=prev, steps=self.steps, got=self.state[-1])
        steps = calls * self.steps
        return {
            "window_s": window_s,
            "attempted": calls,
            "point_steps": steps * self.points,
            "least_bytes": steps * work.least_bytes_per_step(
                self.points, self.levels_read, 4, self.steps_per_kernel
            ),
        }

    def answers(self) -> list:
        return [self.answer]

    def release(self) -> None:
        """Drop everything but the answer, before the reference runs."""
        self.state = None

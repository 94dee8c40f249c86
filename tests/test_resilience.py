"""repro.resilience: epoch-aligned checkpointing, elastic resume, fault
injection, serve-layer migration, and the cross-hardware tune transfer.

The ISSUE 8 acceptance surface on a single device (the multi-rank
4 → 2 elastic resume lives in tests/dist_worker.py): a FaultPlan-killed
run resumed from its last committed snapshot is bitwise-identical to
both the uninterrupted resilient run and ``time_loop`` — including the
p>q wave whose time-buffer rotation *phase* must survive the resume —
plus Checkpointer retention/GC truthfulness and torn-write fallback.
"""
import os

import numpy as np
import pytest

from repro import api
from repro.api import Target
from repro.checkpoint.checkpointer import Checkpointer
from repro.frontends.oec_like import ProgramBuilder
from repro.launch.roofline import V5E
from repro.resilience import (
    FaultPlan,
    ResilientLoop,
    ResumeError,
    SimulatedFault,
    resume,
    truncate_snapshot,
)


def _heat(shape=(16, 16), alpha=0.25, name="heat_res"):
    p = ProgramBuilder(name, shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1))
        * alpha,
    )
    p.store(r, out)
    return p.finish(boundary="periodic")


def _wave(shape=(16, 16), name="wave_res"):
    # p=2 inputs > q=1 output: the rotation phase advances by 1 per
    # epoch-step and must be restored exactly on resume
    p = ProgramBuilder(name, shape)
    um = p.input("u_prev")
    u0 = p.input("u_now")
    out = p.output("u_next")
    tm, t0 = p.load(um), p.load(u0)
    r = p.apply(
        [tm, t0],
        lambda b, um, u0: 2.0 * u0.at(0, 0)
        - um.at(0, 0)
        + 0.1
        * (
            u0.at(-1, 0)
            + u0.at(1, 0)
            + u0.at(0, -1)
            + u0.at(0, 1)
            - 4.0 * u0.at(0, 0)
        ),
    )
    p.store(r, out)
    return p.finish(boundary="zero")


def _rand(shape, seed):
    return (
        np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    )


def _assert_bitwise(got, want, what):
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    assert len(got) == len(want), (what, len(got), len(want))
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (
            f"{what}: buffer {i} differs "
            f"(max |d| = {np.abs(np.asarray(g) - np.asarray(w)).max()})"
        )


# -------------------------------------------------------------------------
# driver: uninterrupted / kill-and-resume bitwise equality
# -------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 4])
def test_kill_and_resume_is_bitwise_heat(k, tmp_path):
    prog = _heat(name=f"heat_res_k{k}")
    u0 = _rand((16, 16), 0)
    tgt = Target(exchange_every=k)
    steps = 24
    ref = api.compile(prog, tgt).time_loop((u0,), steps)

    d = str(tmp_path / "ckpt")
    loop = ResilientLoop(
        prog, tgt, (u0,), steps, directory=d, checkpoint_every=1,
        fault_plan=FaultPlan(kill_at_epoch=(steps // k) // 2),
    )
    with pytest.raises(SimulatedFault):
        loop.run()
    assert ("fault", (steps // k) // 2, steps // 2) in loop.events

    resumed = resume(prog, d, tgt)
    assert resumed.step_count == steps // 2
    assert resumed.resumed_from == steps // 2
    final = resumed.run()
    _assert_bitwise(final, ref, f"heat k={k} kill+resume vs time_loop")


def test_uninterrupted_resilient_run_matches_time_loop(tmp_path):
    prog = _heat(name="heat_res_full")
    u0 = _rand((16, 16), 1)
    tgt = Target(exchange_every=2)
    ref = api.compile(prog, tgt).time_loop((u0,), 16)
    final = ResilientLoop(
        prog, tgt, (u0,), 16, directory=str(tmp_path / "c"),
        checkpoint_every=2,
    ).run()
    _assert_bitwise(final, ref, "uninterrupted resilient run")


@pytest.mark.parametrize("k,kill_epoch", [(1, 5), (2, 3)])
def test_wave_rotation_phase_survives_resume(k, kill_epoch, tmp_path):
    """p=2 > q=1: resuming mid-run must continue the SAME buffer
    rotation — a kill at an odd step (k=1, epoch 5) leaves phase 1."""
    prog = _wave(name=f"wave_res_k{k}")
    s0 = tuple(_rand((16, 16), 10 + i) for i in range(2))
    tgt = Target(exchange_every=k)
    steps = 16
    ref = api.compile(prog, tgt).time_loop(s0, steps)

    d = str(tmp_path / "ckpt")
    loop = ResilientLoop(
        prog, tgt, s0, steps, directory=d, checkpoint_every=1,
        fault_plan=FaultPlan(kill_at_epoch=kill_epoch),
    )
    with pytest.raises(SimulatedFault):
        loop.run()

    resumed = resume(prog, d, tgt)
    assert resumed.step_count == kill_epoch * k
    # k=1 advances one buffer per epoch: odd kill epoch → odd phase
    want_phase = (kill_epoch * (1 if k == 1 else 2)) % 2
    assert resumed._phase == want_phase
    final = resumed.run()
    _assert_bitwise(final, ref, f"wave k={k} rotation-phase resume")


def test_resume_onto_different_exchange_every(tmp_path):
    """The snapshot is global state at an epoch-aligned step — a resumer
    may pick a different temporal-tiling depth and stay bitwise."""
    prog = _heat(name="heat_res_kchange")
    u0 = _rand((16, 16), 2)
    steps = 32
    ref = api.compile(prog, Target(exchange_every=4)).time_loop((u0,), steps)

    d = str(tmp_path / "ckpt")
    loop = ResilientLoop(
        prog, Target(exchange_every=4), (u0,), steps, directory=d,
        checkpoint_every=1, fault_plan=FaultPlan(kill_at_epoch=4),
    )
    with pytest.raises(SimulatedFault):
        loop.run()
    final = resume(prog, d, Target(exchange_every=2)).run()
    _assert_bitwise(final, ref, "resume k=4 -> k=2")


# -------------------------------------------------------------------------
# resume validation
# -------------------------------------------------------------------------


def test_resume_rejects_wrong_program(tmp_path):
    prog = _heat(name="heat_res_owner")
    other = _heat(alpha=0.2, name="heat_res_other")
    d = str(tmp_path / "ckpt")
    ResilientLoop(
        prog, Target(), (_rand((16, 16), 3),), 4, directory=d,
        checkpoint_every=1,
    ).run()
    with pytest.raises(ResumeError, match="fingerprint"):
        resume(other, d, Target())


def test_resume_rejects_epoch_misaligned_target(tmp_path):
    # killed at step 3 under k=1; k=3 divides step 3 but not the
    # remaining 5 of 8 steps — both alignment legs must hold
    prog = _heat(name="heat_res_align")
    d = str(tmp_path / "ckpt")
    loop = ResilientLoop(
        prog, Target(), (_rand((16, 16), 4),), 8, directory=d,
        checkpoint_every=1, fault_plan=FaultPlan(kill_at_epoch=3),
    )
    with pytest.raises(SimulatedFault):
        loop.run()
    with pytest.raises(ResumeError, match="whole epochs"):
        resume(prog, d, Target(exchange_every=3))
    with pytest.raises(ResumeError, match="epoch"):
        ResilientLoop(
            prog, Target(exchange_every=2), (_rand((16, 16), 4),), 8,
            start_step=3,
        )


def test_resume_without_metadata_is_rejected(tmp_path):
    d = str(tmp_path / "ckpt")
    Checkpointer(d).save(0, {"state": {"b0": np.zeros((4, 4))}},
                         blocking=True)
    with pytest.raises(ResumeError, match="metadata"):
        resume(_heat(name="heat_res_meta"), d, Target())


# -------------------------------------------------------------------------
# torn writes: truncation falls back, startup GC reclaims
# -------------------------------------------------------------------------


def test_truncated_checkpoint_is_ignored_and_gcd(tmp_path):
    prog = _heat(name="heat_res_torn")
    u0 = _rand((16, 16), 5)
    tgt = Target(exchange_every=2)
    steps = 16
    ref = api.compile(prog, tgt).time_loop((u0,), steps)

    d = str(tmp_path / "ckpt")
    # checkpoint every epoch; the snapshot at step 10 commits and is then
    # torn, and the process dies before epoch 5 — the freshest COMMITTED
    # snapshot is step 8
    loop = ResilientLoop(
        prog, tgt, (u0,), steps, directory=d, checkpoint_every=1,
        keep_last=8,
        fault_plan=FaultPlan(kill_at_epoch=5, truncate_step=10),
    )
    with pytest.raises(SimulatedFault):
        loop.run()
    assert not os.path.exists(os.path.join(d, "step_00000010", "COMMITTED"))

    # any fresh Checkpointer's startup GC reclaims the wreck (resume()
    # constructs one first thing, so the count is observable here)
    probe = Checkpointer(d, keep_last=8)
    assert probe.stats.gcs == 1
    assert not os.path.exists(os.path.join(d, "step_00000010"))

    resumed = resume(prog, d, tgt)
    # the torn step-10 snapshot is invisible: resume restarts from step 8
    assert resumed.step_count == 8
    final = resumed.run()
    _assert_bitwise(final, ref, "torn-checkpoint fallback resume")


def test_truncate_snapshot_helper(tmp_path):
    d = str(tmp_path / "ckpt")
    ckpt = Checkpointer(d)
    ckpt.save(4, {"u": np.arange(16.0).reshape(4, 4)}, blocking=True)
    assert ckpt.available_steps() == [4]
    truncate_snapshot(d, 4)
    assert ckpt.available_steps() == []


# -------------------------------------------------------------------------
# Checkpointer hardening: retention, GC, truthful counters, manifest
# -------------------------------------------------------------------------


def test_keep_last_retention_and_counters(tmp_path):
    d = str(tmp_path / "ckpt")
    ckpt = Checkpointer(d, keep_last=2)
    for s in range(5):
        ckpt.save(s, {"u": np.full((2, 2), float(s))}, blocking=True)
    assert ckpt.available_steps() == [3, 4]
    assert ckpt.stats.as_dict() == {
        "saves": 5, "prunes": 3, "gcs": 0, "restores": 0,
    }


def test_startup_gc_counts_partials(tmp_path):
    d = str(tmp_path / "ckpt")
    Checkpointer(d).save(2, {"u": np.zeros((2, 2))}, blocking=True)
    # a torn dir (no COMMITTED) and an abandoned staging dir
    os.makedirs(os.path.join(d, "step_00000009"))
    os.makedirs(os.path.join(d, "step_00000011.tmp"))
    ckpt = Checkpointer(d)
    assert ckpt.stats.gcs == 2
    assert sorted(os.listdir(d)) == ["step_00000002"]


def test_manifest_extra_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    ckpt = Checkpointer(d)
    extra = {"program_fingerprint": "abc", "step": 6, "rotation_phase": 1}
    ckpt.save(6, {"state": {"b0": np.ones((3, 3))}}, blocking=True,
              extra=extra)
    m = ckpt.manifest()
    assert m["step"] == 6 and m["extra"] == extra
    assert list(m["leaves"]) == ["state/b0"]
    with pytest.raises(FileNotFoundError):
        Checkpointer(str(tmp_path / "empty")).manifest()


def test_keep_last_must_be_positive(tmp_path):
    with pytest.raises(ValueError, match="keep_last"):
        Checkpointer(str(tmp_path / "c"), keep_last=0)


# -------------------------------------------------------------------------
# serve migration: evacuate -> admit across engines
# -------------------------------------------------------------------------


def test_engine_evacuate_admit_is_bitwise(tmp_path):
    from repro.serve.stencil import StencilEngine, StencilEngineConfig
    from repro.serve.stencil.request import EVACUATED

    prog = _heat(name="heat_res_migrate")
    tgt = Target(exchange_every=2)
    states = [_rand((16, 16), 20 + i) for i in range(3)]
    refs = [
        api.compile(prog, tgt).time_loop((s,), 12) for s in states
    ]

    first = StencilEngine(StencilEngineConfig(slots_per_group=2))
    for s in states:
        first.submit(prog, (s,), 12, target=tgt)
    for _ in range(2):  # two slots advance to step 4; one stays queued
        first.step()
    d = str(tmp_path / "evac")
    evacuated = first.evacuate(prog.fingerprint, d)
    assert [r.steps_done for r in evacuated] == [4, 4, 0]
    assert all(r.status == EVACUATED for r in evacuated)
    assert first.pending == 0
    assert first.metrics.requests_evacuated == 3
    assert first.metrics.snapshot()["requests_evacuated"] == 3

    second = StencilEngine(StencilEngineConfig(slots_per_group=2))
    handles = second.admit_evacuated(d, prog)
    assert [h.steps_done for h in handles] == [4, 4, 0]
    second.run()
    assert second.metrics.requests_resumed == 3
    assert second.metrics.snapshot()["requests_resumed"] == 3
    for h, ref in zip(handles, refs):
        _assert_bitwise(h.result(), ref, f"migrated request {h.rid}")


def test_admit_requires_matching_program(tmp_path):
    from repro.serve.stencil import StencilEngine

    prog = _heat(name="heat_res_mig_owner")
    other = _heat(alpha=0.2, name="heat_res_mig_other")
    first = StencilEngine()
    first.submit(prog, (_rand((16, 16), 30),), 4)
    d = str(tmp_path / "evac")
    first.evacuate(prog.fingerprint, d)
    with pytest.raises(ResumeError, match="no matching Program"):
        StencilEngine().admit_evacuated(d, other)
    with pytest.raises(ResumeError, match="no evacuated requests"):
        StencilEngine().admit_evacuated(str(tmp_path / "nothing_here"), prog)


def test_submit_start_step_is_validated():
    from repro.serve.stencil import StencilEngine

    prog = _heat(name="heat_res_startstep")
    engine = StencilEngine()
    with pytest.raises(ValueError, match="start_step"):
        engine.submit(prog, (_rand((16, 16), 31),), 8,
                      target=Target(exchange_every=2), start_step=3)
    with pytest.raises(ValueError, match="start_step"):
        engine.submit(prog, (_rand((16, 16), 31),), 8, start_step=8)


# -------------------------------------------------------------------------
# tune transfer: cross-hardware warm start
# -------------------------------------------------------------------------


def _tune_kwargs():
    return dict(
        measure=False, backends=("jnp",), exchange_every=(1, 2),
        overlap=(False,), fused_epoch=(False,), device_kind=V5E,
    )


def test_tune_transfer_adopts_foreign_entry(tmp_path, monkeypatch):
    from repro.tune import cache as tc
    from repro.tune import cache_stats, reset_cache_stats, tune

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc"))
    prog = _heat(name="heat_res_xfer")
    res = tune(prog, ranks=1, **_tune_kwargs())
    assert not res.from_cache

    # re-home the stored entry under a fake foreign hardware signature
    # (the mesh=None winner is device-independent, so it rebuilds here)
    entry = tc.load(res.cache_key)
    donor = dict(entry)
    donor["hardware"] = "tpu:TPU v5e:n8"
    donor["n_ranks"] = 8
    tc.store(
        tc.cache_key(prog.fingerprint, donor["hardware"], 8,
                     donor["options"]),
        donor,
    )
    os.unlink(tc.entry_path(res.cache_key))

    reset_cache_stats()
    moved = tune(prog, ranks=1, transfer=True, **_tune_kwargs())
    stats = cache_stats().as_dict()
    assert moved.from_cache and moved.winner.origin == "transfer"
    assert stats["transfer_hits"] == 1 and stats["hits"] == 0
    # a transfer is a warm start, not a local fact: nothing re-stored
    assert stats["stores"] == 0
    assert moved.target.fingerprint == entry["winner"]["fingerprint"]

    # transfer=False (the default): the very same miss searches fresh
    reset_cache_stats()
    fresh = tune(prog, ranks=1, **_tune_kwargs())
    stats = cache_stats().as_dict()
    assert not fresh.from_cache
    assert stats["transfer_hits"] == 0 and stats["stores"] == 1


def test_tune_transfer_ignores_mismatched_entries(tmp_path, monkeypatch):
    """Different options digest or different program never transfers;
    an empty cache dir is a plain None."""
    from repro.tune import cache as tc
    from repro.tune import cache_stats, reset_cache_stats, tune

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "tc"))
    prog = _heat(name="heat_res_noxfer")
    reset_cache_stats()
    assert tc.lookup_transfer(prog, 1, "deadbeef") is None

    res = tune(prog, ranks=1, **_tune_kwargs())
    entry = tc.load(res.cache_key)
    donor = dict(entry)
    donor["hardware"] = "tpu:TPU v5e:n8"
    tc.store(tc.cache_key(prog.fingerprint, donor["hardware"], 8,
                          donor["options"]), donor)
    os.unlink(tc.entry_path(res.cache_key))

    # wrong options digest -> no transfer
    assert tc.lookup_transfer(prog, 1, "0000aaaa0000") is None
    # wrong program -> no transfer
    other = _heat(alpha=0.2, name="heat_res_noxfer2")
    assert tc.lookup_transfer(other, 1, donor["options"]) is None
    assert cache_stats().transfer_hits == 0


# -------------------------------------------------------------------------
# api surface
# -------------------------------------------------------------------------


def test_api_entry_points(tmp_path):
    import repro

    prog = _heat(name="heat_res_api")
    u0 = _rand((16, 16), 40)
    ref = api.compile(prog, Target()).time_loop((u0,), 4)
    d = str(tmp_path / "ckpt")
    loop = repro.resilient_loop(prog, Target(), (u0,), 4, directory=d)
    final = loop.run()
    _assert_bitwise(final, ref, "repro.resilient_loop")
    resumed = repro.resume(prog, d)
    assert resumed.done  # final snapshot is at n_steps
    compiled = api.compile(prog, Target())
    assert compiled.epochs(8) == 8
    assert isinstance(compiled.ret_indices, tuple)
    with pytest.raises(ValueError, match="exchange_every"):
        api.compile(prog, Target(exchange_every=4)).epochs(6)

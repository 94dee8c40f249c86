"""repro.tune — search space, roofline scoring, measurement, and the
persistent on-disk cache; plus the RooflineTerms edge cases the tuner
leans on and the compile-time pallas_tile validation.

Unit scale: single device (mesh candidates under real multi-device
meshes are exercised by tests/dist_worker.py scenarios ``tune-4rank``
and ``pallas-tile-shard-error``).
"""
import json
import math
import os

import numpy as np
import pytest

from repro import api
from repro.api import Target, TargetError
from repro.frontends.oec_like import ProgramBuilder
from repro.launch.roofline import V5E, RooflineTerms
from repro.tune import (
    Candidate,
    cache_stats,
    enumerate_candidates,
    measure_compiled,
    reset_cache_stats,
    target_from_dict,
    target_to_dict,
    tune,
)
from repro.tune import cache as tune_cache
from repro.tune.space import (
    exchange_every_candidates,
    factorizations,
    mesh_assignments,
    pallas_tile_candidates,
    strategy_candidates,
)


def _jacobi_prog(shape=(32, 32), boundary="periodic", name="tune_jacobi"):
    p = ProgramBuilder(name, shape)
    u = p.input("u")
    out = p.output("out")
    t = p.load(u)
    r = p.apply(
        [t],
        lambda b, u: (u.at(-1, 0) + u.at(1, 0) + u.at(0, -1) + u.at(0, 1))
        * 0.25,
    )
    p.store(r, out)
    return p.finish(boundary=boundary)


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    d = tmp_path / "tune-cache"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(d))
    reset_cache_stats()
    yield str(d)
    reset_cache_stats()


# -------------------------------------------------------------------------
# search space
# -------------------------------------------------------------------------


def test_factorizations():
    assert factorizations(1) == [()]
    assert set(factorizations(8)) == {(8,), (2, 4), (4, 2), (2, 2, 2)}
    assert set(factorizations(6)) == {(6,), (2, 3), (3, 2)}


def test_mesh_assignments_dedup_and_rank_bound():
    # rank-2 program: (2,2,2) factorization needs 3 dims → dropped;
    # 2×2 over dims (0,1) and (1,0) are the same assignment
    assigns = mesh_assignments(8, rank=2)
    assert ((2, 0), (4, 1)) in assigns and ((4, 0), (2, 1)) in assigns
    assert ((8, 0),) in assigns and ((8, 1),) in assigns
    assert not any(len(a) > 2 for a in assigns)
    four = mesh_assignments(4, rank=2)
    assert four.count(((2, 0), (2, 1))) == 1


def test_strategy_candidates_respect_divisibility():
    # 6 does not divide 32: no factor-6 grids on either dim
    prog = _jacobi_prog((32, 32))
    strategies = strategy_candidates(prog, 6)
    for s in strategies:
        for g, d in zip(s.grid_shape, s.dims):
            assert 32 % g == 0
    assert strategy_candidates(prog, 1) == [None]


def test_exchange_every_candidates_filter_deep_halo():
    prog = _jacobi_prog((8, 8))
    # single device, shard 8×8, halo 1/step: k=8 fills the shard, fine;
    # k beyond the shard is filtered
    ks = exchange_every_candidates(prog, None, ks=(1, 2, 4, 8, 16))
    assert 1 in ks and 16 not in ks
    # non-epochable inputs keep k=1 only (wave-like: guarded upstream)
    assert exchange_every_candidates(prog, None, ks=(1,)) == [1]


def test_pallas_tile_candidates_divide_shard():
    prog = _jacobi_prog((64, 32))
    tiles = pallas_tile_candidates(prog, None)
    assert None in tiles and (64, 32) in tiles and (32, 32) in tiles
    for t in tiles:
        if t is not None:
            assert all(n % x == 0 for n, x in zip((64, 32), t))


def test_enumerate_baseline_first_and_valid():
    prog = _jacobi_prog()
    cands = enumerate_candidates(prog)
    assert cands[0].origin == "baseline"
    fps = [c.fingerprint for c in cands]
    assert len(fps) == len(set(fps)), "duplicate candidates"
    for c in cands[:6]:  # spot-check: every offered candidate validates
        api._validate_for_program(prog, c.target)


def test_enumerate_emits_fused_epoch_candidates():
    prog = _jacobi_prog()
    cands = enumerate_candidates(prog)
    fused = [c for c in cands if c.target.fused_epoch]
    assert fused, "no fused_epoch candidates offered"
    for c in fused:
        assert c.target.backend == "pallas"
        assert not c.target.overlap  # fused ⊥ overlap
        assert "fused" in c.describe()
    # the axis can be switched off
    none_fused = enumerate_candidates(prog, fused_epoch=(False,))
    assert not any(c.target.fused_epoch for c in none_fused)


def test_enumerate_interpret_follows_inventory():
    import jax

    from repro.tune.space import pallas_interpret_candidates

    # CPU-only inventory (the CI machine): interpret resolves to the
    # default; an accelerator inventory would enumerate the native path
    devs = jax.devices()
    if any(d.platform in ("gpu", "tpu") for d in devs):
        assert pallas_interpret_candidates(devs) == [False]
    else:
        assert pallas_interpret_candidates(devs) == [None]

    class _FakeGPU:
        platform = "gpu"

    assert pallas_interpret_candidates([_FakeGPU()]) == [False]


# -------------------------------------------------------------------------
# cost-model-only tuning + the persistent cache (acceptance)
# -------------------------------------------------------------------------


def test_tuned_cost_model_only_winner_and_cache(tune_dir):
    prog = _jacobi_prog(name="tune_cost_only")
    res = tune(prog, measure=False, device_kind=V5E)
    assert not res.from_cache
    assert cache_stats().misses == 1 and cache_stats().stores == 1

    # the winner is a *validated* Target: it compiles
    compiled = api.compile(prog, res.target)
    assert compiled.target.fingerprint == res.target.fingerprint

    # winner's modeled step_time ≤ every unpruned candidate's
    unpruned = [c for c in res.candidates if not c.pruned]
    assert unpruned and res.winner in unpruned
    assert all(
        res.winner.modeled_s <= c.modeled_s for c in unpruned
    ), [(c.describe(), c.modeled_s) for c in unpruned]

    # second call: persistent-cache hit with the identical winner
    res2 = tune(prog, measure=False, device_kind=V5E)
    assert res2.from_cache
    assert cache_stats().hits == 1
    assert res2.target.fingerprint == res.target.fingerprint
    assert os.path.exists(res2.cache_path)

    # Target.tuned surfaces the same winner (third call, second hit)
    t = Target.tuned(prog, measure=False, device_kind=V5E)
    assert t.fingerprint == res.target.fingerprint
    assert cache_stats().hits == 2


def test_compile_tune_kwarg(tune_dir):
    prog = _jacobi_prog(name="tune_compile_kwarg")
    step = api.compile(prog, tune={"measure": False, "device_kind": V5E})
    assert isinstance(step, api.CompiledStencil)
    with pytest.raises(ValueError, match="not both"):
        api.compile(prog, Target(), tune={"measure": False, "device_kind": V5E})
    # tuned target round-trips through the compile cache
    again = api.compile(prog, tune={"measure": False, "device_kind": V5E})
    assert again is step


def test_tune_measure_single_device(tune_dir):
    prog = _jacobi_prog((16, 16), name="tune_measured")
    res = tune(
        prog, measure=True, steps=4, trials=2, warmup=1,
        backends=("jnp",), exchange_every=(1, 2), device_kind=V5E,
    )
    measured = [c for c in res.candidates if c.measured_s is not None]
    assert measured and res.winner in measured
    assert all(res.winner.measured_s <= c.measured_s for c in measured)
    # pruned candidates were never measured
    assert all(c.measured_s is None for c in res.candidates if c.pruned)
    # measurement protocol: per-step normalization keeps epochs comparable
    compiled = api.compile(prog, res.target)
    t = measure_compiled(compiled, steps=2, trials=1, warmup=1)
    assert t > 0.0 and math.isfinite(t)


def test_single_device_model_has_no_phantom_latency(tune_dir):
    # a non-distributed artifact's exchanges are local rolls — no ICI
    # messages, so the modeled score must not reward deep epochs with
    # latency amortization that cannot happen; the modeled winner on one
    # device keeps one exchange per step
    prog = _jacobi_prog(name="tune_no_phantom")
    res = tune(prog, ranks=1, measure=False, device_kind=V5E)
    assert res.target.exchange_every == 1, res.winner.describe()


def test_tune_raises_informatively_when_nothing_models(tune_dir, monkeypatch):
    prog = _jacobi_prog(name="tune_all_fail")

    def boom(*a, **k):
        raise RuntimeError("backend exploded")

    monkeypatch.setattr(api, "compile", boom)
    with pytest.raises(RuntimeError, match="no candidate .* could be modeled"):
        tune(prog, measure=False, cache=False, device_kind=V5E)


def test_measurement_protocol_changes_cache_key(tune_dir):
    # steps/trials/warmup are part of the options digest: a
    # higher-fidelity search must not read back a low-fidelity entry
    prog = _jacobi_prog((16, 16), name="tune_protocol")
    kw = dict(measure=True, backends=("jnp",), exchange_every=(1,),
              device_kind=V5E)
    r1 = tune(prog, steps=2, trials=1, warmup=1, **kw)
    r2 = tune(prog, steps=4, trials=2, warmup=1, **kw)
    assert r1.cache_key != r2.cache_key
    assert not r2.from_cache


def test_tune_result_table_prints(tune_dir):
    prog = _jacobi_prog(name="tune_table")
    res = tune(prog, measure=False, device_kind=V5E)
    text = res.table(top=5)
    assert "candidate" in text and "modeled/step" in text
    assert "baseline" in res.table()


# -------------------------------------------------------------------------
# cache internals
# -------------------------------------------------------------------------


def test_target_dict_roundtrip_fingerprint():
    t = Target(backend="pallas", pallas_tile=(8, 16), exchange_every=2,
               overlap=True)
    d = target_to_dict(t)
    back = target_from_dict(d)
    assert back.fingerprint == t.fingerprint == d["fingerprint"]
    assert back.pallas_tile == (8, 16) and back.exchange_every == 2


def test_target_dict_roundtrips_fused_epoch():
    t = Target(backend="pallas", exchange_every=4, fused_epoch=True,
               pallas_interpret=True)
    d = target_to_dict(t)
    assert d["fused_epoch"] is True and d["pallas_interpret"] is True
    back = target_from_dict(d)
    assert back.fused_epoch and back.fingerprint == t.fingerprint
    # a pre-fused_epoch (schema v1) winner dict rebuilt under v2 defaults
    # to unfused rather than erroring
    legacy = {k: v for k, v in d.items()
              if k not in ("fused_epoch", "pallas_interpret")}
    old = target_from_dict(legacy)
    assert not old.fused_epoch
    assert old.fingerprint != t.fingerprint


def test_cache_schema_and_corruption_are_misses(tune_dir):
    key = tune_cache.cache_key("fp", "hw", 1, "opts")
    assert tune_cache.load(key) is None  # cold
    tune_cache.store(key, {"winner": {}})
    assert tune_cache.load(key) is not None
    # corrupt file → miss, not an exception
    with open(tune_cache.entry_path(key), "w") as f:
        f.write("{not json")
    assert tune_cache.load(key) is None
    # schema drift → miss
    with open(tune_cache.entry_path(key), "w") as f:
        json.dump({"schema": tune_cache.SCHEMA_VERSION + 1}, f)
    assert tune_cache.load(key) is None


def test_cache_key_separates_programs_hardware_ranks():
    k = tune_cache.cache_key
    assert k("a", "hw", 1, "o") != k("b", "hw", 1, "o")
    assert k("a", "hw", 1, "o") != k("a", "hw2", 1, "o")
    assert k("a", "hw", 1, "o") != k("a", "hw", 2, "o")
    assert k("a", "hw", 1, "o") != k("a", "hw", 1, "o2")


def test_stale_cache_entry_for_other_program_misses(tune_dir):
    # an entry whose winner no longer validates for the program reads as
    # a miss (fresh search), never as a wrong answer
    prog = _jacobi_prog(name="tune_stale")
    res = tune(prog, measure=False, device_kind=V5E)
    with open(res.cache_path) as f:
        entry = json.load(f)
    entry["winner"]["strategy"] = {"grid": [5], "axes": ["x"], "dims": [0]}
    entry["winner"]["mesh"] = None
    with open(res.cache_path, "w") as f:
        json.dump(entry, f)
    reset_cache_stats()
    res2 = tune(prog, measure=False, device_kind=V5E)
    assert not res2.from_cache  # fingerprint/validation rejected the entry
    # the rejected load is counted as a miss, not a hit: the search ran
    assert cache_stats().hits == 0 and cache_stats().misses == 1, (
        cache_stats().as_dict()
    )


# -------------------------------------------------------------------------
# RooflineTerms edge cases (satellite)
# -------------------------------------------------------------------------


def _terms(**kw):
    base = dict(
        flops=1e6, bytes_accessed=1e5, collectives={},
        exchange_every=1, messages_per_epoch=8,
        step_halo=(1, 1), local_shape=(64, 64), device_kind=V5E,
    )
    base.update(kw)
    return RooflineTerms(**base)


def test_recommend_clamps_to_max_k():
    lat = _terms(local_shape=(256, 256))  # latency-dominated: deeper is better
    assert lat.recommend_exchange_every(max_k=8) > 2
    assert lat.recommend_exchange_every(max_k=2) <= 2
    assert lat.recommend_exchange_every(max_k=1) == 1


def test_recommend_returns_1_when_no_latency():
    # t_latency == 0 (no messages): amortization buys nothing, redundant
    # compute only costs — k=1 must win
    quiet = _terms(messages_per_epoch=0)
    assert quiet.t_latency == 0.0
    assert quiet.recommend_exchange_every(max_k=8) == 1
    # no halo at all: terms unavailable → 1
    assert _terms(step_halo=(0, 0)).recommend_exchange_every() == 1
    assert _terms(step_halo=(), local_shape=()).recommend_exchange_every() == 1


def test_recommend_skips_infeasible_k():
    tiny = _terms(local_shape=(4, 4), step_halo=(1, 1))
    assert not tiny.feasible_exchange_every(8)  # deep halo 8 > shard 4
    ranked = tiny.ranked_exchange_every(max_k=8)
    assert all(k <= 4 for k, _ in ranked)
    assert tiny.recommend_exchange_every(max_k=8) <= 4


def test_step_time_monotone_pieces():
    t = _terms()
    # redundant-compute factor: 1.0 at k=1, nondecreasing in k
    rcf = [t.redundant_compute_factor(k) for k in (1, 2, 4, 8)]
    assert rcf[0] == 1.0
    assert all(a <= b for a, b in zip(rcf, rcf[1:]))
    assert rcf[-1] > 1.0
    # latency piece: with a huge shard (rcf ≈ 1) step_time strictly
    # decreases with k — pure 1/k amortization
    lat = _terms(local_shape=(10_000, 10_000))
    times = [lat.step_time(k) for k in (1, 2, 4, 8)]
    assert all(a > b for a, b in zip(times, times[1:]))
    # with no messages, step_time is nondecreasing in k (redundant
    # compute only)
    quiet = _terms(messages_per_epoch=0)
    times = [quiet.step_time(k) for k in (1, 2, 4, 8)]
    assert all(a <= b for a, b in zip(times, times[1:]))


def test_ranked_exchange_every_best_first():
    t = _terms(local_shape=(256, 256))
    ranked = t.ranked_exchange_every(max_k=8)
    assert ranked[0][0] == t.recommend_exchange_every(max_k=8)
    times = [s for _, s in ranked]
    assert times == sorted(times)
    assert 1 in [k for k, _ in ranked]


# -------------------------------------------------------------------------
# pallas_tile compile-time validation (satellite)
# -------------------------------------------------------------------------


def test_pallas_tile_good_compiles():
    prog = _jacobi_prog((32, 32), name="tile_ok")
    step = api.compile(prog, Target(backend="pallas", pallas_tile=(16, 32)))
    u0 = np.random.default_rng(0).standard_normal((32, 32)).astype(np.float32)
    out = step(u0, np.zeros_like(u0))
    assert np.isfinite(np.asarray(out[0])).all()


def test_pallas_tile_wrong_rank_rejected():
    prog = _jacobi_prog((32, 32), name="tile_rank")
    with pytest.raises(TargetError, match=r"pallas_tile .* rank-2"):
        api.compile(prog, Target(backend="pallas", pallas_tile=(16,)))


def test_pallas_tile_nondividing_rejected_with_names():
    prog = _jacobi_prog((32, 32), name="tile_bad")
    with pytest.raises(TargetError) as e:
        api.compile(prog, Target(backend="pallas", pallas_tile=(7, 32)))
    msg = str(e.value)
    assert "(7, 32)" in msg            # the tile
    assert "(32, 32)" in msg           # the local shard shape
    assert "undecomposed" in msg       # the (non-)mesh axis
    assert "tile_bad" in msg


def test_pallas_tile_nonpositive_rejected():
    prog = _jacobi_prog((32, 32), name="tile_zero")
    with pytest.raises(TargetError, match="positive"):
        api.compile(prog, Target(backend="pallas", pallas_tile=(0, 32)))


def test_pallas_tile_auto_retiled_paths_accepted():
    # overlap and temporal-tile split applies re-tile automatically: a
    # shard-nondividing tile must stay accepted there (lowering falls
    # back), while the rank check still applies
    prog = _jacobi_prog((32, 32), name="tile_auto")
    t = Target(backend="pallas", pallas_tile=(7, 32), overlap=True)
    api._validate_for_program(prog, t)  # no raise
    t2 = Target(backend="pallas", pallas_tile=(7, 32), exchange_every=2)
    api._validate_for_program(prog, t2)  # no raise
    with pytest.raises(TargetError, match="rank-2"):
        api._validate_for_program(
            prog, Target(backend="pallas", pallas_tile=(7,), overlap=True)
        )


def test_jnp_backend_ignores_tile_shape():
    # pallas_tile is a pallas knob; the jnp backend never reads it and
    # validation must not reject it there
    prog = _jacobi_prog((32, 32), name="tile_jnp")
    api._validate_for_program(
        prog, Target(backend="jnp", pallas_tile=(7, 5))
    )


# -------------------------------------------------------------------------
# ISSUE 9 — slot-pool width enumeration (ensemble axis)
# -------------------------------------------------------------------------


def test_slot_width_candidates_divide_capacity_and_fit_inventory():
    from repro.tune.space import slot_width_candidates

    assert slot_width_candidates(8, 2, 4) == [4, 2, 1]
    assert slot_width_candidates(8, 4, 6) == [2, 1]  # 6 devices short of 3×4
    assert slot_width_candidates(8, 2, 6) == [3, 2, 1]  # 4 ∤ 6 dropped
    assert slot_width_candidates(1, 1, 4) == [1]  # single device still pools
    for s in slot_width_candidates(16, 2, 12):
        assert 12 % s == 0 and s * 2 <= 16


def test_enumerate_pool_candidates_single_device():
    """On a 1-device inventory the pool space degenerates to the
    pure-ensemble slot-axis candidate (trivial spatial grid at width 1)
    — still a valid, compilable slot-axis Target."""
    from repro.tune.space import enumerate_pool_candidates

    prog = _jacobi_prog(name="tune_pool_1dev")
    cands = enumerate_pool_candidates(prog, capacity=4)
    assert cands, "always at least the width-1 pool"
    for c in cands:
        assert c.origin == "pool"
        assert c.target.slot_axis == "slot"
        assert "slot" in c.target.mesh.axis_names
        assert c.note.startswith("slots=")
    # fingerprints are unique and differ from the solo target's
    fps = [c.fingerprint for c in cands]
    assert len(fps) == len(set(fps))
    assert Target().fingerprint not in fps


# -------------------------------------------------------------------------
# dropped candidates and the peaks table
# -------------------------------------------------------------------------


def test_tune_records_dropped_candidates(tune_dir, monkeypatch):
    """A candidate whose measurement raises is dropped, and the result
    names it with its error instead of hiding it."""
    from repro.tune import search

    real = search.tune_measure.measure_compiled

    def pallas_fails(compiled, **kw):
        if compiled.target.backend == "pallas":
            raise RuntimeError("kernel exploded")
        return real(compiled, **kw)

    monkeypatch.setattr(search.tune_measure, "measure_compiled", pallas_fails)
    prog = _jacobi_prog((16, 16), name="tune_dropped")
    res = tune(
        prog, measure=True, steps=2, trials=1, warmup=1, cache=False,
        keep_quantile=1.0, exchange_every=(1,), fused_epoch=(False,),
        overlap=(False,), device_kind=V5E,
    )
    assert res.dropped
    for d in res.dropped:
        assert "backend=pallas" in d["candidate"]
        assert d["error"] == "measurement: RuntimeError: kernel exploded"
    assert res.target.backend == "jnp"


def test_device_peaks_keyed_by_kind():
    from repro.launch.roofline import PEAKS, device_peaks

    v5e = device_peaks(V5E)
    assert (v5e.flops, v5e.hbm_bytes_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source and set(PEAKS) == {V5E}
    with pytest.raises(ValueError, match="'cpu'"):
        device_peaks("cpu")
    # structural terms need no device; seconds do
    terms = _terms(device_kind=None)
    assert terms.feasible_exchange_every(2)
    with pytest.raises(ValueError, match="None"):
        terms.t_memory

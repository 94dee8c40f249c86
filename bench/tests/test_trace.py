"""The trace reduction: busy union, idle gaps and their labels, exposed
collective time — on hand-made intervals, and on small traces recorded
on the chip and kept in ``data/``."""
import glob
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, spans, window=(0, 100)):
    return tr.Trace(window=window, ops=ops, spans=spans)


def test_merge_clips_and_unions():
    assert tr.merge([(5, 10), (8, 12), (20, 30), (-5, 2), (95, 120)], 0, 100) == [
        [0, 2], [5, 12], [20, 30], [95, 100]]
    assert tr.merge([(10, 10), (200, 300)], 0, 100) == []


def test_gaps_are_the_complement():
    merged = [[0, 2], [5, 12], [20, 30]]
    assert tr.gaps(merged, 0, 40) == [(2, 5), (12, 20), (30, 40)]
    assert tr.length(merged) + sum(e - s for s, e in tr.gaps(merged, 0, 40)) == 40


def test_subtract():
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == 7
    assert tr.subtract([[0, 10], [20, 30]], [[5, 25]]) == 10
    assert tr.subtract([[0, 10]], []) == 10
    assert tr.subtract([[0, 10]], [[0, 10]]) == 0


def test_busy_and_idle_gaps_labelled_by_host_span():
    ops = {"/device:TPU:0": [("fusion.1", 10, 40), ("copy.2", 30, 50), ("fusion.1", 70, 90)]}
    spans = [("bench.window", 0, 100), ("loop.call", 5, 60), ("loop.call", 60, 75)]
    t = _trace(ops, spans)
    assert tr.busy_ns(t) == {"/device:TPU:0": 60}
    gaps = tr.idle_gaps(t)
    assert gaps == [["loop.call", 20e-9], ["bench.window", 10e-9], ["loop.call", 10e-9]]
    assert tr.top_ops(t) == [["fusion.1", 50e-9], ["copy.2", 20e-9]]


def test_exposed_collective_per_device():
    ops = {
        "/device:TPU:0": [("fusion.1", 0, 50), ("collective-permute-start.1", 40, 60),
                          ("collective-permute-done.1", 60, 65)],
        "/device:TPU:1": [("fusion.1", 0, 70), ("collective-permute-done.1", 60, 65)],
        "/device:TPU:2": [("fusion.1", 0, 70)],
    }
    t = _trace(ops, [("bench.window", 0, 100)])
    assert tr.exposed_ns(t) == {"/device:TPU:0": 15, "/device:TPU:1": 0}


def _recorded(pattern):
    paths = sorted(glob.glob(os.path.join(DATA, pattern)))
    if not paths:
        pytest.fail(f"no recorded trace {pattern} in {DATA}")
    return tr.load(paths[0], ("bench.window", "loop.call"))


def test_recorded_one_chip_trace():
    """Three 8-step ``time_loop`` calls of 1024² heat on one TPU v5 lite,
    each a ``loop.call`` span inside ``bench.window``."""
    t = _recorded("heat1024-1chip.xplane.pb")
    assert list(t.ops) == ["/device:TPU:0"]
    assert sum(1 for name, _, _ in t.spans if name == "loop.call") == 3
    names = {name for name, _, _ in t.ops["/device:TPU:0"]}
    assert not any(n.startswith("%while") for n in names)   # control flow left out
    assert all(" = " not in n for n in names)                # instruction names only
    busy = tr.busy_ns(t)["/device:TPU:0"]
    assert tr.window_ns(t) == 3807950.0
    assert busy == 905705.0
    lo, hi = t.window
    merged = tr.merge([(s, e) for _, s, e in t.ops["/device:TPU:0"]], lo, hi)
    idle = sum(e - s for s, e in tr.gaps(merged, lo, hi))
    assert busy + idle == pytest.approx(tr.window_ns(t))
    top = tr.top_ops(t)
    assert top[0][0] == "%fusion.20"                          # the stencil fusion
    assert top[0][1] == pytest.approx(530258e-9)
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["loop.call", pytest.approx(1770779e-9)]
    assert tr.exposed_ns(t) == {}                              # one chip: no collective

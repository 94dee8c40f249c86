"""The XSpace decoder and the readings by IR scope: on hand-made bytes,
and on traces recorded on the chip (``data/``; ``record_trace.py``)."""
import os

import pytest

from bench import registry, scoped, xspace
from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = ("bench.window", "loop.call")


def _varint(n: int) -> bytes:
    out = b""
    while True:
        byte, n = n & 0x7F, n >> 7
        out += bytes([byte | (0x80 if n else 0)])
        if not n:
            return out


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, value: bytes) -> bytes:
    return _field(1, key) + _field(2, value)


def _space() -> bytes:
    """One device plane: ``%pad.1`` with ``tf_op`` as a string, ``%copy.2``
    with it as a reference to a stat metadata's name, ``%fusion.3`` with
    no stat; and one host plane."""
    stat_meta = (_field(5, _entry(1, _field(1, 1) + _field(2, "tf_op")))
                 + _field(5, _entry(9, _field(1, 9) + _field(2, "a/comm.wait/x:"))))
    pad = _field(1, 1) + _field(2, "%pad.1 = f32[8] pad(...)") + _field(
        5, _field(1, 1) + _field(5, "jit(s.step)/comm.halo_pad/jit(_pad)/pad:"))
    copy = _field(1, 2) + _field(2, "%copy.2 = f32[8] copy(...)") + _field(
        5, _field(1, 1) + _field(7, 9))
    fusion = _field(1, 3) + _field(2, "%fusion.3 = f32[8] fusion(...)")
    device = (_field(2, "/device:TPU:0") + _field(3, b"\x08\x01")
              + _field(4, _entry(1, pad)) + _field(4, _entry(2, copy))
              + _field(4, _entry(3, fusion)) + stat_meta)
    host = _field(2, "/host:CPU") + _field(3, b"\x08\x02")
    return _field(1, device) + _field(1, host)


def test_decoder_reads_string_and_reference_stats():
    planes = xspace.event_stats(_space())
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    events = planes["/device:TPU:0"]
    assert events["%pad.1 = f32[8] pad(...)"] == {
        "tf_op": "jit(s.step)/comm.halo_pad/jit(_pad)/pad:"}
    assert events["%copy.2 = f32[8] copy(...)"] == {"tf_op": "a/comm.wait/x:"}
    assert events["%fusion.3 = f32[8] fusion(...)"] == {}
    only = xspace.event_stats(_space(), lambda name: name.startswith("/host"))
    assert only == {"/host:CPU": {}}


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(f)/while/body/closed_call/jit(h.step)/comm.halo_pad/jit(_pad)/pad:", "comm.halo_pad"),
    ("jit(h.step)/shard_map/comm.exchange_start/ppermute:", "comm.exchange_start"),
    ("jit(h.step)/stencil.apply/stencil.apply/while/body/add:", "stencil.apply"),
    ("jit(h.step)/stencil.apply.interior/add:", "stencil.apply.interior"),
    ("jit(f)/while/body/closed_call/jit(<unknown>)/jit(_pad)/pad:", None),
    ("", None),
])
def test_innermost_scope(tf_op, scope):
    assert xspace.innermost_scope(tf_op) == scope


def _load(name):
    path = os.path.join(DATA, name)
    if not os.path.isfile(path):
        pytest.fail(f"no recorded trace {name} in {DATA}")
    return path, tr.load(path, SPANS)


def test_trace_without_scopes_reads_nothing():
    """The trace recorded before the program named its ops: every op's
    ``tf_op`` path holds no IR scope, so no scoped reading is made."""
    path, t = _load("heat1024-1chip.xplane.pb")
    scopes = xspace.scopes(path)
    assert set(scopes) == set(t.ops)
    ops = scopes["/device:TPU:0"]
    assert "%pad.5" in ops and "%fusion.20" in ops
    assert set(ops.values()) == {None}
    assert scoped.halo_pad_share(t, scopes) is None
    assert scoped.unscoped_device_share(t, scopes) is None


def test_accepted_metrics_read_as_before_on_the_old_trace():
    """The four accepted per-layer metrics, pinned on the trace they were
    written against."""
    _, t = _load("heat1024-1chip.xplane.pb")
    record = {"trace": t, "device_kind": "TPU v5 lite", "chips": 1,
              "least_bytes": 3 * 8 * 2 * 4 * 1024**2,
              "compile_host_s": 0.5, "first_call_s": 2.0, "steady_call_s": 0.25}

    def read(metric):
        return registry.reader("layers", metric).read(record)

    assert read("compile_s") == 2.25
    assert read("device_idle_share") == 76.21541774445568
    assert read("stencil_step_roofline") == 27.1412893438829
    assert read("exchange_exposed_share") is None


def test_one_chip_trace_names_each_op_by_its_ir_scope():
    """Three 8-step calls of 1024² heat on one TPU v5 lite, recorded with
    the program's scopes: the stencil fusion lies under
    ``stencil.apply``, the pad under ``comm.halo_pad``, the halo writes
    under ``comm.wait``; the copies of the loop state carry no scope."""
    path, t = _load("heat1024-1chip-scoped.xplane.pb")
    with open(path, "rb") as f:
        tf_op = xspace.event_stats(f.read())["/device:TPU:0"]
    (pad,) = [s["tf_op"] for name, s in tf_op.items() if name.startswith("%pad.5 ")]
    assert pad.startswith("jit(<lambda>)/while/body/closed_call/"
                          "jit(devito_op.step)/comm.halo_pad/")
    ops = xspace.scopes(path)["/device:TPU:0"]
    assert ops["%fusion.20"] == "stencil.apply"
    assert ops["%pad.5"] == "comm.halo_pad"
    assert {ops[f"%dynamic_update_slice.{i}"] for i in range(16, 20)} == {"comm.wait"}
    assert ops["%copy-start"] is ops["%copy-done"] is None
    assert set(t.ops["/device:TPU:0"][i][0] for i in range(3)) <= set(ops)


def test_one_chip_scoped_readings():
    path, t = _load("heat1024-1chip-scoped.xplane.pb")
    scopes = xspace.scopes(path)
    record = {"device_kind": "TPU v5 lite", "chips": 1,
              "least_bytes": 3 * 8 * 2 * 4 * 1024**2}
    assert scoped.halo_pad_share(t, scopes) == pytest.approx(5.130019595396462)
    assert scoped.unscoped_device_share(t, scopes) == pytest.approx(2.906521679132283)
    assert scoped.stencil_kernel_roofline(record, t, scopes) == pytest.approx(
        46.35830556122639)
    # the scoped times and the unscoped one add up to the busy time
    parts = [scoped.scoped_ns(t, scopes, keep)["/device:TPU:0"] for keep in (
        lambda s: s is None, lambda s: s is not None)]
    assert sum(parts) == pytest.approx(tr.busy_ns(t)["/device:TPU:0"])


def test_two_by_two_trace_names_the_exchange():
    """Three 8-step calls of 2048² heat on the 2x2 host: on every chip
    each collective-permute (start and done) lies under
    ``comm.exchange_start`` and each halo write under ``comm.wait``."""
    path, t = _load("heat2048-2x2-scoped.xplane.pb")
    scopes = xspace.scopes(path)
    assert sorted(scopes) == sorted(t.ops) == [f"/device:TPU:{i}" for i in range(4)]
    for ops in scopes.values():
        permutes = [s for n, s in ops.items() if n.startswith("%collective-permute")]
        writes = [s for n, s in ops.items() if n.startswith("%dynamic_update_slice")]
        assert len(permutes) == 8 and set(permutes) == {"comm.exchange_start"}
        assert len(writes) == 4 and set(writes) == {"comm.wait"}
        assert ops["%pad.6"] == "comm.halo_pad"
        assert ops["%fusion.26"] == "stencil.apply"


def test_two_by_two_scoped_readings():
    path, t = _load("heat2048-2x2-scoped.xplane.pb")
    scopes = xspace.scopes(path)
    record = {"device_kind": "TPU v5 lite", "chips": 4,
              "least_bytes": 3 * 8 * 2 * 4 * 2048**2}
    assert scoped.halo_pad_share(t, scopes) == pytest.approx(4.264040143782474)
    assert scoped.unscoped_device_share(t, scopes) == pytest.approx(2.2782314546327176)
    assert scoped.stencil_kernel_roofline(record, t, scopes) == pytest.approx(
        46.358895690513485)

"""The traffic driver through the whole harness, at a tiny size on the
CPU: the run is correct and reports its cell's metrics."""
import jax

from bench import registry
from bench.tests import tiny

CELL = "heat2d-16384.loop"


def test_run_is_correct_and_reports_its_metrics():
    result = tiny.execute(CELL)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["compiles_in_window"] == 0
    want = {m["name"] for m in registry.metrics_for(CELL, registry.spec()["end_to_end"])}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"


def test_traced_run_on_the_cpu_leaves_device_metrics_out():
    """On the CPU there is no device plane: the metrics read from it are
    left out, the host's are read."""
    result = tiny.execute(CELL, trace=True)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"compile_s"}
    assert result["metrics"]["compile_s"]["value"] > 0
    assert "busy_s" not in result["device"] and "breakdown" not in result


def test_the_state_goes_on_from_call_to_call():
    """Each call starts from the state the last one returned: the answer
    checked is the window's last call, from the state it was given."""
    cfg = tiny.config(CELL)
    driver = registry.code("drivers", "loop").Driver(
        cfg, registry.data("traffic", "loop-64"), registry.code("equations", "heat"),
        jax.devices())
    driver.prepare(11)
    warm = driver.state
    record = driver.window(0.5, jax.profiler.TraceAnnotation)
    (answer,) = driver.answers()
    assert record["attempted"] >= 1
    assert answer.steps == 64
    if record["attempted"] == 1:
        assert answer.inputs is warm
    assert answer.got is driver.state[-1]

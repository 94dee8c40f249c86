import pytest

from bench import registry


def test_every_cell_resolves_to_its_files():
    spec = registry.spec()
    for cell in spec["workloads"]:
        config = registry.data("configs", cell["config"])
        traffic = registry.data("traffic", cell["traffic"])
        limits = registry.data("cells", cell["name"])["limits"]
        assert config["chips"] == cell["chips"]
        assert registry.code("drivers", traffic["driver"]).Driver
        assert registry.code("equations", config["equation"]).program
        assert limits["rel_err"] > 0
    for entry in spec["configs"]:
        assert entry["file"] == f"bench/configs/{entry['name']}.json"
        assert registry.data("configs", entry["name"])["reduced"] == entry["reduced"]


def test_every_metric_has_a_reader():
    spec = registry.spec()
    for m in spec["end_to_end"]:
        assert callable(registry.reader("e2e", m["name"]).read)
    for m in spec["per_layer"]:
        assert callable(registry.reader("layers", m["name"]).read)


def test_a_split_metric_is_read_by_its_base():
    assert registry.reader("e2e", "gpts.serve") is not None
    assert registry.reader("layers", "device_idle_share.serve") is not None
    with pytest.raises(registry.UnknownName):
        registry.reader("layers", "no_such_metric.serve")


@pytest.mark.parametrize("kind,name", [
    ("configs", "no-such-config"),
    ("traffic", "no-such-mix"),
    ("cells", "no-such-cell"),
    ("configs", "../BENCHMARK"),
    ("configs", "a/b"),
    ("configs", ""),
    ("nosuchkind", "loop-64"),
])
def test_unknown_data_names_are_refused(kind, name):
    with pytest.raises(registry.UnknownName):
        registry.data(kind, name)


@pytest.mark.parametrize("kind,name", [
    ("drivers", "no_such_driver"),
    ("layers", "no_such_metric"),
    ("e2e", "../run"),
    ("equations", "wave3d"),
])
def test_unknown_code_names_are_refused(kind, name):
    with pytest.raises(registry.UnknownName):
        registry.code(kind, name)


def test_unknown_workload_is_refused():
    with pytest.raises(registry.UnknownName):
        registry.workload("heat2d-1.none", registry.spec())


def test_metrics_for_follows_workloads_lists():
    entries = [{"name": "a"}, {"name": "b", "workloads": ["x"]}]
    assert [m["name"] for m in registry.metrics_for("x", entries)] == ["a", "b"]
    assert [m["name"] for m in registry.metrics_for("y", entries)] == ["a"]


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    spec = registry.spec()
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for cell in spec["workloads"]:
        mine = {m["name"] for m in registry.metrics_for(cell["name"], spec["end_to_end"])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = registry.metrics_for(cell["name"], spec["per_layer"])
        assert layers
        for m in layers:   # what a metric moves is reported in each of its cells
            assert m["moves"] in mine, (cell["name"], m["name"])
            assert m["moves"] in e2e

"""BENCHMARK.json and the files it names: every name resolves to its file
and keeps to the naming rules. No TPU library is loaded."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import correct, flops, spec  # noqa: E402

BENCH = spec.benchmark()
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_benchmark_keys_and_command():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_name_resolves_and_is_well_formed():
    assert spec.problems(BENCH) == []


def test_every_cell_loads_by_name():
    for w in BENCH["workloads"]:
        cell = spec.Cell(BENCH, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.limits and set(cell.limits) <= set(correct.NUMBERS)
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
        assert cell.per_layer, w["name"]


def test_config_layers_match_the_parameter_count():
    for c in BENCH["configs"]:
        config = spec.load_json(spec.config_path(c["name"]))
        counter = flops.for_family(config)
        assert counter is not None, c["name"]
        assert counter.param_count(config) == config["params"], c["name"]
        assert config["reduced"] == c["reduced"]


def test_per_layer_metrics_name_a_layer_and_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["layer"] and "\n" not in m["layer"]
        assert set(m["workloads"]) <= cells


def test_bad_names_are_reported():
    bad = dict(BENCH, per_layer=[dict(BENCH["per_layer"][0],
                                      name="no space", unit="per second")])
    found = spec.problems(bad)
    assert any("bad name 'no space'" in p for p in found)
    assert any("bad unit 'per second'" in p for p in found)


ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_entries_have_just_their_keys_and_short_texts():
    for group, keys in ENTRY_KEYS.items():
        for e in BENCH[group]:
            extra = {"workloads"} if group in ("end_to_end", "per_layer") \
                else set()
            assert keys <= set(e) <= keys | extra, (group, e["name"])
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200, (group, e["name"], k)
                    assert "\n" not in e[k] and "\t" not in e[k]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(BENCH)) <= 64 * 1024

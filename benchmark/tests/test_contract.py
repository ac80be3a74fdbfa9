"""``BENCHMARK.json`` against the parts of its contract that can be read
without a run, and the files its names point to."""

import re

import pytest

from benchmark import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_keys_names_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), m
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s to compile and
    # 1200 s spare have to fit into 43200 s
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 180 + 1200 <= 43200


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if cell in _cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in _cells_of(m) for m in BENCH["per_layer"]), cell


def test_a_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert _cells_of(m) <= _cells_of(e2e[m["moves"]]), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_files_a_cell_names_exist(cell):
    c = harness.Cell(cell)
    assert c.kind in ("train", "serve") and c.kind in c.config
    harness.resolve(f"benchmark.runners.{c.kind}:run")
    for metric, spec in c.per_layer:
        assert callable(harness.resolve(spec["reader"])), metric["name"]
    cfg = next(x for x in BENCH["configs"] if x["name"] == c.entry["config"])
    assert c.config["reduced"] == cfg["reduced"]


def test_layers_are_spelt_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len(layers) == 8, sorted(layers)


def test_lines_are_short_and_on_one_line():
    texts = [w["why"] for w in BENCH["workloads"] + BENCH["configs"]] + \
        [c["source"] for c in BENCH["configs"]] + \
        [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert (harness.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024

"""A later PR adds a configuration, a mix, a cell and a per-layer metric
as NEW files and new entries in ``BENCHMARK.json``; no file that is
there is edited."""

import json
import shutil
import subprocess
import sys

from benchmark import harness

PROBE = """
import json, sys
sys.path.insert(0, ".")
from benchmark import harness
cell = harness.Cell("toy-cell", root=harness.ROOT)
obs = {"series": {"toy/series": [1.0, 3.0]}, "spans": {}, "counters": {}}
print(json.dumps({
    "root": str(harness.ROOT), "kind": cell.kind, "chips": cell.chips,
    "hidden": cell.config["n_embd"], "rate": cell.traffic["arrivals"]["rate_per_s"],
    "end_to_end": [m["name"] for m in cell.end_to_end()],
    "series": cell.series_names(),
    "per_layer": harness.read_per_layer(cell, obs)}))
"""


def test_new_files_are_found_without_editing_old_ones(tmp_path):
    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    b = tmp_path / "benchmark"
    (b / "configs" / "toy.json").write_text(json.dumps({
        "n_embd": 48, "model": {"factory": "benchmark.factories:gpt2_lm"},
        "serve": {"engine": {"n_slots": 2}}}))
    (b / "traffic" / "toy-mix.json").write_text(json.dumps({
        "kind": "serve", "arrivals": {"process": "uniform",
                                      "rate_per_s": 2.5}}))
    (b / "layer_metrics" / "toy_metric.json").write_text(json.dumps({
        "kinds": ["serve"], "reader": "benchmark.readers.toy:total",
        "args": {"series": "toy/series"}, "series": ["toy/series"]}))
    (b / "readers" / "toy.py").write_text(
        "def total(obs, args):\n"
        "    return sum(obs['series'][args['series']])\n")
    bench["configs"].append({"name": "toy", "source": "none",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-cell", "config": "toy",
                               "traffic": "toy-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("toy-cell")
    bench["per_layer"].append({
        "name": "toy_metric", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "toy",
        "moves": "serve_tokens_per_s", "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp_path,
                         capture_output=True, text=True, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["root"] == str(tmp_path)          # the copy, not the repo
    assert got["kind"] == "serve" and got["chips"] == 1
    assert got["hidden"] == 48 and got["rate"] == 2.5
    assert got["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    assert got["series"] == ["toy/series"]
    # metrics declared for every cell come along; the new one is read
    assert got["per_layer"]["toy_metric"] == {"value": 4.0, "unit": "x"}
    after = {p: p.read_bytes() for p in before}
    assert after == before                       # nothing old was edited


def test_a_reader_that_finds_nothing_is_left_out():
    cell = harness.Cell("gpt2m-train")
    obs = {"series": {}, "spans": {}, "counters": {
        "items_per_s_per_chip": 1000.0}, "config": cell.config,
        "settings": cell.settings, "peaks": {"bf16_flops": 197e12}}
    got = harness.read_per_layer(cell, obs)
    assert "train_mfu" in got
    for absent in ("step_device_ms", "device_idle.train", "iter_wall_ms",
                   "input_wait_ms", "cache_hits"):
        assert absent not in got

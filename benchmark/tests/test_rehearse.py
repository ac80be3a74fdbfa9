"""``--rehearse-cpu`` of both runners at toy size exits 4 and prints no
contract line; without it a CPU is refused before a model is built."""

import os
import subprocess
import sys

import pytest

from benchmark import harness

RUN = [sys.executable, str(harness.HERE / "run.py")]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _contract_lines(stdout):
    return [line for line in stdout.splitlines()
            if line.startswith("{") and '"correct"' in line]


@pytest.mark.parametrize("cell,trace", [("gpt2m-train", "1"),
                                        ("gpt2m-serve-chat", "1")])
def test_rehearsal_exits_4_and_prints_no_result(cell, trace):
    p = subprocess.run(RUN + ["--workload", cell, "--rehearse-cpu",
                              "--seed", "3000000019", "--seconds", "1",
                              "--trace", trace],
                       capture_output=True, text=True, env=ENV,
                       cwd=harness.ROOT, timeout=240)
    assert p.returncode == harness.REHEARSAL_EXIT, p.stderr[-2000:]
    assert not _contract_lines(p.stdout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert lines and all(ln.startswith(harness.REHEARSAL_TAG)
                         for ln in lines)
    assert '"correct": true' in lines[-2], lines[-2]
    assert '"compiled_in_window": 0' in p.stdout


def test_a_cpu_is_refused_before_any_model_is_built():
    p = subprocess.run(RUN + ["--workload", "gpt2m-train", "--seed", "1",
                              "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=ENV,
                       cwd=harness.ROOT, timeout=120)
    assert p.returncode not in (0, harness.REHEARSAL_EXIT)
    assert not _contract_lines(p.stdout)
    assert "not 'tpu'" in p.stderr


def test_an_unknown_cell_is_refused():
    p = subprocess.run(RUN + ["--workload", "no-such-cell"],
                       capture_output=True, text=True, env=ENV,
                       cwd=harness.ROOT, timeout=60)
    assert p.returncode not in (0, harness.REHEARSAL_EXIT)
    assert not _contract_lines(p.stdout)


def test_a_four_chip_data_parallel_cell_is_only_data(tmp_path):
    """``gpt2m-train-dp4`` (PERF.md, Open questions) as a later PR would
    add it: a configuration with a ``parallel`` block and a cell with
    ``chips: 4``, on four virtual CPU devices."""
    import json
    import shutil

    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bigdl_tpu").symlink_to(harness.ROOT / "bigdl_tpu")
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config = harness.load_json(harness.HERE / "configs" / "gpt2-medium.json")
    config["parallel"] = {"dataset": "distributed",
                          "parameter_mode": "partitioned", "compress": "bf16"}
    (tmp_path / "benchmark/configs/gpt2-medium-dp4.json").write_text(
        json.dumps(config))
    bench["configs"].append({
        "name": "gpt2-medium-dp4", "source": "test", "reduced": [],
        "file": "benchmark/configs/gpt2-medium-dp4.json", "why": "test"})
    bench["workloads"].append({
        "name": "gpt2m-train-dp4", "config": "gpt2-medium-dp4",
        "traffic": "lm-1k", "chips": 4, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gpt2m-train" in m.get("workloads", []):
            m["workloads"].append("gpt2m-train-dp4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m-train-dp4",
         "--rehearse-cpu", "--seed", "5", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=240)
    assert p.returncode == harness.REHEARSAL_EXIT, p.stderr[-2000:]
    assert '"count": 4' in p.stdout and '"correct": true' in p.stdout
    assert not _contract_lines(p.stdout)


def test_the_benchmark_alone_is_refused(tmp_path):
    """A directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` has no program to measure."""
    import shutil

    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=ENV, cwd=tmp_path, timeout=60)
    assert p.returncode not in (0, harness.REHEARSAL_EXIT)
    assert not _contract_lines(p.stdout)
    assert "not in this checkout" in p.stderr

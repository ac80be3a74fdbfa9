"""What both runners share: finding a cell's files by name, the device
gate, the peaks table, the lines a run prints and the last line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by the name that
``BENCHMARK.json`` gives it; nothing in this module names a cell.
"""

from __future__ import annotations

import functools
import importlib
import json
import pathlib
import statistics
import types
from typing import NamedTuple

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

REHEARSAL_EXIT = 4
REHEARSAL_TAG = "[cpu-rehearsal, NOT a chip result] "

TAG = ""        # run.py sets it for a rehearsal: every line then says so


def say(*parts) -> None:
    print(TAG + " ".join(str(p) for p in parts), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(spec: str):
    """``"package.module:function"`` -> the function."""
    module, _, name = spec.partition(":")
    return getattr(importlib.import_module(module), name)


def metric_applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _rehearsal_sizes(tree: dict) -> dict:
    """A file's ``rehearsal`` block overrides its keys (one level down
    for nested groups): the toy sizes of ``--rehearse-cpu``."""
    out = dict(tree)
    for key, value in tree.get("rehearsal", {}).items():
        out[key] = {**out[key], **value} \
            if isinstance(value, dict) and isinstance(out.get(key), dict) \
            else value
    return out


class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    def __init__(self, name: str, root: pathlib.Path = ROOT,
                 rehearsal: bool = False) -> None:
        self.bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(root / configs[self.entry["config"]]["file"])
        self.bench_dir = root / self.bench["paths"][0]
        self.traffic = load_json(
            self.bench_dir / "traffic" / f"{self.entry['traffic']}.json")
        if rehearsal:
            self.config = _rehearsal_sizes(self.config)
            self.traffic = _rehearsal_sizes(self.traffic)
        self.kind = self.traffic["kind"]            # "train" or "serve"
        # this kind's settings of the configuration, e.g. config["serve"]
        self.settings = self.config[self.kind]

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if metric_applies(m, self.name)]

    @functools.cached_property
    def per_layer(self):
        """(declared metric, its file under layer_metrics/) for every
        per-layer metric this cell reports."""
        out = []
        for m in self.bench["per_layer"]:
            if not metric_applies(m, self.name):
                continue
            spec = load_json(
                self.bench_dir / "layer_metrics" / f"{m['name']}.json")
            if self.kind not in spec["kinds"]:
                raise SystemExit(
                    f"per-layer metric {m['name']!r} is declared for cell "
                    f"{self.name!r} but its file lists kinds {spec['kinds']}")
            out.append((m, spec))
        return out

    def series_names(self):
        """The program's metric series the cell's readers slice at
        window open and close."""
        return sorted({s for _, spec in self.per_layer
                       for s in spec.get("series", [])})


def device_gate(chips: int, rehearsal: bool):
    """The device as jax reports it, or exit 1: a backend that is not a
    TPU of the peaks table, or fewer chips than the cell asks for, is an
    error before any model is built. No path falls back to the CPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    peaks = load_json(HERE / "peaks.json")
    if rehearsal:
        return info, next(v for k, v in peaks.items() if k[0] != "_")
    if dev.platform != "tpu":
        raise SystemExit(f"FAIL: jax's platform is {dev.platform!r}, not "
                         "'tpu': the benchmark only measures on the chip")
    if dev.device_kind not in peaks:
        raise SystemExit(f"FAIL: device_kind {dev.device_kind!r} is not in "
                         "benchmark/peaks.json: add its published peaks "
                         "with their source before measuring on it")
    if len(devices) < chips:
        raise SystemExit(f"FAIL: the cell needs {chips} chips, jax has "
                         f"{len(devices)}")
    return info, peaks[dev.device_kind]


def memory_peak_bytes(chips: int) -> int:
    """The peak on the fullest chip, at least: the larger of
    ``memory_stats()["peak_bytes_in_use"]`` and the footprint of the
    largest program still loaded (its arguments, outputs that alias no
    argument, and temporaries). ``peak_bytes_in_use`` counts live arrays
    and leaves a program's temporaries out: ResNet-50 training at batch
    256 reads 0.57 GB there while its step holds 9.2 GB of temporaries.
    Both parts are as jax reports them; their maximum never overstates
    what was held."""
    import jax

    devices = jax.local_devices()[:chips]
    live = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    program = 0
    for executable in devices[0].client.live_executables():
        m = executable.get_compiled_memory_stats()
        program = max(program, m.argument_size_in_bytes
                      + m.output_size_in_bytes - m.alias_size_in_bytes
                      + m.temp_size_in_bytes)
    return int(max(live, program))


def seeds_from(seed: int, n: int):
    """``n`` independent 31-bit seeds from any whole number: ``--seed``
    may be larger than 32 signed bits hold, and jax's PRNGKey and
    numpy's RandomState take less."""
    import numpy as np

    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values) -> float:
    return float(statistics.median(values))


def read_per_layer(cell: Cell, obs: dict) -> dict:
    """Run each declared per-layer metric's reader over what the run
    observed. A reader that finds nothing to read returns None and the
    metric is left out of the line."""
    out = {}
    for metric, spec in cell.per_layer:
        value = resolve(spec["reader"])(obs, spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


#: what run.py hands a runner: cell, seed, seconds, trace, rehearsal,
#: t_start, log (CompileLog), device, peaks, trace_dir
Context = types.SimpleNamespace


class Result(NamedTuple):
    """What a runner hands back: the end-to-end values by name, the
    verdict, the observations the per-layer readers read (``obs``), and
    lines of information to print before the last line."""

    end_to_end: dict
    correct: bool
    attempted: int
    failed: int
    obs: dict
    info: list


def observations(ctx, setup_log, **parts) -> dict:
    """What every per-layer reader may read, whatever the runner: the
    cell's files, the peaks, the compile log over set-up, and the
    runner's own ``series``, ``spans`` and ``counters``."""
    programs, hits, _ = setup_log
    cell = ctx.cell
    parts["counters"] = dict(
        parts.get("counters", {}), setup_programs=programs,
        setup_cache_hits=hits, setup_programs_compiled=programs - hits)
    obs = dict(kind=cell.kind, config=cell.config, settings=cell.settings,
               traffic=cell.traffic, peaks=ctx.peaks, chips=cell.chips,
               **parts)
    if ctx.trace:
        from benchmark import trace_reduce

        obs["trace"] = trace_reduce.reduce_dir(ctx.trace_dir, cell.chips)
    return obs


class Window:
    """Snapshots of the compile log and of the program's metric series
    at window open and close: ``CompileLog``, ``Metrics`` and
    ``ServingMetrics`` all count from construction, so "over the window"
    is a slice."""

    def __init__(self, log, metrics, names) -> None:
        self.log, self.metrics, self.names = log, metrics, names
        self.t_open = self.t_close = None

    def _lens(self):
        return {n: len(self.metrics.values(n)) for n in self.names}

    def open(self, now: float) -> None:
        self.t_open = now
        self._programs0 = self.log.snapshot()[0]
        self._lens0 = self._lens()

    def close(self, now: float) -> None:
        self.t_close = now
        self.compiled_inside = self.log.snapshot()[0] - self._programs0
        self.series = {n: self.metrics.values(n)[self._lens0[n]:]
                       for n in self.names}

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

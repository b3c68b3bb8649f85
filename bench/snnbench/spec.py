"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
part is a file of its own under ``bench/``:

* a configuration: the ``file`` its entry names (``bench/configs/<name>.json``);
* a traffic mix: ``bench/traffic/<mix>.json``, parameters that the one
  generator (`traffic.py`) reads;
* a metric's reader: ``bench/metrics/<metric>.py``, or, for a metric split
  by the end-to-end metric it moves (``service_ms.open``), the reader of the
  name before the first dot (``service_ms.py``);
* the peaks: ``bench/peaks.json``, keyed by ``device_kind``.

So a later change adds a configuration, a mix or a metric by adding files
and entries, and edits no file that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    """A cell, file or device that the benchmark cannot resolve."""


def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") \
            from None


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _read_json(os.path.join(root, c["file"]))
    raise SpecError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH) -> dict:
    return _read_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def peaks(device_kind: str, bench_dir: str = BENCH) -> dict:
    table = _read_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def reader_path(metric: str, bench_dir: str = BENCH) -> str:
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(bench_dir, "metrics", f"{stem}.py")
        if os.path.isfile(path):
            return path
    raise SpecError(f"no reader for metric {metric!r} under bench/metrics")


def reader(metric: str, bench_dir: str = BENCH):
    """The ``read(ctx)`` function of a metric's reader file."""
    path = reader_path(metric, bench_dir)
    mod_name = "snnbench_metric_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell_name: str, traced: bool) -> list:
    """The metric entries a cell reports: its end-to-end metrics untraced,
    its per-layer metrics traced."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]

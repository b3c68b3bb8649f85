"""Every cell resolves by name, and a tiny run assembles its last line."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from conftest import BENCH, ROOT, tiny
from snnbench import harness, spec

BENCHMARK = spec.load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = spec.cell(BENCHMARK, name)
    cfg = spec.config(BENCHMARK, cell["config"])
    assert cfg["name"] == cell["config"]
    for key in ("n", "d", "metric", "query_pool", "reduced", "assumed",
                "guarantee", "limits"):
        assert key in cfg
    mix = spec.traffic(cell["traffic"])
    assert mix["loop"] in ("open", "closed")
    for traced in (False, True):
        ms = spec.metrics_for(BENCHMARK, name, traced)
        assert ms, f"{name} reports no {'per-layer' if traced else 'e2e'}"
        for m in ms:
            assert callable(spec.reader(m["name"]))
    e2e = {m["name"] for m in spec.metrics_for(BENCHMARK, name, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in spec.metrics_for(BENCHMARK, name, True):
        assert m["moves"] in e2e


def test_every_config_file_is_under_the_benchmark_and_used():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    for c in BENCHMARK["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(BENCHMARK["paths"][0] + "/")
        assert spec.config(BENCHMARK, c["name"])["reduced"] == c["reduced"]


def test_a_missing_reader_or_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric.open")
    with pytest.raises(spec.SpecError):
        spec.cell(BENCHMARK, "no.such-cell")


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_in_interpret_mode(trace):
    """The whole run at a tiny size, the kernels in interpret mode."""
    name = "sift1m.radius-open"
    args = harness.parse(["--workload", name, "--seed", "3000000019",
                          "--seconds", "1", "--trace", str(trace)])
    out = harness.run(args, time.perf_counter(), require_tpu=False,
                      overrides=tiny(name, lane="pallas-tpu"))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 30
    want = {m["name"] for m in spec.metrics_for(BENCHMARK, name, bool(trace))}
    # the CPU has no device plane: the trace readers find nothing there
    got = set(out["metrics"])
    if trace:
        assert {"queue_ms.open", "service_ms.open",
                "launches_per_row.open"} <= got <= want
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
    else:
        assert got == want
        assert out["metrics"]["setup_s"]["unit"] == "s"
    json.dumps(out)


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.radius-open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_refuses_to_run_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_the_command_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout

"""A rehearsal of every cell runs its code path and comes out correct;
without a TPU, or without the program, the command prints no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_is_correct(name, trace):
    cell = harness.load_cell(name)
    result = harness.run(cell, seed=2**31 + 12345, seconds=1.0, trace=trace,
                         rehearse=True)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    entries = cell.per_layer if trace else cell.end_to_end
    allowed = {m["name"] for m in entries if m["source"] != "device_trace"}
    assert set(result["metrics"]) <= allowed
    if not trace:
        assert set(result["metrics"]) == allowed
    assert "busy_s" not in result["device"]


def _command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "7",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_no_tpu_prints_no_result():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_every_kind_and_check_is_found_by_name():
    import importlib

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        for system in config["systems"]:
            importlib.import_module(f"bench.operators.{system['operator']['kind']}")
            importlib.import_module(f"bench.rhs.{system['rhs']['kind']}")
        for name in config["limits"]:
            importlib.import_module(f"bench.checks.{name}")


def test_a_mix_draws_each_share_in_a_seeded_order():
    from bench import generators

    config = {"systems": [
        {"operator": {"kind": "poisson_2d", "nx": 3, "ny": 3, "conductance_scale": 1e-4},
         "rhs": {"kind": "uniform_rhs", "scale": 1e-6}, "method": "analog_2n",
         "opamp": "AD712", "share": 3},
        {"operator": {"kind": "stencil27_3d", "nx": 2, "ny": 2, "nz": 2, "diagonal": 26.0,
                      "off_diagonal": -1.0, "conductance_scale": 1e-5},
         "rhs": {"kind": "uniform_solution", "lo": -0.5, "hi": 0.5}, "method": "analog_n",
         "opamp": "AD712", "share": 1}]}
    systems = generators.load_systems(config)
    first = generators.TicketStream(systems, 8, 2**31 + 5).next_round()
    again = generators.TicketStream(systems, 8, 2**31 + 5).next_round()
    other = generators.TicketStream(systems, 8, 2**31 + 6).next_round()
    assert [s.index for s, _ in first] == [s.index for s, _ in again]
    assert all((b1 == b2).all() for (_, b1), (_, b2) in zip(first, again))
    for round_ in (first, other):
        assert sorted(s.index for s, _ in round_) == [0] * 6 + [1] * 2
    assert [b.shape[0] for s, b in first if s.index == 1] == [8, 8]
    with pytest.raises(ValueError):
        generators.round_counts(systems, 6)

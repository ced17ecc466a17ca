"""The control, the next precision below the configuration's, comes
out not correct at the rehearsal sizes (the chip readings at the cells'
own sizes are in PERF.md)."""

import json
from pathlib import Path

import pytest

from bench import control

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    (reading,) = control.readings(name, "control", [31337], 1.0, rehearse=True)
    assert not reading["correct"], reading


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    (reading,) = control.readings(name, "program", [31337], 1.0, rehearse=True)
    assert reading["correct"], reading

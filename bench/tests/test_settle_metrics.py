"""The readers of the settle path's counter and span: device time per
Euler step over the growth of ``stats["settle_steps_swept"]``, and the
growth of the ``core.settle_prep`` span per micro-batch.  A program
without them (the parent of the change that added them) reads nothing,
and no reader raises."""

import importlib
import json
from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


def record(before, after, trace=None, window=None):
    return harness.RunRecord(
        cell=None, config={}, systems=[], tickets=[object()] * 8,
        window_tickets=[], delivered=8, setup_s=0.0, window_s=1.0,
        stats_before=before, stats_after=after, device_kind="TPU v5 lite",
        platform="tpu", devices=[0], trace=trace, trace_window=window)


def spans(batches, **seconds):
    out = {"serve.dispatch": {"count": batches, "s": 2.0 * batches}}
    out.update({k: {"count": batches, "s": s} for k, s in seconds.items()})
    return out


def synthetic_sweep() -> tr.Trace:
    """Two chunk programs of the tiled sweep inside the window, one
    after it, and a DC program that is not a sweep."""
    return tr.Trace(
        ops={0: []},
        modules={0: [("jit_tiled_transient_sweep_pallas", 10 * MS, 40 * MS),
                     ("jit__dc_solve_refined", 40 * MS, 45 * MS),
                     ("jit_tiled_transient_sweep_pallas", 50 * MS, 80 * MS),
                     ("jit_tiled_transient_sweep_pallas", 110 * MS, 140 * MS)]},
        host={"python": [("bench.window", 0, 100 * MS)]},
    )


def test_sweep_ms_per_step_reads_programs_over_steps_swept():
    t = synthetic_sweep()
    run = record({"settle_steps_swept": 100}, {"settle_steps_swept": 200},
                 trace=t, window=tr.window(t))
    assert reader("sweep_ms_per_step")(run) == pytest.approx(60.0 / 100)


def test_sweep_ms_per_step_reads_nothing_without_the_counter():
    t = synthetic_sweep()
    read = reader("sweep_ms_per_step")
    parent = {"spans": spans(2)}
    assert read(record(parent, parent, trace=t, window=tr.window(t))) is None
    same = {"settle_steps_swept": 100}
    assert read(record(same, same, trace=t, window=tr.window(t))) is None
    assert read(record(same, {"settle_steps_swept": 200})) is None


def test_settle_prep_per_micro_batch():
    read = reader("settle_prep_ms_per_batch")
    before = {"spans": spans(2, **{"core.settle_prep": 0.9})}
    after = {"spans": spans(5, **{"core.settle_prep": 2.1})}
    assert read(record(before, after)) == pytest.approx(400.0)
    parent = {"spans": spans(2, **{"core.assemble": 1.0})}
    assert read(record(parent, parent)) is None          # no such span


def test_settle_metrics_list_both_settle_cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in ("sweep_ms_per_step", "settle_prep_ms_per_batch"):
        assert entries[name]["workloads"] == ["poisson5.settle",
                                              "elastic3d.settle"]
        assert entries[name]["moves"] == "solves_per_s"

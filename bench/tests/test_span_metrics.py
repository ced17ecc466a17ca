"""The readers of the program's spans and counters: the growth of
``stats["spans"]`` and ``stats["queue_wait_s"]`` over the traced window,
and the idle share inside the ``core.settle`` spans of a trace.  A
program without them (the parent of the change that added them) reads
nothing, and no reader raises."""

import importlib
import json
from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[2]
SPAN_METRICS = {"netlist_ms_per_batch": "core.build_nets",
                "assemble_ms_per_batch": "core.assemble",
                "transfer_ms_per_batch": "core.transfer"}
NEW = (*SPAN_METRICS, "settle_idle_share", "queue_wait_ms_per_solve")
MS = 1_000_000


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


def record(before, after, tickets=8, trace=None, window=None):
    return harness.RunRecord(
        cell=None, config={}, systems=[], tickets=[object()] * tickets,
        window_tickets=[], delivered=tickets, setup_s=0.0, window_s=1.0,
        stats_before=before, stats_after=after, device_kind="TPU v5 lite",
        platform="tpu", devices=[0], trace=trace, trace_window=window)


def spans(batches, **seconds):
    out = {"serve.dispatch": {"count": batches, "s": 2.0 * batches}}
    out.update({k: {"count": batches, "s": s} for k, s in seconds.items()})
    return out


BEFORE = {"spans": spans(2, **{"core.build_nets": 0.4, "core.assemble": 1.0,
                               "core.transfer": 0.1}),
          "queue_wait_s": 1.0}
AFTER = {"spans": spans(6, **{"core.build_nets": 1.6, "core.assemble": 5.0,
                              "core.transfer": 0.3}),
         "queue_wait_s": 5.0}


@pytest.mark.parametrize("name,want", [("netlist_ms_per_batch", 300.0),
                                       ("assemble_ms_per_batch", 1000.0),
                                       ("transfer_ms_per_batch", 50.0)])
def test_span_metric_per_micro_batch(name, want):
    assert reader(name)(record(BEFORE, AFTER)) == pytest.approx(want)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_span_metric_reads_nothing_without_spans(name):
    parent = {"host_build_s": 1.0, "queue_wait_s": 0.0}
    assert reader(name)(record(parent, parent)) is None
    # spans, but not this one, or no micro-batch in the window
    other = {"spans": spans(2)}
    assert reader(name)(record(other, {"spans": spans(4)})) is None
    assert reader(name)(record(BEFORE, BEFORE)) is None


def test_span_metric_counts_a_span_new_in_the_window():
    """A span first opened inside the window grows from nothing."""
    before = {"spans": spans(2)}
    after = {"spans": spans(4, **{"core.assemble": 0.8})}
    assert reader("assemble_ms_per_batch")(record(before, after)) == \
        pytest.approx(400.0)


def test_queue_wait_per_ticket():
    read = reader("queue_wait_ms_per_solve")
    assert read(record(BEFORE, AFTER, tickets=8)) == pytest.approx(500.0)
    assert read(record({"host_build_s": 0.0}, {"host_build_s": 1.0})) is None
    assert read(record(BEFORE, AFTER, tickets=0)) is None


def synthetic_settle() -> tr.Trace:
    """A 100 ms window with two settle spans: 15 of the first 20 ms and
    2 of the second 20 ms busy on the device; a busy stretch outside any
    settle span does not count."""
    return tr.Trace(
        ops={0: [("dense_step", 10 * MS, 15 * MS),
                 ("dense_step", 20 * MS, 30 * MS),
                 ("fusion", 40 * MS, 48 * MS),
                 ("dense_step", 50 * MS, 52 * MS)]},
        modules={0: []},
        host={"python": [("bench.window", 0, 100 * MS),
                         ("bench.drain", 0, 100 * MS),
                         ("serve.finish", 9 * MS, 71 * MS),
                         ("core.settle", 10 * MS, 30 * MS),
                         ("core.sweep_chunk", 11 * MS, 12 * MS),
                         ("core.settle_poll", 12 * MS, 29 * MS),
                         ("core.settle", 50 * MS, 70 * MS)]},
    )


def test_settle_idle_share_reads_inside_settle_spans():
    t = synthetic_settle()
    run = record({}, {}, trace=t, window=tr.window(t))
    assert reader("settle_idle_share")(run) == pytest.approx(100.0 * 23 / 40)


def test_settle_idle_share_reads_nothing_without_the_span():
    t = synthetic_settle()
    t.host["python"] = [e for e in t.host["python"] if e[0] != "core.settle"]
    read = reader("settle_idle_share")
    assert read(record({}, {}, trace=t, window=tr.window(t))) is None
    assert read(record({}, {})) is None


def test_idle_gap_named_by_innermost_program_span():
    """The benchmark's gap names reach into the program's spans."""
    t = synthetic_settle()
    gaps = tr.idle_gaps(t, 0, *tr.window(t))
    assert [label for label, _ in gaps] == [
        "bench.drain",                      # 52-100 ms, after serve.finish
        "bench.drain",                      # 0-10 ms
        "bench.drain/serve.finish",         # 30-40 ms
        "bench.drain/core.settle_poll",     # 15-20 ms
        "bench.drain/serve.finish",         # 48-50 ms
    ]
    assert [s for _, s in gaps] == pytest.approx(
        [0.048, 0.010, 0.010, 0.005, 0.002])


def test_new_metrics_are_listed_with_their_cells():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name in NEW:
        assert set(entries[name]["workloads"]) <= cells
        importlib.import_module(f"bench.metrics.{name}")
    assert entries["settle_idle_share"]["workloads"] == ["poisson5.settle"]
    assert entries["queue_wait_ms_per_solve"]["moves"] == "latency_p50_ms"

"""Spans on the solve path: ``repro.analysis.runtime.span`` labels a
phase for SyncWatch, annotates the profiler trace and adds to the
installed per-service totals; ``SolveService.stats`` reads its phase
timers and ``queue_wait_s`` from them.  The profiler test reads the
trace with the benchmark's own reader (``bench/trace.py``), which names
the chip's idle gaps by these spans."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.analysis.runtime import _SCOPE_STACK, SpanTotals, SyncWatch, span
from repro.data.spd import random_rhs_from_solution, random_spd
from repro.serving.solve_service import SolveService

ROOT = Path(__file__).resolve().parents[1]


def _submit(svc, seed, count, n=6, **opts):
    rng = np.random.default_rng(seed)
    rids = []
    for _ in range(count):
        a = random_spd(rng, n)
        _, b = random_rhs_from_solution(rng, a)
        rids.append(svc.submit(a, b, **opts))
    return rids


# ------------------------------------------------------------------ units


def test_span_nesting_counts_and_times():
    totals = SpanTotals()
    with totals.installed():
        with span("outer"):
            for _ in range(3):
                with span("inner"):
                    time.sleep(0.002)
    snap = totals.snapshot()
    assert set(snap) == {"outer", "inner"}
    assert snap["outer"]["count"] == 1 and snap["inner"]["count"] == 3
    assert isinstance(snap["inner"]["count"], int)
    assert 0.006 <= snap["inner"]["s"] <= snap["outer"]["s"]
    assert totals.seconds("inner") == snap["inner"]["s"]
    assert totals.seconds("never") == 0.0
    # the snapshot is a copy
    snap["inner"]["count"] = 99
    assert totals.snapshot()["inner"]["count"] == 3


def test_span_outside_a_sink_only_annotates():
    totals = SpanTotals()
    with span("loose"):
        pass
    with totals.installed():
        with span("kept"):
            pass
    with span("loose"):
        pass
    assert set(totals.snapshot()) == {"kept"}


def test_span_records_and_unwinds_on_error():
    totals = SpanTotals()
    with totals.installed(), pytest.raises(ValueError):
        with span("fails", sync="harvest"):
            assert _SCOPE_STACK[-1] == "harvest"
            raise ValueError("boom")
    assert totals.snapshot()["fails"]["count"] == 1
    assert _SCOPE_STACK == ["ambient"]


def test_span_as_decorator_reads_the_sink_per_call():
    @span("decorated")
    def work():
        return 7

    assert work() == 7                   # no sink: annotation only
    first, second = SpanTotals(), SpanTotals()
    with first.installed():
        work()
        work()
    with second.installed():
        work()
    assert first.snapshot()["decorated"]["count"] == 2
    assert second.snapshot()["decorated"]["count"] == 1


def test_sync_inside_timing_span_charged_to_enclosing_label():
    import jax.numpy as jnp

    y = jnp.arange(3.0)
    with SyncWatch() as watch:
        with span("serve.dispatch", sync="dispatch"):
            with span("core.assemble"):          # timing only
                np.asarray(y)
                with span("core.transform", sync="net_build"):
                    np.asarray(y)
                np.asarray(y)
        with span("core.pattern"):               # timing only, ambient
            np.asarray(y)
    assert watch.counts == {"dispatch": 2, "net_build": 1, "ambient": 1}
    assert _SCOPE_STACK == ["ambient"]


# ------------------------------------------------------- service totals


def test_service_span_totals_are_per_service():
    """Two services in one process: each drain's spans, those opened
    deep in core/ included, land on the service that drained."""
    one, two = SolveService(batch_slots=2), SolveService(batch_slots=2)
    _submit(one, 0, 4)                   # two micro-batches
    _submit(two, 1, 2)                   # one micro-batch
    one.drain()
    two.drain()
    a, b = one.stats["spans"], two.stats["spans"]
    assert a["serve.drain"]["count"] == b["serve.drain"]["count"] == 1
    assert a["serve.dispatch"]["count"] == 2
    assert b["serve.dispatch"]["count"] == 1
    for spans, batches in ((a, 2), (b, 1)):
        for name in ("core.build_nets", "core.transform", "core.pattern",
                     "core.assemble", "core.transfer", "serve.pad",
                     "serve.harvest", "serve.finish", "serve.unpack"):
            assert spans[name]["count"] >= batches, name
    # a drain on one service leaves the other's totals as they were
    before = two.stats["spans"]
    _submit(one, 2, 2)
    one.drain()
    assert two.stats["spans"] == before
    assert one.stats["spans"]["serve.drain"]["count"] == 2


def test_service_legacy_timers_read_span_totals():
    svc = SolveService(batch_slots=2)
    _submit(svc, 3, 3)
    svc.drain()
    st = svc.stats
    spans = st["spans"]
    for key, name in (("wall_s", "serve.drain"),
                      ("host_build_s", "serve.dispatch"),
                      ("device_wait_s", "serve.harvest"),
                      ("settle_finish_s", "serve.finish"),
                      ("unpack_s", "serve.unpack")):
        assert st[key] == spans[name]["s"], key
    assert st["host_build_s"] > 0
    assert st["host_build_s"] + st["device_wait_s"] <= st["wall_s"]
    # no span outside a drain reaches the service
    with span("serve.dispatch"):
        pass
    assert svc.stats["spans"] == spans


def test_service_queue_wait_counts_each_ticket_once():
    """batch_slots=1: the second ticket waits at least the first one's
    whole dispatch; each ticket's wait is counted once."""
    svc = SolveService(batch_slots=1, inflight_per_device=1)
    assert svc.stats["queue_wait_s"] == 0.0
    t0 = time.perf_counter()
    _submit(svc, 4, 2)
    svc.drain()
    t1 = time.perf_counter()
    st = svc.stats
    first_dispatch = st["spans"]["serve.dispatch"]["s"] / 2
    assert 0.0 < st["queue_wait_s"] <= 2 * (t1 - t0)
    assert st["queue_wait_s"] >= 0.5 * first_dispatch
    # an empty drain adds nothing
    svc.drain()
    assert svc.stats["queue_wait_s"] == st["queue_wait_s"]


# --------------------------------------------------------- profiler trace


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session over a two-ticket DC drain and a one-ticket
    euler-settle drain, each inside a ``bench.drain`` annotation as the
    benchmark's rounds are; read back with ``bench.trace.load``."""
    import jax

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import trace as tr

    svc = SolveService(batch_slots=2)
    _submit(svc, 5, 2)
    svc.drain()                          # compile outside the trace
    _submit(svc, 6, 1, compute_settling=True, settle_method="euler")
    svc.drain()
    log_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(log_dir))
    try:
        _submit(svc, 7, 2)
        with jax.profiler.TraceAnnotation("bench.drain"):
            svc.drain()
        _submit(svc, 8, 1, compute_settling=True, settle_method="euler")
        with jax.profiler.TraceAnnotation("bench.drain"):
            svc.drain()
    finally:
        jax.profiler.stop_trace()
    return tr.load(tr.find_xplane(log_dir))


def _nested_in_drain(trace, drain_index, names):
    """``{name: count}`` of the host events called ``names`` inside the
    ``drain_index``-th ``bench.drain`` span, on that span's thread."""
    for thread, events in trace.host.items():
        drains = sorted((s, e) for n, s, e in events if n == "bench.drain")
        if len(drains) <= drain_index:
            continue
        lo, hi = drains[drain_index]
        inside = [n for n, s, e in events if lo <= s and e <= hi]
        return {n: inside.count(n) for n in names}
    raise AssertionError("no thread carries the bench.drain spans")


def test_trace_has_dispatch_spans_inside_bench_drain(traced):
    names = ("serve.drain", "serve.dispatch", "core.build_nets",
             "core.assemble", "core.transfer")
    counts = _nested_in_drain(traced, 0, names)
    assert all(counts[n] >= 1 for n in names), counts
    assert counts["serve.dispatch"] == 1      # two tickets, one micro-batch


def test_trace_has_settle_spans_inside_bench_drain(traced):
    names = ("core.settle", "core.sweep_chunk", "core.settle_poll")
    counts = _nested_in_drain(traced, 1, names)
    assert counts["core.settle"] == 1
    assert counts["core.sweep_chunk"] >= 1
    assert counts["core.settle_poll"] == counts["core.sweep_chunk"]

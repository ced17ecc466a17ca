"""The reduction from a trace to busy time, program time and gaps."""

from pathlib import Path

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def synthetic() -> tr.Trace:
    ms = 1_000_000
    return tr.Trace(
        ops={0: [("fusion.1", 1 * ms, 3 * ms), ("dense_step", 2 * ms, 4 * ms),
                 ("fusion.2", 6 * ms, 7 * ms), ("fusion.1", 20 * ms, 30 * ms)]},
        modules={0: [("jit__dc_solve_refined(1)", 1 * ms, 4 * ms),
                     ("jit_other(2)", 6 * ms, 7 * ms)]},
        host={"python": [("bench.window", 0, 10 * ms),
                         ("bench.drain", 0, 5 * ms),
                         ("bench.check", 5 * ms, 10 * ms),
                         ("np.add.at", 5 * ms, 6 * ms)]},
    )


def test_window_busy_and_modules():
    t = synthetic()
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 10_000_000)
    # ops 1-4 ms overlap into one interval, 6-7 ms; 20-30 ms is outside
    assert tr.busy_ns(t, 0, lo, hi) == 4_000_000
    assert tr.module_ns(t, "_dc_solve_refined", lo, hi) == (3_000_000, 1)
    assert tr.top_ops(t, [0], lo, hi)[0] == ["fusion.1 (jit__dc_solve_refined)", 0.002]


def test_idle_gaps_named_by_host_span():
    t = synthetic()
    gaps = tr.idle_gaps(t, 0, *tr.window(t))
    assert gaps == [
        ["bench.check", 0.003],               # 7-10 ms
        ["bench.check/np.add.at", 0.002],     # 4-6 ms
        ["bench.drain", 0.001],               # 0-1 ms
    ]


def test_json_round_trip():
    t = synthetic()
    assert tr.Trace.from_json(t.to_json()) == t


def test_recorded_trace():
    """80 ms of a poisson5.settle drain on one TPU v5e, from 30 ms before
    its first dense_step launch (events cut to that interval, a
    bench.window span over it): the sweep's launches and the host
    waiting between them."""
    t = tr.Trace.from_json((DATA / "trace_extract.json").read_text())
    lo, hi = tr.window(t)
    assert hi - lo == 80_000_000
    busy = tr.busy_ns(t, 0, lo, hi)
    sweep_ns, launches = tr.module_ns(t, "transient_step_batched_pallas", lo, hi)
    assert 0 < sweep_ns <= busy < hi - lo
    assert launches == 20
    assert tr.top_ops(t, [0], lo, hi)[0][0] == \
        "%dense_step.1 (jit_transient_step_batched_pallas)"
    gaps = tr.idle_gaps(t, 0, lo, hi)
    assert len(gaps) == 10
    assert all(label.startswith("bench.drain") for label, _ in gaps)
    assert sum(g for _, g in gaps) <= (hi - lo - busy) / 1e9 + 1e-9

"""Seeded chaos suite for the fault-tolerant serving stack.

Exercises the delivery contract of :class:`SolveService` — every
submitted ticket yields exactly one SolveResult or structured
SolveError, drain() terminates under any persistent fault, and tickets
untouched by faults keep 1e-9 parity with the direct solve — plus the
unit behavior of the injector, the stream circuit breaker, and the
analog→digital fallback.  Multi-device chaos (8 forced host devices)
runs in a subprocess so the in-process tests keep the single-device
JAX runtime the rest of the suite expects.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.solver import (
    FALLBACK_RESIDUAL_TOL,
    BatchSolveResult,
    SolveResult,
    _apply_digital_fallback,
    fallback_mask,
    solve,
)
from repro.data.spd import random_rhs_from_solution, random_spd
from repro.distributed.sharding import StreamBreaker
from repro.serving.faults import (
    ERROR_KINDS,
    FAULT_KINDS,
    FaultInjector,
    FaultPlan,
    SolveError,
)
from repro.serving.solve_service import SolveService


def _sys(rng, n):
    a = random_spd(rng, n)
    x, b = random_rhs_from_solution(rng, a)
    return a, x, b


# ------------------------------------------------------- error taxonomy
def test_solve_error_validates_kind():
    err = SolveError(kind="device_fault", attempts=2, detail="boom")
    assert err.kind == "device_fault" and err.attempts == 2
    with pytest.raises(ValueError, match="unknown error kind"):
        SolveError(kind="gremlins")


def test_fault_plan_validates():
    FaultPlan(rates={"device_fault": 0.5, "nonfinite": 0.5})
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan(rates={"gremlins": 0.1})
    with pytest.raises(ValueError, match="unknown scheduled fault"):
        FaultPlan(schedule=((0, "gremlins"),))
    with pytest.raises(ValueError, match="sum to"):
        FaultPlan(rates={"device_fault": 0.7, "nonfinite": 0.7})


# ------------------------------------------------------- fault injector
def test_injector_seeded_and_deterministic():
    plan = FaultPlan(seed=7, rates={"device_fault": 0.3, "nonfinite": 0.2})
    seq_a = [FaultInjector(plan).draw() for _ in range(1)]  # fresh each call
    inj1 = FaultInjector(plan)
    inj2 = FaultInjector(plan)
    seq1 = [inj1.draw() for _ in range(200)]
    seq2 = [inj2.draw() for _ in range(200)]
    assert seq1 == seq2                         # pure function of seed
    hits = [k for k in seq1 if k is not None]
    assert hits, "a 50% total rate must inject in 200 draws"
    assert set(hits) <= set(FAULT_KINDS)
    # empirical rate in the right ballpark for n=200, p=0.5
    assert 60 <= len(hits) <= 140
    st = inj1.stats()
    assert st["dispatches"] == 200
    assert st["total_injected"] == len(hits)


def test_injector_schedule_overrides_rates():
    inj = FaultInjector(FaultPlan(schedule=((3, "build_error"),)))
    draws = [inj.draw() for _ in range(6)]
    assert draws == [None, None, None, "build_error", None, None]


def test_injector_device_filter_does_not_retime():
    """Narrowing the device target set must not shift WHEN the other
    faults fire — the rng is consumed before the filter."""
    plan_all = FaultPlan(seed=3, rates={"device_fault": 0.4})
    plan_dev0 = FaultPlan(seed=3, rates={"device_fault": 0.4}, devices=(0,))
    inj_all = FaultInjector(plan_all)
    inj_dev0 = FaultInjector(plan_dev0)
    devs = [i % 4 for i in range(100)]
    seq_all = [inj_all.draw(dev=d) for d in devs]
    seq_dev0 = [inj_dev0.draw(dev=d) for d in devs]
    for i, d in enumerate(devs):
        if d == 0:
            assert seq_dev0[i] == seq_all[i]    # same timeline on target
        else:
            assert seq_dev0[i] is None          # filtered elsewhere
    assert any(k is not None for k in seq_dev0)


# ------------------------------------------------------ circuit breaker
def test_breaker_trips_after_threshold_and_probes_after_backoff():
    t = [0.0]
    br = StreamBreaker(2, threshold=3, backoff_s=1.0, clock=lambda: t[0])
    assert br.acquire(0) and br.state(0) == "closed"
    assert not br.record_failure(0)
    assert not br.record_failure(0)
    assert br.record_failure(0)                 # third failure trips
    assert br.state(0) == "open" and br.trips == 1
    assert not br.acquire(0)                    # backoff pending
    assert br.acquire(1)                        # other stream unaffected
    t[0] = 1.5
    assert br.acquire(0)                        # backoff elapsed: probe
    assert br.state(0) == "half_open" and br.probes == 1
    assert not br.acquire(0)                    # one probe at a time
    br.record_success(0)
    assert br.state(0) == "closed" and br.restores == 1


def test_breaker_failed_probe_doubles_backoff_capped():
    t = [0.0]
    br = StreamBreaker(1, threshold=1, backoff_s=1.0, backoff_max_s=3.0,
                       clock=lambda: t[0])
    assert br.record_failure(0)                 # trip: backoff 1.0
    for expect in (2.0, 3.0, 3.0):              # doubling, then capped
        t[0] += 10.0
        assert br.acquire(0)                    # probe
        assert br.record_failure(0)             # probe fails
        assert br._streams[0].backoff_s == expect


def test_breaker_release_returns_probe_unjudged():
    t = [0.0]
    br = StreamBreaker(1, threshold=1, backoff_s=1.0, clock=lambda: t[0])
    br.record_failure(0)
    t[0] = 2.0
    assert br.acquire(0) and br.state(0) == "half_open"
    br.release(0)                               # host build raised
    assert br.state(0) == "open"
    assert br.acquire(0)                        # next acquire re-probes now


def test_breaker_force_probe_expires_soonest_open():
    t = [0.0]
    br = StreamBreaker(2, threshold=1, backoff_s=5.0, clock=lambda: t[0])
    br.record_failure(0)
    t[0] = 1.0
    br.record_failure(1)                        # recovers later than 0
    assert br.force_probe() == 0
    assert br.acquire(0)                        # probes immediately
    br.record_success(0)
    assert br.stats()["states"] == ["closed", "open"]


# ------------------------------------------------ analog→digital fallback
def test_fallback_mask_flags_nonfinite_and_uncertified_overflow():
    rng = np.random.default_rng(0)
    a = np.stack([random_spd(rng, 5) for _ in range(3)])
    x = np.stack([np.linalg.solve(a[i], np.ones(5)) for i in range(3)])
    b = np.einsum("bij,bj->bi", a, x)
    good = fallback_mask(x, a, b)
    assert not good.any()
    x_bad = x.copy()
    x_bad[1, 2] = np.inf
    assert fallback_mask(x_bad, a, b).tolist() == [False, True, False]
    # uncertified + residual overflow flags; uncertified + accurate not
    cert = np.array([False, True, False])
    x_off = x.copy()
    x_off[0] = x[0] + 1.0                       # huge residual
    m = fallback_mask(x_off, a, b, certified=cert)
    assert m.tolist() == [True, False, False]


def test_apply_digital_fallback_repairs_bad_rows_only():
    rng = np.random.default_rng(1)
    a = np.stack([random_spd(rng, 6) for _ in range(2)])
    x_true = np.stack([np.linalg.solve(a[i], np.arange(1.0, 7.0))
                       for i in range(2)])
    b = np.einsum("bij,bj->bi", a, x_true)
    x = x_true.copy()
    x[0, 0] = np.nan
    res = BatchSolveResult(
        x=x, method="analog_2n", stable=np.array([True, True]),
        settle_time=None, info={},
    )
    out = _apply_digital_fallback(
        res, a, b, method="cholesky", tol=1e-10, max_iter=100,
        residual_tol=FALLBACK_RESIDUAL_TOL,
    )
    assert list(out.info["fallback"]) == ["cholesky", ""]
    np.testing.assert_allclose(out.x[0], x_true[0], rtol=1e-9, atol=1e-9)
    np.testing.assert_array_equal(out.x[1], x_true[1])   # untouched


def test_solver_fallback_validation():
    rng = np.random.default_rng(2)
    a, x, b = _sys(rng, 5)
    with pytest.raises(ValueError, match="unknown fallback"):
        solve(a, b, method="analog_2n", fallback="quantum")
    r = solve(a, b, method="analog_2n", fallback=None)    # None -> "none"
    np.testing.assert_allclose(r.x, x, rtol=1e-6, atol=1e-9)


# --------------------------------------------------- service-level chaos
def _chaos_run(*, rates, n_streams=1, n_requests=18, seed=11, **svc_kw):
    """Submit a mixed stream under an armed injector and check the
    delivery contract; returns (service, results, direct solutions)."""
    rng = np.random.default_rng(seed)
    dev = jax.devices()[0]
    svc = SolveService(
        batch_slots=2,
        devices=[dev] * n_streams,           # n independent streams
        fault_injector=FaultInjector(FaultPlan(seed=seed, rates=rates)),
        **svc_kw,
    )
    want = {}
    for i in range(n_requests):
        n = (6, 9, 12)[i % 3]
        a, x, b = _sys(rng, n)
        m = ("analog_2n", "cholesky", "cg")[i % 3]
        want[svc.submit(a, b, method=m, tol=1e-12)] = (a, b, m)
    res = svc.drain()
    # exactly-once: every rid answered, nothing extra, queue empty
    assert set(res) == set(want)
    assert len(svc.queue) == 0
    for rid, r in res.items():
        assert isinstance(r, (SolveResult, SolveError))
        if isinstance(r, SolveError):
            assert r.kind in ERROR_KINDS
        else:
            # a delivered solution is a CLEAN solution — retried or
            # not, it matches the direct solve
            a, b, m = want[rid]
            direct = solve(a, b, method=m, tol=1e-12)
            np.testing.assert_allclose(r.x, direct.x, rtol=0.0, atol=1e-9)
    return svc, res, want


@pytest.mark.parametrize("rates", [
    {"device_fault": 0.2},
    {"nonfinite": 0.2},
    {"build_error": 0.2},
    {"device_fault": 0.1, "nonfinite": 0.05, "build_error": 0.05},
    # the same 50/25/25 split at a 5% total rate
    {"device_fault": 0.025, "nonfinite": 0.0125, "build_error": 0.0125},
])
def test_service_chaos_exactly_once_under_faults(rates):
    svc, res, want = _chaos_run(rates=rates, max_attempts=4)
    assert svc.stats["fault_injections"] > 0
    # the overwhelming majority still delivers at 20% injection with
    # a 4-attempt budget
    ok = sum(isinstance(r, SolveResult) for r in res.values())
    assert ok >= len(want) - 2


def test_service_chaos_zero_rate_is_fault_free():
    svc, res, want = _chaos_run(rates={})
    assert svc.stats["fault_injections"] == 0
    assert all(isinstance(r, SolveResult) for r in res.values())
    assert svc.stats["retries"] == 0 and svc.stats["bisections"] == 0


def test_service_persistent_fault_terminates_with_errors():
    """rate=1.0 device faults: drain must still terminate, answering
    every ticket with a bounded device_fault error."""
    svc, res, want = _chaos_run(
        rates={"device_fault": 1.0}, n_requests=6, max_attempts=2,
        breaker_backoff_s=0.005,
    )
    assert all(
        isinstance(r, SolveError) and r.kind == "device_fault"
        and r.attempts == 2
        for r in res.values()
    )
    assert svc.stats["errors"]["device_fault"] == 6
    assert svc.stats["breaker"]["trips"] >= 1    # quarantined + probed


def test_service_quarantine_reroutes_to_healthy_stream():
    """A sick stream (targeted injection) trips its breaker; its work
    re-queues blamelessly onto the healthy stream and ALL tickets
    deliver correct solutions."""
    rng = np.random.default_rng(21)
    dev = jax.devices()[0]
    inj = FaultInjector(FaultPlan(
        seed=5, rates={"device_fault": 1.0}, devices=(0,),
    ))
    svc = SolveService(
        batch_slots=1, devices=[dev, dev], fault_injector=inj,
        breaker_threshold=1, breaker_backoff_s=30.0, max_attempts=10,
    )
    want = {}
    for _ in range(8):
        a, x, b = _sys(rng, 6)
        want[svc.submit(a, b, method="cholesky")] = (a, b)
    res = svc.drain()
    assert set(res) == set(want)
    for rid, (a, b) in want.items():
        np.testing.assert_allclose(
            res[rid].x, np.linalg.solve(a, b), rtol=1e-6, atol=1e-9)
    st = svc.stats
    assert st["quarantines"] >= 1
    assert st["breaker"]["states"][0] == "open"          # still sick
    assert st["breaker"]["states"][1] == "closed"        # carried the load
    assert sum(st["errors"].values()) == 0               # blameless requeue


def test_service_breaker_recovers_after_transient_fault():
    """A stream that trips on a one-off fault is probed half-open and
    restored to closed within the same drain."""
    rng = np.random.default_rng(23)
    dev = jax.devices()[0]
    inj = FaultInjector(FaultPlan(schedule=((0, "device_fault"),)))
    svc = SolveService(
        batch_slots=1, devices=[dev, dev], fault_injector=inj,
        breaker_threshold=1, breaker_backoff_s=0.0, max_attempts=5,
    )
    want = {}
    for _ in range(8):
        a, x, b = _sys(rng, 6)
        want[svc.submit(a, b, method="cholesky")] = (a, b)
    res = svc.drain()
    for rid, (a, b) in want.items():
        np.testing.assert_allclose(
            res[rid].x, np.linalg.solve(a, b), rtol=1e-6, atol=1e-9)
    st = svc.stats["breaker"]
    assert st["trips"] >= 1 and st["restores"] >= 1
    assert st["states"] == ["closed", "closed"]


def test_service_slow_fault_is_harmless_but_counted():
    svc, res, want = _chaos_run(rates={"slow": 0.5})
    assert all(isinstance(r, SolveResult) for r in res.values())
    assert svc.stats["fault_injections"] > 0
    assert svc.stats["retries"] == 0


# --------------------------------------------------- engine-side pieces
def test_admission_queue_preserves_explicit_stamps():
    """Regression: push() used to unconditionally overwrite the item's
    priority/deadline with its own defaults, silently erasing stamps
    set on a caller-constructed Request."""
    from repro.serving.engine import AdmissionQueue, Request

    q = AdmissionQueue()
    pre = Request(rid=0, prompt=np.arange(3), priority=7, deadline=42.0)
    q.push(pre)                                  # no kwargs: preserved
    assert pre.priority == 7 and pre.deadline == 42.0
    over = Request(rid=1, prompt=np.arange(3), priority=7)
    q.push(over, priority=1, deadline=5.0)       # explicit: overrides
    assert over.priority == 1 and over.deadline == 5.0
    assert q.pop() is pre                        # higher priority first
    # requeue keeps original stamps, seq included
    seq = pre.seq
    q.requeue([pre])
    assert pre.seq == seq and q.pop() is pre


def test_serve_engine_rejects_expired_deadline():
    import time

    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.serving.engine import Request, ServeEngine

    cfg = get_smoke_config("mamba2_370m")
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=48)
    stale = Request(rid=0, prompt=np.arange(4), max_new=3)
    fresh = Request(rid=1, prompt=np.arange(4), max_new=3)
    eng.submit(stale, deadline=time.monotonic() - 1.0)
    eng.submit(fresh, deadline=time.monotonic() + 60.0)
    eng.run(max_steps=100)
    assert stale.done and stale.error is not None
    assert stale.error.kind == "deadline_expired"
    assert stale.out == []                       # never prefilled
    assert fresh.done and fresh.error is None and len(fresh.out) >= 3
    assert eng.expired == 1


def test_serve_engine_survives_injected_step_faults():
    from repro.configs import get_smoke_config
    from repro.models.model import init_params
    from repro.serving.engine import Request, ServeEngine

    cfg = get_smoke_config("mamba2_370m")
    params = init_params(cfg, jax.random.PRNGKey(1))
    inj = FaultInjector(FaultPlan(seed=9, rates={"device_fault": 0.3}))
    eng = ServeEngine(cfg, params, batch_slots=1, max_seq=48,
                      fault_injector=inj)
    reqs = [Request(rid=i, prompt=np.arange(4), max_new=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run(max_steps=200)                       # budget covers retries
    assert all(r.done and len(r.out) >= 3 for r in reqs)
    assert eng.faulted_steps > 0


# ------------------------------------------------- 8-device chaos (slow)
_CHAOS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.core.solver import SolveResult, solve
    from repro.data.spd import random_spd, random_rhs_from_solution
    from repro.serving.faults import FaultInjector, FaultPlan, SolveError
    from repro.serving.solve_service import SolveService

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(31)
    inj = FaultInjector(FaultPlan(seed=31, rates={
        "device_fault": 0.1, "nonfinite": 0.05, "build_error": 0.05,
    }))
    svc = SolveService(batch_slots=2, n_devices=8, fault_injector=inj,
                       max_attempts=4, breaker_backoff_s=0.01)
    want = {}
    for i in range(32):
        n = [6, 10][i % 2]
        a = random_spd(rng, n)
        x, b = random_rhs_from_solution(rng, a)
        m = "analog_2n" if i % 2 else "cholesky"
        want[svc.submit(a, b, method=m)] = (a, b, m)
    res = svc.drain()
    assert set(res) == set(want)                 # exactly-once
    assert len(svc.queue) == 0                   # terminated clean
    worst, n_err = 0.0, 0
    for rid, r in res.items():
        if isinstance(r, SolveError):
            n_err += 1
            continue
        a, b, m = want[rid]
        direct = solve(a, b, method=m)
        worst = max(worst, float(np.abs(r.x - direct.x).max()))
    assert worst < 1e-9, worst                   # delivered == clean
    st = svc.stats
    assert st["fault_injections"] > 0
    print(json.dumps({
        "worst": worst, "errors": n_err, "devices": st["devices"],
        "injected": st["fault_injections"], "retries": st["retries"],
    }))
""")


@pytest.mark.slow
def test_service_chaos_over_eight_forced_devices():
    """The acceptance gate: 20% mixed fault rate over 8 forced host
    devices — exactly-once delivery, clean termination, and 1e-9
    parity for every delivered solution."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host devices: the child must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _CHAOS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    info = json.loads(out.stdout.strip().splitlines()[-1])
    assert info["devices"] == 8 and info["worst"] < 1e-9
    assert info["injected"] > 0

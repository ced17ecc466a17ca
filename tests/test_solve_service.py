"""Solve service: batched digital dispatch, padding parity, bucketed
multi-device request batching, and the vectorized netlist builders."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.network import (
    build_preliminary,
    build_preliminary_batch,
    build_proposed,
    build_proposed_batch,
)
from repro.core.solver import solve, solve_batch
from repro.data.spd import random_rhs_from_solution, random_sdd, random_spd
from repro.serving.faults import SolveError
from repro.serving.solve_service import (
    DEFAULT_PAD_SIZES,
    PAD_QUANTUM,
    SolveService,
    pad_system,
)


def _sys(rng, n, kind="spd"):
    a = random_sdd(rng, n) if kind == "sdd" else random_spd(rng, n)
    x, b = random_rhs_from_solution(rng, a)
    return a, x, b


# ---------------------------------------------------- digital batch dispatch
@pytest.mark.parametrize("method", ["cholesky", "cg", "jacobi"])
def test_solve_batch_digital_dispatch_matches_looped_solve(method):
    """Regression: solve_batch(method=digital) used to crash inside
    _build_nets with a misleading 'unknown analog method' error."""
    rng = np.random.default_rng(0)
    kind = "sdd" if method == "jacobi" else "spd"   # jacobi needs dominance
    systems = [_sys(rng, 12, kind) for _ in range(6)]
    a = np.stack([s[0] for s in systems])
    b = np.stack([s[2] for s in systems])

    batch = solve_batch(a, b, method=method, tol=1e-12)
    assert len(batch) == 6 and batch.method == method
    assert batch.settle_time is None
    for k in range(6):
        single = solve(a[k], b[k], method=method, tol=1e-12)
        np.testing.assert_allclose(batch.x[k], single.x, rtol=0.0, atol=1e-10)
        res = batch[k]
        assert res.stable is True and res.method == method
        if method != "cholesky":
            # per-system freezing: iterate sequences (hence counts)
            # match the single-system solver, not the batch's slowest
            assert res.info["iterations"] == single.info["iterations"]
            assert isinstance(res.info["iterations"], int)
            np.testing.assert_allclose(
                res.info["residual_norm"], single.info["residual_norm"],
                rtol=1e-6, atol=1e-15,
            )


def test_solve_batch_unknown_method_is_a_clear_error():
    a = np.eye(4)[None] * 1e-4
    b = np.ones((1, 4)) * 1e-5
    with pytest.raises(ValueError, match="unknown method 'qr'"):
        solve_batch(a, b, method="qr")
    with pytest.raises(ValueError, match="unknown analog method"):
        from repro.core.solver import _build_nets

        _build_nets(a, b, "qr", d_policy="proposed", beta=0.5, alpha=1.0,
                    params=None)


# ------------------------------------------------- vectorized netlist build
@pytest.mark.parametrize("kwargs", [
    {},
    {"d_policy": "scaled", "beta": 0.7},
    {"d_policy": "gremban"},
    {"alpha": 0.25},
])
def test_build_proposed_batch_matches_single(kwargs):
    rng = np.random.default_rng(1)
    systems = [_sys(rng, 11, "sdd" if i == 2 else "spd") for i in range(5)]
    a = np.stack([s[0] for s in systems])
    b = np.stack([s[2] for s in systems])
    b[3] = -np.abs(b[3])            # all-negative RHS exercises supply signs
    nets_b = build_proposed_batch(a, b, **kwargs)
    for k in range(5):
        net_s = build_proposed(a[k], b[k], **kwargs)
        nb = nets_b[k]
        assert nb.design == net_s.design
        for f in ("branch_i", "branch_j", "cell_i", "cell_j"):
            np.testing.assert_array_equal(getattr(nb, f), getattr(net_s, f))
        for f in ("branch_g", "ground_g", "supply_g", "supply_v", "cell_w",
                  "element_count"):
            np.testing.assert_allclose(
                getattr(nb, f), np.asarray(getattr(net_s, f)),
                rtol=1e-12, atol=1e-18, err_msg=f,
            )


def test_build_preliminary_batch_matches_single():
    rng = np.random.default_rng(2)
    systems = [_sys(rng, 9) for _ in range(4)]
    a = np.stack([s[0] for s in systems])
    b = np.stack([s[2] for s in systems])
    nets_b = build_preliminary_batch(a, b)
    for k in range(4):
        net_s = build_preliminary(a[k], b[k])
        nb = nets_b[k]
        for f in ("branch_i", "branch_j", "cell_i", "cell_j"):
            np.testing.assert_array_equal(getattr(nb, f), getattr(net_s, f))
        for f in ("branch_g", "ground_g", "supply_g", "cell_w",
                  "element_count"):
            np.testing.assert_allclose(
                getattr(nb, f), np.asarray(getattr(net_s, f)),
                rtol=1e-12, atol=1e-18, err_msg=f,
            )


# ------------------------------------------------------------ pad parity
def test_pad_system_structure():
    rng = np.random.default_rng(3)
    a, x, b = _sys(rng, 6)
    a_pad, b_pad = pad_system(a, b, 10)
    assert a_pad.shape == (10, 10) and b_pad.shape == (10,)
    np.testing.assert_array_equal(a_pad[:6, :6], a)
    np.testing.assert_array_equal(a_pad[:6, 6:], 0.0)
    g_pad = np.mean(np.diagonal(a))
    np.testing.assert_allclose(np.diagonal(a_pad)[6:], g_pad)
    # pad block solves to the nominal pad voltage (nonzero -> pad nodes
    # keep a supply leg; the circuit is never floating)
    np.testing.assert_allclose(
        np.linalg.solve(a_pad, b_pad)[6:], b_pad[6] / g_pad
    )
    with pytest.raises(ValueError):
        pad_system(a, b, 4)


@pytest.mark.parametrize("method", ["analog_2n", "analog_n", "cholesky", "cg"])
def test_padding_parity_inside_bucket(method):
    """A padded system in a shared-pattern bucket matches its unpadded
    solve() to 1e-10 — non-SDD SPD and all-negative-b included."""
    rng = np.random.default_rng(4)
    cases = []
    a, x, b = _sys(rng, 7)                       # non-SDD SPD (dense random)
    cases.append((a, b))
    a, x, b = _sys(rng, 7, "sdd")                # fully passive 2n path
    cases.append((a, b))
    a, x, b = _sys(rng, 7)
    b = -np.abs(b)                               # all-negative RHS
    cases.append((a, b))

    svc = SolveService(batch_slots=4)
    rids = [svc.submit(a, b, method=method, tol=1e-12) for a, b in cases]
    res = svc.drain()
    for rid, (a, b) in zip(rids, cases):
        direct = solve(a, b, method=method, tol=1e-12)
        assert res[rid].x.shape == b.shape       # pad masked back out
        np.testing.assert_allclose(res[rid].x, direct.x, rtol=0.0, atol=1e-10)
        assert res[rid].info["service_n_padded"] == 8


# ------------------------------------------------------------- the service
def test_pad_grid():
    svc = SolveService()
    assert svc.pad_to(3) == DEFAULT_PAD_SIZES[0]
    assert svc.pad_to(16) == 16
    assert svc.pad_to(17) == 32
    assert svc.pad_to(300) == 320 and 320 % PAD_QUANTUM == 0


def test_service_mixed_stream_buckets_and_parity():
    rng = np.random.default_rng(5)
    svc = SolveService(batch_slots=3)
    want = {}
    for i in range(10):
        n = [6, 11, 16][i % 3]
        method = "analog_2n" if i % 2 else "cholesky"
        a, x, b = _sys(rng, n)
        want[svc.submit(a, b, method=method)] = (a, b, method)
    res = svc.drain()
    assert set(res) == set(want)
    for rid, (a, b, method) in want.items():
        direct = solve(a, b, method=method)
        np.testing.assert_allclose(res[rid].x, direct.x, rtol=0.0, atol=1e-9)
    st = svc.stats
    assert st["requests"] == 10
    # sizes 6/11/16 with methods x2 -> buckets (8, 16) x (analog, chol)
    assert set(st["buckets"]) == {
        "n8/analog_2n", "n16/analog_2n", "n8/cholesky", "n16/cholesky"
    }
    assert st["pad_overhead"] > 1.0


def test_service_bucket_pipeline_reuses_pattern():
    """Steady-state analog buckets keep one stamp pattern across
    micro-batches (the per-bucket jit/pattern cache)."""
    rng = np.random.default_rng(6)
    svc = SolveService(batch_slots=2)
    for _ in range(6):                           # 3 micro-batches, one bucket
        a, x, b = _sys(rng, 10)
        svc.submit(a, b, method="analog_2n")
    svc.drain()
    (key, pipe), = svc._pipelines.items()
    assert pipe.micro_batches == 3
    assert pipe.pattern is not None
    assert pipe.pattern_rebuilds == 0
    # the 2n slot set is normalized per (n, design): ONE union derivation
    # serves every micro-batch of the bucket
    assert pipe.pattern_derivations == 1
    pat_first = pipe.pattern
    for _ in range(2):                           # later drain, same bucket
        a, x, b = _sys(rng, 10)
        svc.submit(a, b, method="analog_2n")
    svc.drain()
    assert pipe.pattern is pat_first and pipe.micro_batches == 4
    assert pipe.pattern_derivations == 1
    assert svc.stats["buckets"]["n16/analog_2n"]["pattern_derivations"] == 1


def _tridiag_spd(n):
    a = np.zeros((n, n))
    idx = np.arange(n - 1)
    a[idx, idx + 1] = a[idx + 1, idx] = -1.0
    np.fill_diagonal(a, 3.0)
    return a


def test_service_analog_n_pattern_cached_and_merge_is_sound():
    """analog_n slot sets are data-dependent, but the bucket caches the
    union pattern: repeated-sparsity streams derive once, a micro-batch
    stamping new slots grows the union via merge — and the merged
    pattern's extra inactive slots are exact no-ops (results still match
    the direct per-system solve)."""
    rng = np.random.default_rng(20)
    svc = SolveService(batch_slots=2)
    a_sp = _tridiag_spd(8)                       # sparse slot population
    cases = []
    for _ in range(4):                           # 2 micro-batches, 1 pattern
        x, b = random_rhs_from_solution(rng, a_sp)
        cases.append((a_sp, b, svc.submit(a_sp, b, method="analog_n")))
    res = svc.drain()
    (key, pipe), = svc._pipelines.items()
    assert pipe.micro_batches == 2
    assert pipe.pattern_derivations == 1         # cache hit on batch 2
    assert pipe.pattern_rebuilds == 0

    a_dense, x, b = _sys(rng, 8)                 # stamps slots tridiag lacks
    cases.append((a_dense, b, svc.submit(a_dense, b, method="analog_n")))
    x2, b2 = random_rhs_from_solution(rng, a_sp)
    cases.append((a_sp, b2, svc.submit(a_sp, b2, method="analog_n")))
    res.update(svc.drain())
    assert pipe.pattern_derivations == 2         # one miss -> one merge
    assert pipe.pattern_rebuilds == 1
    st = svc.stats["buckets"]["n8/analog_n"]
    assert st["pattern_derivations"] == 2
    for a, b, rid in cases:
        direct = solve(a, b, method="analog_n")
        np.testing.assert_allclose(res[rid].x, direct.x, rtol=0.0, atol=1e-9)


def test_service_custom_opamp_spec():
    """A custom OpAmpSpec (including one shadowing a registry name)
    buckets separately and is solved with ITS parameters."""
    import dataclasses

    from repro.core.operating_point import DEFAULT_NONIDEAL
    from repro.core.specs import OPAMPS

    rng = np.random.default_rng(8)
    a, x, b = _sys(rng, 6)
    mod = dataclasses.replace(OPAMPS["AD712"], open_loop_gain=50.0)
    svc = SolveService(batch_slots=2)
    r1 = svc.submit(a, b, method="analog_2n", opamp=mod,
                    nonideal=DEFAULT_NONIDEAL)
    r2 = svc.submit(a, b, method="analog_2n", opamp="AD712",
                    nonideal=DEFAULT_NONIDEAL)
    out = svc.drain()
    assert len(svc._pipelines) == 2          # shared name, distinct buckets
    for rid, spec in ((r1, mod), (r2, "AD712")):
        direct = solve(a, b, method="analog_2n", opamp=spec,
                       nonideal=DEFAULT_NONIDEAL)
        np.testing.assert_allclose(out[rid].x, direct.x, rtol=0.0, atol=1e-10)
    # gain=50 must visibly differ — proves the custom params were used
    assert not np.allclose(out[r1].x, out[r2].x, rtol=0.0, atol=1e-8)
    with pytest.raises(ValueError, match="unknown opamp"):
        svc.submit(a, b, opamp="OP999")


def test_service_builds_nets_once_per_micro_batch():
    """The bucket pipeline's cover-check netlists are handed through to
    solve_batch — no double host-side build."""
    import repro.core.solver as solver_mod
    import repro.serving.solve_service as ss

    rng = np.random.default_rng(9)
    a, x, b = _sys(rng, 6)
    calls = {"n": 0}
    orig = solver_mod._build_nets

    def counting(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    solver_mod._build_nets = counting
    ss._build_nets = counting
    try:
        svc = SolveService(batch_slots=2)
        svc.submit(a, b, method="analog_2n")
        svc.submit(a, b, method="analog_2n")
        svc.drain()
    finally:
        solver_mod._build_nets = orig
        ss._build_nets = orig
    assert calls["n"] == 1


def test_service_stats_distinct_buckets_and_fill_overhead():
    """Signature-distinct buckets sharing (n_pad, method) keep separate
    stats entries, and pad_overhead counts repeat-fill slots."""
    rng = np.random.default_rng(10)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=4)
    svc.submit(a, b, method="cg", tol=1e-10)     # tol IS a CG knob:
    svc.submit(a, b, method="cg", tol=1e-12)     # two distinct buckets
    svc.drain()
    st = svc.stats
    assert set(st["buckets"]) == {"n8/cg", "n8/cg#2"}
    # 2 real n=6 systems, each alone in a 4-slot n_pad=8 micro-batch
    want = (2 * 4 * 8.0 ** 2) / (2 * 6.0 ** 2)
    assert st["pad_overhead"] == pytest.approx(want)


def test_service_signature_normalization_shares_buckets():
    """Options the dispatched solver ignores must not fragment batches:
    a cholesky request's opamp / settle options, an analog request's CG
    tolerance."""
    rng = np.random.default_rng(12)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=4)
    svc.submit(a, b, method="cholesky", opamp="AD712", tol=1e-10)
    svc.submit(a, b, method="cholesky", opamp="LTC2050", tol=1e-13)
    svc.submit(a, b, method="analog_2n", tol=1e-10)
    svc.submit(a, b, method="analog_2n", tol=1e-13,
               settle_method="eig")              # no compute_settling
    res = svc.drain()
    assert len(svc._pipelines) == 2              # one per method only
    for rid in res:
        np.testing.assert_allclose(
            res[rid].x, np.linalg.solve(a, b), rtol=1e-6, atol=1e-9
        )


def test_service_iterative_tol_honored_under_padding():
    """Zero-extended digital pad RHS: the relative-residual stopping
    test sees the real ||b||, so a padded CG request converges exactly
    like the unpadded solve — even when the real RHS is tiny."""
    rng = np.random.default_rng(13)
    a, x, b = _sys(rng, 6)
    b = b * 1e-4                                 # small-magnitude RHS
    x = np.linalg.solve(a, b)
    svc = SolveService(batch_slots=2)
    rid = svc.submit(a, b, method="cg", tol=1e-10)
    res = svc.drain()[rid]
    direct = solve(a, b, method="cg", tol=1e-10)
    np.testing.assert_allclose(res.x, direct.x, rtol=0.0, atol=1e-14)
    assert res.info["iterations"] == direct.info["iterations"]
    np.testing.assert_allclose(res.x, x, rtol=1e-5, atol=1e-12)


def test_service_poison_fails_fast_and_batch_mates_still_solve():
    """Regression for the v1 livelock: a persistently-failing request
    used to re-queue the WHOLE drain forever.  Now the poison bisects
    out of its micro-batch, burns its own retry budget, and lands as a
    structured SolveError — while its batch-mates deliver solutions."""
    import repro.serving.solve_service as ss

    rng = np.random.default_rng(15)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=2, max_attempts=3)
    good = svc.submit(a, b, method="cholesky")
    bad_a = a.copy()
    bad_a[0, 0] = np.nan                       # marks the poison request
    bad = svc.submit(bad_a, b, method="analog_2n")
    good2 = svc.submit(a, b, method="analog_2n")

    # the poison's own host build deterministically raises (tied to
    # the request's data, so it follows the ticket through bisection)
    orig = ss.solve_batch_submit

    def building(a_stack, b_stack, **kw):
        if np.isnan(a_stack).any():
            raise RuntimeError("netlist build failed")
        return orig(a_stack, b_stack, **kw)

    ss.solve_batch_submit = building
    try:
        res = svc.drain()                      # terminates — no livelock
    finally:
        ss.solve_batch_submit = orig
    # exactly-once delivery: every ticket answered, queue empty
    assert set(res) == {good, bad, good2}
    assert len(svc.queue) == 0
    err = res[bad]
    assert isinstance(err, SolveError)
    assert err.kind == "poison"
    assert err.attempts == 3                   # full budget consumed
    assert svc.stats["errors"]["poison"] == 1
    assert svc.stats["bisections"] >= 1        # isolated from good2
    for rid in (good, good2):
        np.testing.assert_allclose(res[rid].x, np.linalg.solve(a, b),
                                   rtol=1e-6, atol=1e-9)
    assert not hasattr(svc, "results")          # no unbounded retention

    # the service is healthy afterwards
    again = svc.submit(a, b, method="analog_2n")
    np.testing.assert_allclose(svc.drain()[again].x, np.linalg.solve(a, b),
                               rtol=1e-6, atol=1e-9)


def test_service_nan_system_lands_as_bounded_nonfinite_error():
    """A NaN-carrying system flows through the whole pipeline (the DC
    singular-repair path included — regression: it crashed on JAX's
    read-only buffers) and lands as a bounded structured nonfinite
    error, not a raise and not a livelock."""
    rng = np.random.default_rng(15)
    a, x, b = _sys(rng, 6)
    bad_a = a.copy()
    bad_a[0, 0] = np.nan
    svc = SolveService(batch_slots=1, max_attempts=2)
    rid = svc.submit(bad_a, b, method="analog_2n")
    res = svc.drain()
    err = res[rid]
    assert isinstance(err, SolveError)
    assert err.kind == "nonfinite"
    assert err.attempts == 2
    assert len(svc.queue) == 0


def test_service_priority_deadline_admission_order():
    """Under a saturated bucket the queue admits by priority first,
    earliest-deadline within a class, FIFO last — observed as the
    micro-batch dispatch order.  (Deadlines are absolute monotonic
    stamps and are enforced, so the test uses comfortable offsets from
    SolveService.now().)"""
    rng = np.random.default_rng(17)
    a, x, b = _sys(rng, 6)
    now = SolveService.now()
    svc = SolveService(batch_slots=2)
    rid_fifo = svc.submit(a, b, method="cholesky")
    rid_late = svc.submit(a, b, method="cholesky", deadline=now + 120.0)
    rid_hi = svc.submit(a, b, method="cholesky", priority=5)
    rid_soon = svc.submit(a, b, method="cholesky", deadline=now + 60.0)

    order = []
    orig = svc._dispatch_micro_batch

    def spy(pipe, chunk, dev):
        order.extend(t.rid for t in chunk)
        return orig(pipe, chunk, dev)

    svc._dispatch_micro_batch = spy
    res = svc.drain()
    assert order == [rid_hi, rid_soon, rid_late, rid_fifo]
    assert set(res) == {rid_fifo, rid_late, rid_hi, rid_soon}


def test_service_expired_deadline_rejected_never_dispatched():
    """An expired ticket is rejected at pop time with deadline_expired
    — it never reaches a device — while fresh tickets still solve."""
    rng = np.random.default_rng(22)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=1)
    stale = svc.submit(a, b, method="cholesky",
                       deadline=SolveService.now() - 1.0)
    fresh = svc.submit(a, b, method="cholesky",
                       deadline=SolveService.now() + 60.0)

    dispatched = []
    orig = svc._dispatch_micro_batch

    def spy(pipe, chunk, dev):
        dispatched.extend(t.rid for t in chunk)
        return orig(pipe, chunk, dev)

    svc._dispatch_micro_batch = spy
    res = svc.drain()
    assert stale not in dispatched
    err = res[stale]
    assert isinstance(err, SolveError) and err.kind == "deadline_expired"
    assert svc.stats["deadline_expired"] == 1
    np.testing.assert_allclose(res[fresh].x, np.linalg.solve(a, b),
                               rtol=1e-6, atol=1e-9)


def test_service_queue_depth_shedding_drops_lowest_rank():
    """max_queue_depth sheds the lowest-admission-rank excess with a
    structured shed error; the admitted head still solves."""
    rng = np.random.default_rng(23)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=2, max_queue_depth=2)
    hi = svc.submit(a, b, method="cholesky", priority=5)
    mid = svc.submit(a, b, method="cholesky")
    lo = svc.submit(a, b, method="cholesky", priority=-1)
    res = svc.drain()
    assert isinstance(res[lo], SolveError) and res[lo].kind == "shed"
    assert svc.stats["shed"] == 1
    for rid in (hi, mid):
        np.testing.assert_allclose(res[rid].x, np.linalg.solve(a, b),
                                   rtol=1e-6, atol=1e-9)


def test_service_midflight_injected_fault_retries_to_delivery():
    """A device-side fault surfacing at harvest (injected mid-stream by
    the chaos injector) is retried transparently: every ticket still
    delivers a correct solution exactly once, and the drain's recovery
    work is visible in stats."""
    from repro.serving.faults import FaultInjector, FaultPlan

    rng = np.random.default_rng(18)
    systems = [_sys(rng, 6) for _ in range(4)]
    # the 3rd dispatch's device dies — exact, seeded, layout-independent
    inj = FaultInjector(FaultPlan(schedule=((2, "device_fault"),)))
    svc = SolveService(batch_slots=1, inflight_per_device=2,
                       fault_injector=inj)
    rids = [svc.submit(a, b, method="cholesky") for a, x, b in systems]
    res = svc.drain()
    assert set(res) == set(rids)               # exactly-once, no raise
    for (a, x, b), rid in zip(systems, rids):
        np.testing.assert_allclose(res[rid].x, np.linalg.solve(a, b),
                                   rtol=1e-6, atol=1e-9)
    assert svc.stats["fault_injections"] == 1
    assert svc.stats["retries"] == 1
    assert svc.stats["errors"]["device_fault"] == 0
    assert len(svc.queue) == 0


def test_service_double_buffered_dispatch_parity():
    """inflight_per_device=2 (overlapped) and =1 (serial reference)
    produce bitwise-identical results, both within 1e-9 of the direct
    solve — the overlap changes scheduling, never the computation."""
    rng = np.random.default_rng(19)
    cases = [_sys(rng, 10) for _ in range(6)]
    got = {}
    for inflight in (1, 2):
        svc = SolveService(batch_slots=2, inflight_per_device=inflight)
        rids = [svc.submit(a, b, method="analog_2n") for a, x, b in cases]
        res = svc.drain()
        got[inflight] = [res[r].x for r in rids]
        for (a, x, b), r in zip(cases, rids):
            direct = solve(a, b, method="analog_2n")
            np.testing.assert_allclose(
                res[r].x, direct.x, rtol=0.0, atol=1e-9
            )
    for x_serial, x_overlap in zip(got[1], got[2]):
        np.testing.assert_array_equal(x_serial, x_overlap)


def test_service_vectorized_unpack_matches_batch_getitem():
    """The batched-gather unpack delivers exactly what the per-ticket
    BatchSolveResult.__getitem__ path did: same values, same python
    scalar types, pad masked out."""
    rng = np.random.default_rng(21)
    cases = [_sys(rng, 6) for _ in range(2)]     # 2 real + 1 repeat-fill
    svc = SolveService(batch_slots=3)
    rids = [svc.submit(a, b, method="analog_2n") for a, x, b in cases]
    res = svc.drain()

    padded = [pad_system(a, b, 8) for a, x, b in cases]
    padded.append(padded[-1])                    # the service's repeat-fill
    batch = solve_batch(
        np.stack([p[0] for p in padded]), np.stack([p[1] for p in padded]),
        method="analog_2n",
    )
    for k, rid in enumerate(rids):
        ref = batch[k]
        got = res[rid]
        np.testing.assert_array_equal(got.x, ref.x[:6])
        assert got.stable == ref.stable and got.method == ref.method
        assert got.settle_time is None and ref.settle_time is None
        for key, want in ref.info.items():
            assert type(got.info[key]) is type(want), key
            assert got.info[key] == want, key


def test_service_analog_n_normalization():
    """analog_n ignores d_policy/beta/alpha (preliminary builder takes
    only (a, b)); requests differing there must share a bucket."""
    rng = np.random.default_rng(16)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=2)
    svc.submit(a, b, method="analog_n", beta=0.5)
    svc.submit(a, b, method="analog_n", beta=0.3, d_policy="scaled")
    svc.drain()
    assert len(svc._pipelines) == 1


def test_service_settling_buckets_at_exact_n():
    """Settle metrics describe the whole circuit, so settling requests
    must not be padded — their settle_time equals the direct solve's."""
    rng = np.random.default_rng(14)
    a, x, b = _sys(rng, 6)                       # off-grid size
    svc = SolveService(batch_slots=2)
    rid = svc.submit(a, b, method="analog_2n", compute_settling=True,
                     settle_method="eig")
    res = svc.drain()[rid]
    assert res.info["service_n_padded"] == 6     # exact-n bucket
    direct = solve(a, b, method="analog_2n", compute_settling=True,
                   settle_method="eig")
    np.testing.assert_allclose(res.settle_time, direct.settle_time,
                               rtol=1e-6)


def test_service_settling_passthrough():
    rng = np.random.default_rng(7)
    a, x, b = _sys(rng, 6)
    svc = SolveService(batch_slots=2)
    rid = svc.submit(a, b, method="analog_2n", compute_settling=True,
                     settle_method="eig")
    res = svc.drain()[rid]
    assert res.settle_time is not None and 0 < res.settle_time < 1.0
    assert res.stable


# ------------------------------------------------- subprocess integration
_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    from repro.core.solver import solve
    from repro.data.spd import random_spd, random_rhs_from_solution
    from repro.distributed.sharding import solver_mesh
    from repro.serving.solve_service import SolveService

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(11)
    svc = SolveService(batch_slots=4, mesh=solver_mesh())
    want = {}
    for i in range(6):
        n = [8, 12][i % 2]
        a = random_spd(rng, n)
        x, b = random_rhs_from_solution(rng, a)
        m = "analog_2n" if i % 2 else "cg"
        want[svc.submit(a, b, method=m, tol=1e-12)] = (a, b, m)
    res = svc.drain()
    worst = 0.0
    for rid, (a, b, m) in want.items():
        direct = solve(a, b, method=m, tol=1e-12)
        worst = max(worst, float(np.abs(res[rid].x - direct.x).max()))
    assert worst < 1e-9, worst
    st = svc.stats
    assert st["host_build_s"] > 0 and st["device_wait_s"] >= 0
    print(json.dumps({"worst": worst, "devices": st["devices"]}))
""")


@pytest.mark.slow
def test_service_streams_over_forced_devices():
    """mesh= still resolves the device streams (v1 constructor compat);
    round-robin placement over 4 forced host devices keeps 1e-9 parity."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host devices: the child must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["worst"] < 1e-9


_MIX_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import sys
    import numpy as np
    import jax
    sys.path.insert(0, os.getcwd())
    from chip_smoke import build_stream
    from repro.core.solver import solve
    from repro.serving.solve_service import SolveService

    assert len(jax.devices()) == 4
    stream = build_stream(0, repeat=1)
    direct = [solve(s["a"], s["b"], method=s["method"]).x for s in stream]
    worst = {}
    for slots, streams, inflight in ((2, 1, 2), (4, 1, 1), (4, 1, 2),
                                     (4, 2, 2), (4, 4, 2)):
        svc = SolveService(batch_slots=slots, n_devices=streams,
                           inflight_per_device=inflight)
        rids = [svc.submit(s["a"], s["b"], method=s["method"])
                for s in stream]
        res = svc.drain()
        assert set(res) == set(rids) and len(svc.queue) == 0
        st = svc.stats
        assert st["devices"] == streams
        assert min(st["device_micro_batches"]) > 0
        worst[f"{slots}/{streams}/{inflight}"] = max(
            float(np.abs(res[r].x - x).max()) for r, x in zip(rids, direct))
    print(json.dumps(worst))
""")


def test_service_mixed_stream_over_forced_devices():
    """chip_smoke's nine-system mix (n 16-192, both designs, an SDD
    system, Cholesky; n = 24 pads into the 32 bucket) through bucketed,
    padded services at 2 and 4 slots, serial and double-buffered, over
    1, 2 and 4 forced host device streams: every answer within 1e-9 of
    a direct solve()."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host devices: the child must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _MIX_PROG],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    worst = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(worst) == 5 and max(worst.values()) < 1e-9, worst

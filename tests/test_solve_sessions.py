"""Serving-layer suite for PR 8: the multi-round session ticket kind
and the settle submit/wait split.

Contracts under test:

* ``PendingBatchSolve`` analog handles are two-phase: ``wait_dc()``
  harvests the device DC phase, ``wait()`` composes the deferred
  settle sweep + fallback on top — and the composition equals the
  one-shot ``solve_batch`` result exactly.
* The service releases a stream slot at DC harvest (``finishing``
  queue) and runs settle/fallback afterwards — accounted in
  ``stats()['settle_finish_s']`` — without losing delivery parity.
* :class:`SolveSession` satisfies the ``rounds=`` protocol of
  :mod:`repro.optim.batched_newton`: a Newton run whose every round
  rides the service's bucketed pipelines matches the direct batched
  run, reuses ONE stamp pattern across rounds, preserves interleaved
  one-shot traffic, reports terminal per-ticket failures as
  :class:`SessionRoundError` with partial results, and recovers
  injected mid-loop device faults without perturbing the iterates.
* A mixed-grid FEM mesh stream served end-to-end keeps 1e-9 parity
  with direct solves.
"""

import numpy as np
import pytest

from repro.core.solver import solve, solve_batch, solve_batch_submit
from repro.data.spd import random_rhs_from_solution, random_spd
from repro.optim.batched_newton import (
    BatchedNewtonConfig,
    newton_batch,
    newton_looped,
)
from repro.serving import SessionRoundError, SolveService
from repro.serving.faults import FaultInjector, FaultPlan, SolveError

PARITY_ATOL = 1e-9


def _systems(bsz, n, seed=0):
    rng = np.random.default_rng(seed)
    a = np.stack([random_spd(rng, n) for _ in range(bsz)])
    xb = [random_rhs_from_solution(rng, a[k]) for k in range(bsz)]
    return a, np.stack([x for x, _ in xb]), np.stack([b for _, b in xb])


# --------------------------------------------------- two-phase handles
def test_analog_pending_is_split_and_composes_to_solve_batch():
    a, _, b = _systems(3, 5)
    ref = solve_batch(a, b, method="analog_2n", compute_settling=True)
    pending = solve_batch_submit(
        a, b, method="analog_2n", compute_settling=True
    )
    assert pending.split
    dc = pending.wait_dc()                 # device phase only
    assert dc.x.shape == b.shape
    assert dc.settle_time is None and "settle_method" not in dc.info
    full = pending.wait()                  # + settle sweep + fallback
    assert np.array_equal(full.x, ref.x)
    assert full.settle_time is not None and "settle_method" in full.info
    # the finish phase completes the DC batch in place; afterwards both
    # views are idempotent and return the final batch
    assert full is dc
    assert pending.wait() is full and pending.wait_dc() is full


def test_digital_pending_is_single_phase():
    a, _, b = _systems(2, 4, seed=1)
    pending = solve_batch_submit(a, b, method="cholesky")
    assert not pending.split
    assert pending.wait_dc() is pending.wait()


def test_wait_without_wait_dc_still_runs_both_phases():
    a, _, b = _systems(2, 4, seed=2)
    ref = solve_batch(a, b, method="analog_2n")
    pending = solve_batch_submit(a, b, method="analog_2n")
    assert np.array_equal(pending.wait().x, ref.x)


def test_injected_nonfinite_lands_after_the_finish_phase():
    """The chaos injector must corrupt the *delivered* batch on a split
    handle — wait_dc() stays clean, wait() carries the NaN (so the
    fallback cannot repair it and the service sees the corruption)."""
    a, _, b = _systems(2, 4, seed=3)
    pending = solve_batch_submit(a, b, method="analog_2n")
    inj = FaultInjector(FaultPlan(schedule=((0, "nonfinite"),)))
    inj.arm(pending, inj.draw())
    assert np.isfinite(pending.wait_dc().x).all()
    assert np.isnan(pending.wait().x[:, 0]).all()


# ------------------------------------------------- settle split in the service
def test_service_settle_split_accounts_and_keeps_parity():
    svc = SolveService(batch_slots=2)
    a, _, b = _systems(6, 5, seed=4)
    # half the stream requests the settle sweep: those micro-batches
    # must release their stream slot at DC harvest and finish later
    rids = [
        svc.submit(a[k], b[k], method="analog_2n",
                   compute_settling=(k % 2 == 0))
        for k in range(6)
    ]
    out = svc.drain()
    for k, rid in enumerate(rids):
        ref = solve(a[k], b[k], method="analog_2n")
        assert np.abs(out[rid].x - ref.x).max() <= PARITY_ATOL
        if k % 2 == 0:
            assert out[rid].settle_time is not None
    st = svc.stats
    # the settle/fallback work ran in deferred finish phases, after
    # each flight's stream slot was already released
    assert st["settle_finish_s"] > 0.0
    assert st["errors"] == {k: 0 for k in st["errors"]}


# ----------------------------------------------------------- session rounds
def test_session_round_validates_shapes():
    svc = SolveService(batch_slots=2)
    sess = svc.session(method="cholesky")
    with pytest.raises(ValueError, match="expected"):
        sess.solve_round(np.eye(4), np.ones(4))
    with pytest.raises(ValueError, match="expected"):
        sess.solve_round(np.ones((2, 4, 4)), np.ones((3, 4)))


def test_session_round_parity_and_counters():
    svc = SolveService(batch_slots=4)
    sess = svc.session(method="analog_2n")
    a, _, b = _systems(4, 5, seed=5)
    x = sess.solve_round(a, b)
    for k in range(4):
        ref = solve(a[k], b[k], method="analog_2n")
        assert np.abs(x[k] - ref.x).max() <= PARITY_ATOL
    assert sess.rounds == sess.solve_rounds == 1
    assert sess.systems == 4


def test_session_newton_matches_direct_batched_run():
    """The tentpole end-to-end: a Newton client whose rounds ride the
    service's bucketed pipelines converges identically to the direct
    solve_batch executor, on ONE pattern across all rounds."""
    rng = np.random.default_rng(6)
    bsz, n = 3, 5
    t = rng.normal(size=(bsz, n))
    m = rng.normal(size=(bsz, n, n)) / np.sqrt(n)
    q = 0.5 * np.einsum("bij,bkj->bik", m, m) + np.eye(n)
    eye = np.eye(n)

    def grad_hess(x):
        d = x - t
        return (
            np.einsum("bij,bj->bi", q, d) + d ** 3,
            q + (3.0 * d ** 2)[:, :, None] * eye,
        )

    cfg = BatchedNewtonConfig(method="analog_2n", tol=1e-9, max_iter=30)
    tr_direct = newton_batch(grad_hess, np.zeros((bsz, n)), cfg)

    svc = SolveService(batch_slots=4)
    sess = svc.session(method="analog_2n")
    tr_svc = newton_batch(grad_hess, np.zeros((bsz, n)), cfg, rounds=sess)

    assert tr_svc.converged.all()
    assert np.array_equal(tr_svc.iterations, tr_direct.iterations)
    assert np.abs(tr_svc.x - tr_direct.x).max() <= 1e-7
    assert tr_svc.iterations.max() >= 3          # genuinely multi-round
    assert tr_svc.solve_rounds == tr_svc.iterations.max()
    # one sparsity class across every round -> one pattern derivation
    assert sess.pattern_derivations == 1


def test_session_newton_matches_looped_reference():
    """Newton rounds through a session follow the one-system-at-a-time
    looped reference: the same iteration counts, iterates to last-ulp."""
    rng = np.random.default_rng(0)
    bsz, n = 6, 8
    t = rng.normal(size=(bsz, n))
    m = rng.normal(size=(bsz, n, n)) / np.sqrt(n)
    q = 0.5 * np.einsum("bij,bkj->bik", m, m) + np.eye(n)
    eye = np.eye(n)

    def grad_hess(x):
        d = x - t
        return (
            np.einsum("bij,bj->bi", q, d) + d ** 3,
            q + (3.0 * d ** 2)[:, :, None] * eye,
        )

    cfg = BatchedNewtonConfig(method="analog_2n", tol=1e-8)
    sess = SolveService(batch_slots=bsz).session(method="analog_2n")
    tr_svc = newton_batch(grad_hess, np.zeros((bsz, n)), cfg, rounds=sess)
    tr_ref = newton_looped(grad_hess, np.zeros((bsz, n)), cfg)

    assert tr_svc.converged.all()
    assert np.array_equal(tr_svc.iterations, tr_ref.iterations)
    assert np.abs(tr_svc.x - tr_ref.x).max() <= 1e-12


def test_session_preserves_interleaved_foreign_traffic():
    svc = SolveService(batch_slots=4)
    a1, _, b1 = _systems(1, 5, seed=7)
    foreign = svc.submit(a1[0], b1[0], method="cholesky")
    sess = svc.session(method="cholesky")
    a, _, b = _systems(3, 5, seed=8)
    x = sess.solve_round(a, b)
    assert np.isfinite(x).all()
    # the round's drain answered the one-shot ticket too; the session
    # parks it instead of dropping it
    assert foreign in sess.other_results
    ref = np.linalg.solve(a1[0], b1[0])
    assert np.abs(sess.other_results[foreign].x - ref).max() <= PARITY_ATOL


def test_session_round_error_carries_partial_solutions():
    svc = SolveService(batch_slots=4)
    sess = svc.session(method="analog_2n")
    a, _, b = _systems(3, 5, seed=9)
    a[1, 0, 0] = np.nan                    # one poisoned system
    with pytest.raises(SessionRoundError) as ei:
        sess.solve_round(a, b)
    err = ei.value
    assert err.round_index == 0
    assert set(err.errors) == {1}
    assert isinstance(err.errors[1], SolveError)
    assert np.isnan(err.x[1]).all()
    for k in (0, 2):                       # healthy rows still delivered
        ref = solve(a[k], b[k], method="analog_2n")
        assert np.abs(err.x[k] - ref.x).max() <= PARITY_ATOL
    assert sess.rounds == 1                # the round completed (failed)


def test_session_newton_recovers_injected_midloop_device_fault():
    """A device fault on a mid-loop round dispatch is retried/bisected
    by the service invisibly to the Newton client: zero terminal
    errors, iterates identical to the clean run."""
    rng = np.random.default_rng(10)
    bsz, n = 2, 5
    t = rng.normal(size=(bsz, n))
    eye = np.eye(n)

    def grad_hess(x):
        d = x - t
        return d + d ** 3, (1.0 + 3.0 * d ** 2)[:, :, None] * eye

    cfg = BatchedNewtonConfig(method="analog_2n", tol=1e-9, max_iter=30)
    clean_svc = SolveService(batch_slots=4)
    tr_clean = newton_batch(
        grad_hess, np.zeros((bsz, n)), cfg,
        rounds=clean_svc.session(method="analog_2n"),
    )

    inj = FaultInjector(FaultPlan(schedule=((1, "device_fault"),)))
    svc = SolveService(batch_slots=4, fault_injector=inj)
    tr = newton_batch(
        grad_hess, np.zeros((bsz, n)), cfg,
        rounds=svc.session(method="analog_2n"),
    )
    st = svc.stats
    assert st["fault_injections"] >= 1
    assert st["retries"] + st["bisections"] >= 1
    assert st["errors"] == {k: 0 for k in st["errors"]}
    assert tr.converged.all()
    assert np.array_equal(tr.iterations, tr_clean.iterations)
    assert np.abs(tr.x - tr_clean.x).max() <= 1e-12


# --------------------------------------------------------- FEM mesh stream
def test_fem_stream_through_service_parity():
    from repro.data.fem import mesh_stream

    meshes = list(mesh_stream(0, 10, grids=((4, 4), (5, 5), (6, 6))))
    svc = SolveService(batch_slots=4)
    rids = [svc.submit(m.a, m.b, method="analog_2n") for m in meshes]
    out = svc.drain()
    for rid, m in zip(rids, meshes):
        ref = solve(m.a, m.b, method="analog_2n")
        assert np.abs(out[rid].x - ref.x).max() <= PARITY_ATOL
    st = svc.stats
    assert st["requests"] == len(meshes)
    # one pattern per bucket: the fixed sparsity class per grid size
    assert all(
        b["pattern_derivations"] == 1 for b in st["buckets"].values()
    )

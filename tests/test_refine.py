"""Mixed-precision graded recovery (PR 9): the refinement drivers,
the quantized/noisy-hardware recovery grid, deterministic iteration
counts, the serving precision contract (precision paths, counter
split, ``unrefined`` fail-fast), warm-started sessions, and the bf16
settle sweep."""

import numpy as np
import pytest

from repro.core.operating_point import NonIdealities
from repro.core.refine import (
    DEFAULT_REFINE,
    RefineSpec,
    as_refine_spec,
    fcg_batch,
    refine_batch,
    relative_residuals,
)
from repro.core.solver import PRECISION_PATHS, solve_batch
from repro.data.spd import random_rhs_from_solution, random_sdd, random_spd
from repro.serving.faults import FaultInjector, FaultPlan, SolveError
from repro.serving.solve_service import SolveService, SolveSession

RECOVER_TOL = 1e-10
# research budget for the recovery grid: the serving default (12) is a
# latency contract that escalates slow rows to fallback; recovery to
# 1e-10 on the worst quantized rows needs up to ~16 passes
BUDGET = RefineSpec(tol=RECOVER_TOL, max_iters=24)


def _mixed_batch(seed: int, n: int = 10):
    """3 systems: non-SDD sparse SPD, SDD, dense SPD — the recovery
    claim must hold off the paper's diagonally-dominant class."""
    rng = np.random.default_rng(seed)
    aa, bb, xx = [], [], []
    for kind, density in (("spd", 0.5), ("sdd", 1.0), ("spd", 1.0)):
        a = (random_sdd(rng, n) if kind == "sdd"
             else random_spd(rng, n, density=density))
        x, b = random_rhs_from_solution(rng, a)
        aa.append(a)
        bb.append(b)
        xx.append(x)
    return np.stack(aa), np.stack(bb), np.stack(xx)


# ------------------------------------------------------------- spec API
def test_refine_spec_validation():
    with pytest.raises(ValueError):
        RefineSpec(tol=0.0)
    with pytest.raises(ValueError):
        RefineSpec(max_iters=0)
    with pytest.raises(ValueError):
        RefineSpec(driver="gmres")
    assert as_refine_spec(None) is None
    assert as_refine_spec(False) is None
    assert as_refine_spec(True) == DEFAULT_REFINE
    assert as_refine_spec("fcg").driver == "fcg"
    spec = RefineSpec(max_iters=5)
    assert as_refine_spec(spec) is spec
    with pytest.raises(TypeError):
        as_refine_spec(3)


# ------------------------------------------------- driver unit behavior
@pytest.mark.parametrize("driver", [refine_batch, fcg_batch])
def test_drivers_converge_with_noisy_inner_solve(driver):
    """A digital inner solve with per-row relative error converges to
    fp64, with per-row freezing (rows stop consuming inner solves the
    pass after they land under tol)."""
    a, b, x_true = _mixed_batch(3)
    # per-row error scale: row 0 nearly exact, row 2 a sloppy 20%
    noise = np.array([1e-8, 1e-2, 2e-1])
    calls = {"idx": []}

    def inner(idx, rhs):
        idx = np.asarray(idx)
        calls["idx"].append(idx.copy())
        d = np.stack([np.linalg.solve(a[i], r) for i, r in zip(idx, rhs)])
        rng = np.random.default_rng(len(calls["idx"]))
        pert = rng.standard_normal(d.shape)
        scale = noise[idx][:, None] * np.max(np.abs(d), axis=1)[:, None]
        return d + pert * scale / np.maximum(
            np.max(np.abs(pert), axis=1)[:, None], 1e-30)

    spec = RefineSpec(tol=1e-12, max_iters=40)
    res = driver(a, b, np.zeros_like(b), inner, spec=spec)
    assert bool(res.converged.all())
    assert float(res.residual.max()) <= 1e-12
    np.testing.assert_allclose(res.x, x_true, rtol=0.0, atol=1e-8)
    # per-row freezing: the near-exact row needs strictly fewer inner
    # solves than the sloppy row, and later calls carry fewer rows
    assert res.iters[0] < res.iters[2]
    assert len(calls["idx"][-1]) < len(calls["idx"][0])


def test_refine_reports_stall_on_non_contracting_inner():
    """An inner solve that returns junk cannot contract; the driver
    must report a stall instead of burning the budget."""
    a, b, _ = _mixed_batch(4)
    res = refine_batch(
        a, b, np.zeros_like(b),
        lambda idx, rhs: np.zeros_like(rhs),
        spec=RefineSpec(tol=1e-12, max_iters=10),
    )
    assert not bool(res.converged.any())
    assert bool(res.stalled.all())
    assert int(res.iters.max()) < 10   # stall detected, budget not burnt


# ------------------------------------------- quantized recovery grid
@pytest.mark.parametrize("bits,pot_tol", [
    (6, 0.0), (6, 0.01), (8, 0.0), (8, 0.01),
])
@pytest.mark.parametrize("method", ["analog_2n", "analog_n"])
def test_quantized_hardware_recovers_to_fp64(bits, pot_tol, method):
    """The acceptance grid: on quantized/noisy hardware both designs
    recover every system — including non-SDD SPD — to a 1e-10 fp64
    relative residual, through the analog path for 8-bit pots."""
    a, b, x_true = _mixed_batch(seed=10 * bits + int(100 * pot_tol))
    ni = NonIdealities(pot_bits=bits, pot_tol=pot_tol, seed=1)
    res = solve_batch(a, b, method=method, nonideal=ni, refine=BUDGET)
    rel = np.asarray(res.info["residual"])
    path = np.asarray(res.info["precision_path"])
    assert float(rel.max()) <= RECOVER_TOL
    np.testing.assert_allclose(res.x, x_true, rtol=0.0, atol=1e-7)
    assert set(path.tolist()) <= set(PRECISION_PATHS)
    # graded, never binary: every row carries its iteration count and
    # rows the driver did refine landed under tol without fallback
    refined = path == "refined"
    assert bool((np.asarray(res.info["refine_iters"])[refined] >= 1).all())


def test_fcg_recovers_int8_hardware_without_fallback():
    """FCG(1) accelerates past plain IR's stall heuristic: on 8-bit 1%
    hardware every system — including the dense SPD row IR escalates —
    recovers through the analog path alone."""
    a, b, x_true = _mixed_batch(81)
    ni = NonIdealities(pot_bits=8, pot_tol=0.01, seed=1)
    res = solve_batch(
        a, b, method="analog_2n", nonideal=ni,
        refine=RefineSpec(tol=RECOVER_TOL, max_iters=24, driver="fcg"),
    )
    path = np.asarray(res.info["precision_path"])
    assert set(path.tolist()) <= {"analog", "refined"}
    assert float(np.asarray(res.info["residual"]).max()) <= RECOVER_TOL
    np.testing.assert_allclose(res.x, x_true, rtol=0.0, atol=1e-7)


def test_int4_hardware_still_delivers_via_fallback():
    """4-bit pots at 5% tolerance are beyond refinement's reach — the
    graded path must escalate to digital fallback and still meet the
    residual contract."""
    a, b, _ = _mixed_batch(7)
    ni = NonIdealities(pot_bits=4, pot_tol=0.05, seed=2)
    res = solve_batch(a, b, method="analog_2n", nonideal=ni, refine=BUDGET)
    rel = np.asarray(res.info["residual"])
    path = np.asarray(res.info["precision_path"])
    assert float(rel.max()) <= RECOVER_TOL
    assert "fallback" in set(path.tolist())
    fb = np.asarray(res.info["fallback"])
    np.testing.assert_array_equal(fb != "", path == "fallback")


def test_refine_iteration_counts_are_deterministic():
    """Fixed seed -> identical perturbations -> bit-identical refined
    solutions and iteration counts across runs."""
    a, b, _ = _mixed_batch(11)
    ni = NonIdealities(pot_bits=8, pot_tol=0.01, seed=3)
    r1 = solve_batch(a, b, method="analog_2n", nonideal=ni, refine=BUDGET)
    r2 = solve_batch(a, b, method="analog_2n", nonideal=ni, refine=BUDGET)
    np.testing.assert_array_equal(r1.info["refine_iters"],
                                  r2.info["refine_iters"])
    np.testing.assert_array_equal(r1.x, r2.x)
    np.testing.assert_array_equal(r1.info["precision_path"],
                                  r2.info["precision_path"])


def test_unrefined_rows_survive_with_fallback_disabled():
    """fallback='none' + a starved budget: stalled rows are delivered
    as 'unrefined' with their honest residual, never silently."""
    a, b, _ = _mixed_batch(13)
    ni = NonIdealities(pot_bits=4, pot_tol=0.05, seed=4)
    res = solve_batch(
        a, b, method="analog_2n", nonideal=ni,
        refine=RefineSpec(tol=RECOVER_TOL, max_iters=2), fallback="none",
    )
    path = np.asarray(res.info["precision_path"])
    rel = np.asarray(res.info["residual"])
    assert "unrefined" in set(path.tolist())
    bad = path == "unrefined"
    assert bool(np.isfinite(rel[bad]).all()) and float(rel[bad].min()) > RECOVER_TOL


def test_refine_none_keeps_legacy_contract():
    """refine=None must leave the PR-7 binary fallback path untouched:
    no precision keys in info."""
    a, b, _ = _mixed_batch(17)
    res = solve_batch(a, b, method="analog_2n")
    assert "precision_path" not in res.info
    assert "refine_iters" not in res.info


# ------------------------------------------------------ precision grid
# (bits, pot_tol) -> (analog-path fraction, mean refinement passes) of
# the precision grid's cells, either sweep dtype, at the 1e-10 recovery
# contract; a cell may fall short of either by GRID_SLACK
PRECISION_CELLS = {
    (4, 0.0): (0.0, 2.8333333333333335),
    (4, 0.01): (0.0, 3.1666666666666665),
    (8, 0.0): (1.0, 5.666666666666667),
    (8, 0.01): (1.0, 6.833333333333333),
}
GRID_SLACK = 0.25


@pytest.mark.parametrize("sweep_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bits,pot_tol", list(PRECISION_CELLS))
def test_precision_grid_cell(bits, pot_tol, sweep_dtype):
    """One cell of the precision grid: six general SPD systems (n = 24,
    density 0.6) on seeded quantized/noisy hardware, graded recovery
    with the matrix-free settling probe against the raw analog answer.
    Every system recovers to 1e-10; on 8-bit 1% hardware all of them
    through the analog path alone, with no digital fallback."""
    rng = np.random.default_rng(0)
    a = np.stack([random_spd(rng, 24, density=0.6) for _ in range(6)])
    b = np.stack([random_rhs_from_solution(rng, ak)[1] for ak in a])
    ni = NonIdealities(pot_bits=bits, pot_tol=pot_tol, seed=0)
    raw = solve_batch(a, b, method="analog_2n", nonideal=ni, fallback="none")
    res = solve_batch(
        a, b, method="analog_2n", nonideal=ni, refine=BUDGET,
        fallback="cholesky", compute_settling=True, settle_method="euler",
        settle_matrix_free=True, x_ref=raw.x, settle_max_steps=100_000,
        sweep_dtype=sweep_dtype,
    )
    rel = np.asarray(res.info["residual"])
    path = np.asarray(res.info["precision_path"])
    analog_frac = float(np.mean(np.isin(path, ("analog", "refined"))))
    iters_mean = float(np.mean(res.info["refine_iters"]))
    want_frac, want_iters = PRECISION_CELLS[bits, pot_tol]
    assert float(rel.max()) <= RECOVER_TOL
    assert analog_frac >= (1.0 - GRID_SLACK) * want_frac
    assert iters_mean <= (1.0 + GRID_SLACK) * want_iters
    if (bits, pot_tol) == (8, 0.01):
        assert analog_frac == 1.0


# -------------------------------------------------- serving contract
def test_service_precision_contract_and_counters():
    svc = SolveService(batch_slots=4, refine=BUDGET)
    a, b, x_true = _mixed_batch(19)
    ni = NonIdealities(pot_bits=8, pot_tol=0.01, seed=5)
    rids = [svc.submit(a[k], b[k], nonideal=ni) for k in range(3)]
    out = svc.drain()
    st = svc.stats
    for k, rid in enumerate(rids):
        res = out[rid]
        assert not isinstance(res, SolveError)
        assert float(res.info["residual"]) <= RECOVER_TOL
        assert res.info["precision_path"] in ("analog", "refined")
        np.testing.assert_allclose(res.x, x_true[k], rtol=0.0, atol=1e-7)
    paths = st["precision_paths"]
    assert paths["refined"] + paths["analog"] == 3
    assert paths["fallback"] == 0 and paths["unrefined"] == 0
    assert st["refine_iters_total"] >= paths["refined"]
    assert st["fallbacks"] == 0 and st["fallbacks_injected"] == 0


def test_service_unrefined_is_fail_fast():
    """Budget-exhausted tickets with fallback disabled land as one
    SolveError(kind='unrefined') on the FIRST attempt — stalling is
    deterministic, so retrying would just re-stall."""
    svc = SolveService(
        batch_slots=4, fallback="none",
        refine=RefineSpec(tol=RECOVER_TOL, max_iters=2),
    )
    a, b, _ = _mixed_batch(23)
    ni = NonIdealities(pot_bits=4, pot_tol=0.05, seed=6)
    rids = [svc.submit(a[k], b[k], nonideal=ni) for k in range(3)]
    out = svc.drain()
    errs = [out[r] for r in rids if isinstance(out[r], SolveError)]
    assert errs, "starved budget must produce unrefined errors"
    for e in errs:
        assert e.kind == "unrefined"
        assert e.attempts == 1
    # unrefined is a terminal ERROR kind: it lands in the error
    # counters, never in the delivered-path histogram
    assert svc.stats["precision_paths"]["unrefined"] == 0
    assert svc.stats["errors"]["unrefined"] == len(errs)


def test_service_refine_exactly_once_under_faults():
    """Refinement coinciding with injected faults must not break
    exactly-once delivery, and injected corruption must be counted
    apart from genuine numerical fallbacks."""
    svc = SolveService(
        batch_slots=2, max_attempts=4, breaker_backoff_s=0.01,
        refine=BUDGET,
        fault_injector=FaultInjector(FaultPlan(
            seed=7, rates={"device_fault": 0.2, "nonfinite": 0.2},
        )),
    )
    a, b, x_true = _mixed_batch(29)
    rids = []
    ni = NonIdealities(pot_bits=8, pot_tol=0.01, seed=8)
    for rep in range(4):
        for k in range(3):
            rids.append(svc.submit(a[k], b[k], nonideal=ni))
    out = svc.drain()
    assert sorted(out.keys()) == sorted(rids)      # exactly once
    st = svc.stats
    assert st["fault_injections"] > 0
    delivered = [r for r in out.values() if not isinstance(r, SolveError)]
    for res in delivered:
        assert float(res.info["residual"]) <= RECOVER_TOL
    # a retried micro-batch re-runs clean: injected nonfinite passes
    # count into fallbacks_injected, never into the genuine counter
    assert st["fallbacks"] == 0
    assert st["fallbacks_injected"] >= 0


def test_service_rejects_bad_sweep_dtype_and_x0():
    svc = SolveService(batch_slots=2)
    a, b, _ = _mixed_batch(31)
    with pytest.raises(ValueError):
        svc.submit(a[0], b[0], sweep_dtype="float16")
    with pytest.raises(ValueError):
        svc.submit(a[0], b[0], x0=np.full(b.shape[1], np.nan))
    with pytest.raises(ValueError):
        svc.submit(a[0], b[0], x0=np.zeros(b.shape[1] + 1))


# ------------------------------------------------- warm-started rounds
def test_session_warm_start_reuses_previous_round():
    """warm_start=True feeds round k's solutions back as round k+1's
    initial sweep state: the warm rounds must settle in no more steps
    than the cold round (the systems drift by ~1% per round)."""
    svc = SolveService(batch_slots=4)
    sess = SolveSession(
        svc, warm_start=True,
        compute_settling=True, settle_method="euler",
        settle_max_steps=50_000,
    )
    rng = np.random.default_rng(37)
    a = np.stack([random_sdd(rng, 8) for _ in range(3)])
    x, b = zip(*(random_rhs_from_solution(rng, a[k]) for k in range(3)))
    b = np.stack(b)
    for _ in range(3):
        got = sess.solve_round(a, b)
        for k in range(3):
            ref = np.linalg.solve(a[k], b[k])
            np.testing.assert_allclose(got[k], ref, rtol=0.0, atol=1e-6)
        b = b * (1.0 + 0.01 * rng.standard_normal(b.shape))
    assert sess.rounds == 3
    assert sess.warm_submits == 6          # rounds 2 and 3, 3 tickets each
    steps = sess.settle_steps_by_round
    assert len(steps) == 3 and all(s is not None for s in steps)
    assert max(steps[1], steps[2]) <= steps[0] * 1.05


def test_session_cold_by_default():
    svc = SolveService(batch_slots=4)
    sess = SolveSession(svc)
    rng = np.random.default_rng(41)
    a = np.stack([random_sdd(rng, 8) for _ in range(2)])
    b = np.stack([random_rhs_from_solution(rng, a[k])[1] for k in range(2)])
    sess.solve_round(a, b)
    sess.solve_round(a, b)
    assert sess.warm_submits == 0


# ------------------------------------------------------ bf16 settling
def test_bf16_sweep_settles_and_matches_f32():
    """The bf16-storage/fp32-accumulate sweep must settle (inside the
    widened BF16 band) and deliver the same DC solution — fp64
    recovery past the band is refinement's job, not the sweep's."""
    rng = np.random.default_rng(43)
    a = np.stack([random_sdd(rng, 8) for _ in range(2)])
    xs, bs = zip(*(random_rhs_from_solution(rng, a[k]) for k in range(2)))
    b, x_ref = np.stack(bs), np.stack(xs)
    out = {}
    for dt in ("float32", "bfloat16"):
        out[dt] = solve_batch(
            a, b, method="analog_2n",
            compute_settling=True, settle_method="euler",
            settle_matrix_free=True, x_ref=x_ref,
            settle_max_steps=50_000, sweep_dtype=dt,
        )
        # finite settle_time == the sweep converged into its band
        assert bool(np.isfinite(np.asarray(out[dt].settle_time)).all())
        assert int(np.asarray(out[dt].info["settle_steps"]).max()) < 50_000
    np.testing.assert_allclose(out["bfloat16"].x, out["float32"].x,
                               rtol=0.0, atol=1e-9)


def test_relative_residuals_flags_nonfinite():
    a, b, x = _mixed_batch(47)
    rel = relative_residuals(a, b, x)
    assert float(rel.max()) < 1e-12
    x_bad = x.copy()
    x_bad[1, 0] = np.nan
    rel = relative_residuals(a, b, x_bad)
    assert np.isinf(rel[1]) and np.isfinite(rel[[0, 2]]).all()


def test_amplitude_settle_steps_tracks_initial_error():
    """The amplitude-aware bound: a warm start with little slow-mode
    content predicts far fewer steps than the blind cold-start bound,
    and unstable rows keep the blind bound."""
    from repro.core.spectral import SpectralBounds, amplitude_settle_steps

    nz = 4
    basis = np.zeros((2, 1, nz))
    basis[:, 0, 0] = 1.0                      # slow subspace = e0
    bounds = SpectralBounds(
        rate_max=np.full(2, 1e4),
        slow_re=np.array([-100.0, -100.0]),
        slow_residual=np.zeros(2),
        fov_slow=None, sym_max=None,
        dt_limit=np.full(2, 1e-3), dt=np.full(2, 1e-3),
        settle_time=np.full(2, np.log(100.0) / 100.0),
        settle_steps=np.full(2, 47.0),
        certified=np.ones(2, bool),
        slow_basis=basis,
    )
    cold = np.zeros((2, nz))
    cold[:, 0] = 1.0                          # full slow-mode amplitude
    warm = cold * np.array([1.0, 1e-3])[:, None]
    steps = amplitude_settle_steps(bounds, warm, rtol=0.01,
                                   x_scale=np.ones(2))
    assert steps[1] < steps[0]                # warm row needs fewer
    assert steps[1] <= 10.0
    # unstable row falls back to the blind bound
    bounds_u = SpectralBounds(
        **{**bounds.__dict__, "slow_re": np.array([-100.0, 1.0])}
    )
    steps_u = amplitude_settle_steps(bounds_u, warm, rtol=0.01,
                                     x_scale=np.ones(2))
    assert steps_u[1] == 47.0

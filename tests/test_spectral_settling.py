"""The corrected spectral settling estimator: 2x slow-mode accuracy on
both designs, abscissa-aware dt for underdamped operators, non-vacuous
stability certificates, the spectral sweep-chunk schedule, solve() /
solve_batch settling-kwarg parity, BatchSolveResult indexing, and the
CrosspointLayout DC round-trip."""

import zlib

import numpy as np
import pytest

from repro.core import engine, spectral
from repro.core.network import build_preliminary, build_proposed
from repro.core.solver import BatchSolveResult, solve, solve_batch
from repro.data.spd import random_sdd, random_spd, random_rhs_from_solution


def _batch(seed, n, count, *, builder=build_proposed, with_non_pd=False,
           with_sdd=False, density=1.0):
    rng = np.random.default_rng(seed)
    nets, xs = [], []
    for k in range(count):
        a = random_spd(rng, n, density=density)
        if with_non_pd and k == 1:
            a = -a
        if with_sdd and k == count - 1:
            a = random_sdd(rng, n, density=density)
        x, b = random_rhs_from_solution(rng, a)
        nets.append(builder(a, b))
        xs.append(x)
    return nets, np.stack(xs)


def _true_slow(m):
    lam = np.linalg.eigvals(m)
    return np.array([la.real[la.real < 0].max() for la in lam]), lam


# ------------------------------------------------- slow-mode accuracy
@pytest.mark.parametrize("builder", [build_proposed, build_preliminary])
def test_slow_mode_within_2x_of_eig(builder):
    """The tentpole contract: deflated slow-mode extraction lands within
    2x of the exact rightmost eigenvalue on the tier-1 reference set —
    both designs, non-diagonally-dominant SPD included."""
    nets, _ = _batch(47, 12, 4, builder=builder)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell)
    true_slow, _ = _true_slow(dense.m)
    ratio = sb.slow_re / true_slow
    assert np.all(sb.slow_re < 0)
    assert np.all((ratio > 0.5) & (ratio < 2.0)), ratio


# label -> (design, n, systems) of the settling-accuracy guard's set
REFERENCE_SET = {
    "proposed": (build_proposed, 14, 4),
    "proposed_sparse": (build_proposed, 20, 3),
    "preliminary": (build_preliminary, 12, 3),
    "sdd": (build_proposed, 12, 3),
    "non_pd": (build_proposed, 10, 3),
}


@pytest.mark.parametrize("label", list(REFERENCE_SET))
def test_slow_mode_within_2x_of_eig_reference_set(label):
    """The settling-accuracy guard's reference set: the slow-mode
    estimate stays within [0.5, 2.0]x of the exact rightmost eigenvalue
    (density 0.4 in ``proposed_sparse``, an SDD last system in ``sdd``),
    and the estimator flags the unstable system (``non_pd``: the last
    one negated)."""
    builder, n, count = REFERENCE_SET[label]
    rng = np.random.default_rng(zlib.crc32(label.encode()))
    nets = []
    for k in range(count):
        a = random_spd(rng, n,
                       density=0.4 if label == "proposed_sparse" else 1.0)
        if label == "non_pd" and k == count - 1:
            a = -a
        if label == "sdd" and k == count - 1:
            a = random_sdd(rng, n)
        _x, b = random_rhs_from_solution(rng, a)
        nets.append(builder(a, b))
    dense = engine.assemble_batch(nets)
    sb = spectral.spectral_bounds(engine.assemble_batch_ell(nets))
    lam = np.linalg.eigvals(dense.m)
    unstable = lam.real.max(axis=1) >= 0
    assert unstable.sum() == (label == "non_pd")
    assert np.all(sb.slow_re[unstable] >= 0)
    for k in np.flatnonzero(~unstable):
        ratio = sb.slow_re[k] / lam[k].real[lam[k].real < 0].max()
        assert 0.5 <= ratio <= 2.0, (k, ratio)


def test_slow_mode_settle_time_within_2x():
    """The settling-time prediction inherits the 2x band against the
    e-folding time of the exact slow mode."""
    nets, _ = _batch(31, 14, 4, with_sdd=True)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell)
    true_slow, _ = _true_slow(dense.m)
    t_exact = np.log(1.0 / 0.01) / (-true_slow)
    ratio = sb.settle_time / t_exact
    assert np.all((ratio > 0.5) & (ratio < 2.0)), ratio


# --------------------------------------------- abscissa-aware dt rule
def _underdamped(re, im, extra_real):
    blocks = [np.array([[re, im], [-im, re]])]
    blocks += [np.array([[r]]) for r in extra_real]
    n = sum(b.shape[0] for b in blocks)
    m = np.zeros((n, n))
    i = 0
    for b in blocks:
        k = b.shape[0]
        m[i:i + k, i:i + k] = b
        i += k
    return m


def test_abscissa_aware_dt_underdamped():
    """For |Im| >> |Re| pairs the modulus rule 2/|lambda|_max puts the
    Euler map outside the unit circle; the per-mode rule
    dt < 2|Re|/|lambda|^2 must keep every mode inside it."""
    batch = np.stack([
        _underdamped(-1e3, 1e7, [-2e6, -5e5, -1e4]),
        _underdamped(-5e4, 4e6, [-3e6, -1e5, -2e4]),
    ])
    sb = spectral.spectral_bounds(batch)
    lam = np.linalg.eigvals(batch)
    for b in range(batch.shape[0]):
        # the bare modulus rule demonstrably diverges on these...
        dt_mod = 2.0 * 0.5 / np.abs(lam[b]).max()
        assert np.abs(1.0 + dt_mod * lam[b]).max() > 1.0
        # ...while the abscissa-aware step contracts every mode
        assert np.abs(1.0 + sb.dt[b] * lam[b]).max() <= 1.0
    # and the slow mode is still exact on the synthetic spectrum
    true_slow = np.array([la.real[la.real < 0].max() for la in lam])
    np.testing.assert_allclose(sb.slow_re, true_slow, rtol=1e-6)


def test_mode_dt_reduces_to_modulus_rule_for_real_spectra():
    """On the circuit operators (overdamped settling modes) the mode
    rule must not collapse the step: dt stays within a small factor of
    the modulus rule."""
    nets, _ = _batch(61, 10, 3)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell, slow_iters=0)
    modulus = 2.0 * 0.5 / (sb.rate_max * spectral.RATE_MARGIN)
    assert np.all(sb.dt <= modulus * (1.0 + 1e-12))
    assert np.all(sb.dt > 0.1 * modulus)


# ------------------------------------------------------- certificates
def test_certificate_non_vacuous_on_circuit_operators():
    """The restricted numerical abscissa certifies stability where the
    global symmetric-part bound is vacuous (sym_max >> 0)."""
    nets, _ = _batch(47, 12, 4)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell, lanczos_iters=24)
    # global FoV bound: positive (vacuous) for these non-normal operators
    assert np.all(sb.sym_max > 0)
    # restricted certificate: negative, within a small factor of slow_re
    assert np.all(sb.fov_slow < 0)
    assert np.all(sb.certified)
    assert np.all(sb.slow_residual < 0.5)


def test_certificate_withheld_for_unstable_system():
    nets, _ = _batch(53, 10, 4, with_non_pd=True)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell)
    assert not sb.stable[1] and not sb.certified[1]
    assert np.isinf(sb.settle_time[1])
    assert sb.stable[[0, 2, 3]].all()
    # the unstable direction shows up in the restricted numerical range
    assert sb.fov_slow[1] > 0


def test_transient_batch_spectral_carries_certificates():
    nets, x = _batch(59, 12, 4, with_non_pd=True)
    tr = engine.transient_batch(nets, method="spectral", x_ref=x)
    assert tr.certified is not None
    assert not tr.certified[1]
    assert tr.certified[[0, 2, 3]].all()


# ----------------------------------------------- sweep chunk schedule
def test_sweep_chunk_schedule():
    from repro.kernels.ops import sweep_chunk_schedule

    # no finite prediction -> conservative floor
    assert sweep_chunk_schedule([np.inf, np.inf], 10_000) == 50
    # prediction drives the chunk to ~median/splits, clipped to bounds
    assert sweep_chunk_schedule([8000.0, 8000.0], 200_000) == 1000
    assert sweep_chunk_schedule([100.0], 200_000) == 50
    assert sweep_chunk_schedule([1e9], 200_000, ceil=4096) == 4096
    # ceil never exceeds max_steps
    assert sweep_chunk_schedule([1e9], 2000) == 2000


def test_euler_spectral_policy_uses_schedule_and_settles():
    """dt_policy='spectral' through transient_batch: abscissa-aware dt
    plus prediction-sized chunks still settle to the reference."""
    nets, x = _batch(83, 12, 3)
    tr = engine.transient_batch(
        nets, method="euler", x_ref=x, interpret=True,
        max_steps=120_000, dt_policy="spectral",
    )
    assert np.all(tr.stable)
    np.testing.assert_allclose(tr.x_converged, x, rtol=0.02, atol=1e-3)


# ------------------------------------- solve() settling-kwarg parity
def test_solve_forwards_settling_kwargs():
    """solve() must reach the euler/spectral paths exactly like a B=1
    solve_batch call (it used to drop the settle_* kwargs entirely)."""
    rng = np.random.default_rng(71)
    a = random_spd(rng, 8)
    x = rng.uniform(-0.5, 0.5, 8)
    b = a @ x

    for kwargs in (
        dict(settle_method="spectral", x_ref=x),
        dict(settle_method="euler", settle_dt_policy="spectral",
             settle_max_steps=120_000),
        dict(settle_method="euler", settle_matrix_free=True, x_ref=x,
             settle_max_steps=120_000),
    ):
        single = solve(a, b, compute_settling=True, **kwargs)
        kw_batch = dict(kwargs)
        if "x_ref" in kw_batch:
            kw_batch["x_ref"] = kw_batch["x_ref"][None, :]
        batched = solve_batch(
            a[None], b[None], compute_settling=True, **kw_batch
        )[0]
        assert single.info["settle_method"] == batched.info["settle_method"]
        assert single.stable == batched.stable
        np.testing.assert_allclose(single.x, batched.x, rtol=0, atol=0)
        np.testing.assert_allclose(
            single.settle_time, batched.settle_time, rtol=1e-12
        )


def test_solve_default_settling_matches_batch_default():
    """Default settle_method='auto' resolves identically for solve and
    solve_batch (exact modal path at this size)."""
    rng = np.random.default_rng(73)
    a = random_spd(rng, 6)
    x = rng.uniform(-0.5, 0.5, 6)
    b = a @ x
    single = solve(a, b, compute_settling=True)
    batched = solve_batch(a[None], b[None], compute_settling=True)[0]
    assert single.info["settle_method"] == "eig"
    np.testing.assert_allclose(
        single.settle_time, batched.settle_time, rtol=1e-12
    )


# ------------------------------------------ BatchSolveResult indexing
def test_batch_result_getitem_normalizes_mixed_info():
    """0-d arrays, shared python scalars, numpy scalars and per-system
    arrays all round-trip to clean python/per-system values."""
    res = BatchSolveResult(
        x=np.arange(6.0).reshape(3, 2),
        method="analog_2n",
        stable=np.array([True, False, True]),
        settle_time=np.array([1.0, np.inf, 3.0]),
        info={
            "per_system": np.array([10.0, 20.0, 30.0]),
            "per_system_vec": np.arange(12).reshape(3, 4),
            "shared_scalar": 42,
            "shared_str": "spectral",
            "shared_0d": np.array(7.5),
            "numpy_scalar": np.float64(2.5),
            "str_array": np.asarray(["a", "b", "c"]),
        },
    )
    one = res[1]
    assert one.info["per_system"] == 20.0
    np.testing.assert_array_equal(one.info["per_system_vec"], [4, 5, 6, 7])
    assert one.info["shared_scalar"] == 42
    assert one.info["shared_str"] == "spectral"
    # 0-d arrays and numpy scalars come back as python scalars
    assert one.info["shared_0d"] == 7.5
    assert type(one.info["shared_0d"]) is float
    assert type(one.info["numpy_scalar"]) is float
    assert one.info["str_array"] == "b"
    assert type(one.info["str_array"]) is str
    assert one.stable is False and one.settle_time == float("inf")


def test_batch_result_getitem_roundtrip_from_solve_batch():
    rng = np.random.default_rng(79)
    a = np.stack([random_spd(rng, 6) for _ in range(3)])
    x = rng.uniform(-0.5, 0.5, (3, 6))
    b = np.einsum("bij,bj->bi", a, x)
    out = solve_batch(a, b, compute_settling=True, settle_method="spectral",
                      x_ref=x)
    one = out[2]
    assert type(one.info["n_nodes"]) is int
    assert type(one.info["settle_method"]) is str
    assert type(one.info["max_rel_error"]) is float
    assert isinstance(one.info["settle_certified"], bool)


# ------------------------------------- CrosspointLayout DC round-trip
@pytest.mark.parametrize("seed", [3, 17])
def test_crosspoint_dc_operator_roundtrip_non_sdd(seed):
    """Layout -> dc_operator reproduces the engine-assembled DC operator
    on non-SDD SPD systems (negative external cells engaged)."""
    from repro.core.crosspoint import crosspoint_layout
    from repro.core.transform import transform_2n

    rng = np.random.default_rng(seed)
    a = random_spd(rng, 9)
    x, b = random_rhs_from_solution(rng, a)
    tr = transform_2n(a, b)
    lay = crosspoint_layout(tr)
    # the non-SDD path must exercise the external-cell sign branch
    assert np.asarray(lay.external_cells).max() > 0
    m_lay = np.asarray(lay.dc_operator())
    m_net = build_proposed(a, b).assemble_dc()
    scale = np.abs(m_net).max()
    np.testing.assert_allclose(m_lay, m_net, rtol=0, atol=1e-12 * scale)


def test_crosspoint_dc_operator_roundtrip_negative_b():
    """All-negative b flips every supply connection to the -rail; the
    round-trip must still match the engine assembly exactly."""
    from repro.core.crosspoint import crosspoint_layout
    from repro.core.transform import transform_2n

    rng = np.random.default_rng(23)
    a = random_spd(rng, 7)
    x = -np.abs(rng.uniform(0.1, 0.5, 7))
    b = a @ x
    # ensure the sign path is hit on every component
    b = -np.abs(b)
    x = np.linalg.solve(a, b)
    tr = transform_2n(a, b)
    lay = crosspoint_layout(tr)
    assert np.all(np.asarray(tr.b_sign) < 0)
    m_lay = np.asarray(lay.dc_operator())
    m_net = build_proposed(a, b).assemble_dc()
    scale = np.abs(m_net).max()
    np.testing.assert_allclose(m_lay, m_net, rtol=0, atol=1e-12 * scale)

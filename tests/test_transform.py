"""Property tests for the 2n transform (Eqs. 13-23) — hypothesis-driven."""

import numpy as np
import pytest
from _hyp_compat import example, given, settings, st

from repro.core.transform import (
    assemble_2n,
    column_abs_sums,
    d_matrix_proposed,
    eigen_split,
    scale_system,
    stability_condition,
    supply_conductance,
    transform_2n,
)
from repro.data.spd import random_spd, random_sdd, random_rhs_from_solution

US = 1e-6


def _sys(seed, n, density=1.0):
    r = np.random.default_rng(seed)
    a = random_spd(r, n, density=density)
    x, b = random_rhs_from_solution(r, a)
    return a, x, b


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 24))
def test_transform_recovers_solution(seed, n):
    """Solving the transformed 2n system yields [x; -x] exactly."""
    a, x, b = _sys(seed, n)
    tr = transform_2n(a, b)
    m = np.asarray(tr.assembled())
    rhs = np.asarray(tr.rhs())
    y = np.linalg.solve(m, rhs)
    np.testing.assert_allclose(y[:n], x, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(y[n:], -x, rtol=1e-8, atol=1e-10)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 24))
def test_transform_preserves_pd(seed, n):
    """SPD input -> PD transformed operator (Eq. 17-20)."""
    a, x, b = _sys(seed, n)
    tr = transform_2n(a, b)
    m = np.asarray(tr.assembled())
    ev = np.linalg.eigvalsh((m + m.T) / 2)
    assert ev.min() > -1e-12 * max(abs(ev).max(), 1.0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 20))
def test_eigen_split(seed, n):
    """spec(K_2n) = spec(K_A+K_B) U spec(K_A-K_B)  (Eq. 17), and the
    difference block reproduces spec(A)."""
    a, x, b = _sys(seed, n)
    tr = transform_2n(a, b)
    lam_minus, lam_plus = (np.asarray(v) for v in eigen_split(tr))
    m = np.asarray(tr.assembled())
    ev_full = np.sort(np.linalg.eigvalsh((m + m.T) / 2))
    ev_split = np.sort(np.concatenate([lam_minus, lam_plus]))
    np.testing.assert_allclose(ev_full, ev_split, rtol=1e-7, atol=1e-12)
    np.testing.assert_allclose(
        np.sort(lam_minus), np.sort(np.linalg.eigvalsh(a)), rtol=1e-7, atol=1e-12)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 24))
def test_off_diagonals_nonpositive(seed, n):
    """All off-diagonals of K_A and K_B are <= 0: at most n negative-
    resistance cells (the diagonal of K_B) — the paper's key claim."""
    a, x, b = _sys(seed, n)
    tr = transform_2n(a, b)
    for blk in (np.asarray(tr.k_a), np.asarray(tr.k_b)):
        off = blk - np.diag(np.diag(blk))
        assert off.max() <= 1e-12 * max(abs(blk).max(), 1.0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 20))
def test_column_sum_support_structure(seed, n):
    """Under the proposed D (Eq. 22) the (K_A + K_B) column sums vanish
    except column 1 (= k_s1): only nodes 1 and n+1 touch ground."""
    a, x, b = _sys(seed, n)
    tr = transform_2n(a, b)
    cs = np.asarray(tr.k_a + tr.k_b).sum(axis=0)
    scale = abs(np.asarray(tr.k_a)).max()
    np.testing.assert_allclose(cs[1:], 0.0, atol=1e-12 * scale)
    np.testing.assert_allclose(cs[0], np.asarray(tr.k_s)[0], rtol=1e-9)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 16),
       alpha=st.floats(1e-3, 1e3))
def test_scaling_invariance(seed, n, alpha):
    """Eq. 27: scaling all conductances leaves the solution unchanged."""
    a, x, b = _sys(seed, n)
    tr = scale_system(transform_2n(a, b), alpha)
    m = np.asarray(tr.assembled())
    rhs = np.asarray(tr.rhs())      # k_s is scaled -> rhs is alpha*b already
    y = np.linalg.solve(m, rhs)
    np.testing.assert_allclose(y[:n], x, rtol=1e-6, atol=1e-9)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 20))
def test_stability_condition_satisfied(seed, n):
    """The proposed D satisfies Eq. 20 with equality margin >= 0."""
    a, x, b = _sys(seed, n)
    k_s = np.asarray(supply_conductance(b))
    d = np.asarray(d_matrix_proposed(a, k_s))
    margin = np.asarray(stability_condition(a, k_s, d))
    assert margin.min() >= -1e-12 * abs(a).max()


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(3, 20))
@example(seed=7168, n=4)    # support node: A_00 < 2 K_s00 + off-diagonal sum
def test_sdd_gives_nonpositive_kb_diag(seed, n):
    """Diagonally dominant systems (Eq. 25) need no op-amps."""
    r = np.random.default_rng(seed)
    a = random_sdd(r, n)
    x, b = random_rhs_from_solution(r, a)
    tr = transform_2n(a, b)
    assert np.asarray(tr.negative_cell_conductances()).max() <= 1e-18


def test_colsum_matches_numpy():
    a = np.random.default_rng(1).standard_normal((17, 17))
    np.testing.assert_allclose(
        np.asarray(column_abs_sums(a)), np.abs(a).sum(axis=0), rtol=1e-12)


def test_assemble_shape():
    a, x, b = _sys(3, 7)
    tr = transform_2n(a, b)
    m = assemble_2n(tr.k_a, tr.k_b)
    assert m.shape == (14, 14)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m).T, rtol=1e-12)

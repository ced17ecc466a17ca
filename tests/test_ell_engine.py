"""Matrix-free ELL engine: assembly/SpMV/sweep parity with the dense
path, the no-dense-materialization guarantee, the fill-ratio fallback
switch, and the spectral settling bounds."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import engine, spectral
from repro.core.network import build_preliminary, build_proposed
from repro.data.spd import random_sdd, random_spd, random_rhs_from_solution

from tests._hyp_compat import given, settings, st


def _batch(seed, n, count, *, builder=build_proposed, with_non_pd=False,
           with_sdd=False, density=1.0):
    rng = np.random.default_rng(seed)
    nets, xs = [], []
    for k in range(count):
        a = random_spd(rng, n, density=density)
        if with_non_pd and k == 1:
            a = -a                       # Fig. 8 protocol: destabilized
        if with_sdd and k == count - 1:
            a = random_sdd(rng, n, density=density)
        # x is drawn exactly and b = A x formed from it (valid for the
        # sign-flipped and SDD variants too) — no solve needed
        x, b = random_rhs_from_solution(rng, a)
        nets.append(builder(a, b))
        xs.append(x)
    return nets, np.stack(xs)


# ------------------------------------------------------------- assembly
@pytest.mark.parametrize("builder", [build_proposed, build_preliminary])
def test_ell_assembly_matches_dense(builder):
    """ELL assembly reproduces the dense operator to f64 round-off,
    both designs, non-PD and SDD systems included."""
    nets, _ = _batch(7, 11, 5, builder=builder, with_non_pd=True,
                     with_sdd=True)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    scale = np.abs(dense.m).max()
    np.testing.assert_allclose(ell.to_dense(), dense.m, rtol=0.0,
                               atol=1e-12 * scale)
    np.testing.assert_allclose(np.asarray(ell.c), dense.c, rtol=1e-12)
    assert ell.ell_width < ell.n_states          # actually sparse
    assert np.array_equal(ell.amp_active, dense.amp_active)


def test_ell_assembly_v_os_and_no_buffers():
    nets, _ = _batch(9, 8, 3)
    rng = np.random.default_rng(1)
    v_os = [rng.normal(0.0, 1e-3, size=net.n_amps) for net in nets]
    for kw in ({"v_os": v_os}, {"buffers": False}):
        dense = engine.assemble_batch(nets, **kw)
        ell = engine.assemble_batch_ell(nets, **kw)
        scale = np.abs(dense.m).max()
        np.testing.assert_allclose(ell.to_dense(), dense.m, rtol=0.0,
                                   atol=1e-12 * scale)
        np.testing.assert_allclose(np.asarray(ell.c), dense.c, rtol=1e-12)


def test_ell_spmv_matches_dense_matvec():
    """The gathered row reduction is the dense matvec to ~1e-12 (f64)."""
    nets, _ = _batch(13, 10, 4, with_sdd=True)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    rng = np.random.default_rng(2)
    z = rng.standard_normal((len(nets), ell.n_states))
    want = np.einsum("bij,bj->bi", dense.m, z)
    got = np.asarray(ell.matvec(jnp.asarray(z)))
    np.testing.assert_allclose(got, want, rtol=1e-12,
                               atol=1e-12 * np.abs(want).max())
    want_t = np.einsum("bij,bi->bj", dense.m, z)
    got_t = np.asarray(ell.matvec_t(jnp.asarray(z)))
    np.testing.assert_allclose(got_t, want_t, rtol=1e-12,
                               atol=1e-12 * np.abs(want_t).max())
    # block form (the spectral subspace iteration's workhorse)
    zb = rng.standard_normal((len(nets), 5, ell.n_states))
    want_b = np.einsum("bij,bkj->bki", dense.m, zb)
    got_b = np.asarray(ell.matvec_block(jnp.asarray(zb)))
    np.testing.assert_allclose(got_b, want_b, rtol=1e-12,
                               atol=1e-12 * np.abs(want_b).max())
    np.testing.assert_allclose(
        np.asarray(ell.diagonal()),
        np.diagonal(dense.m, axis1=1, axis2=2),
        rtol=1e-12,
    )


@settings(max_examples=8, deadline=None)
@given(
    n=st.integers(min_value=3, max_value=17),     # non-block-multiple sizes
    seed=st.integers(min_value=0, max_value=2**16),
    preliminary=st.booleans(),
)
def test_ell_assembly_parity_property(n, seed, preliminary):
    """Property: for any size (far from any kernel block multiple),
    seed and design, ELL == dense assembly to f64 round-off."""
    builder = build_preliminary if preliminary else build_proposed
    nets, _ = _batch(seed, n, 2, builder=builder, with_non_pd=(n % 2 == 0))
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    scale = np.abs(dense.m).max()
    np.testing.assert_allclose(ell.to_dense(), dense.m, rtol=0.0,
                               atol=1e-12 * scale)


# ---------------------------------------------------------------- sweep
def test_ell_sweep_matches_dense_sweep():
    """Same dt, same step counts, f32-level state agreement between the
    ELL-SpMV sweep and the dense Pallas sweep."""
    nets, x = _batch(29, 16, 4)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    sd, xd, rd, dtd = engine.euler_settle_batch(
        dense, x, max_steps=40_000, interpret=True
    )
    se, xe, re_, dte = engine.euler_settle_batch(
        ell, x, max_steps=40_000, interpret=True
    )
    np.testing.assert_array_equal(sd, se)
    np.testing.assert_allclose(dtd, dte, rtol=1e-12)
    np.testing.assert_allclose(xe, xd, rtol=0.0, atol=2e-5)
    assert np.all(se < 40_000)
    np.testing.assert_allclose(xe, x, rtol=0.02, atol=1e-3)


PARITY_SIZES = (16, 48)


def _parity_sweep_batch(n):
    """The n-sweep's three systems at size ``n``, the second negated
    (non-PD): one generator (seed 123) draws every size in order."""
    rng = np.random.default_rng(123)
    for size in PARITY_SIZES[:PARITY_SIZES.index(n) + 1]:
        nets, xs = [], []
        for k in range(3):
            a = random_spd(rng, size)
            if k == 1:
                a = -a
            x, b = random_rhs_from_solution(rng, a)
            nets.append(build_proposed(a, b))
            xs.append(x)
    return nets, np.stack(xs)


@pytest.mark.parametrize("n", PARITY_SIZES)
def test_ell_sweep_parity_over_n_sweep(n):
    """Dense <-> ELL drift over an n-sweep with a non-PD system: assembly
    to f64 round-off, identical step counts (the non-PD system runs out
    the budget on both paths), f32-level states of the settled ones."""
    nets, x = _parity_sweep_batch(n)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    scale = np.abs(dense.m).max()
    np.testing.assert_allclose(ell.to_dense(), dense.m, rtol=0.0,
                               atol=1e-12 * scale)
    sd, xd, _, _ = engine.euler_settle_batch(dense, x, max_steps=40_000)
    se, xe, _, _ = engine.euler_settle_batch(ell, x, max_steps=40_000)
    np.testing.assert_array_equal(sd, se)
    assert sd[1] == 40_000 and np.all(sd[[0, 2]] < 40_000)
    np.testing.assert_allclose(xe[[0, 2]], xd[[0, 2]], rtol=0.0, atol=2e-5)


def test_ell_sweep_non_block_multiple_n():
    """Regression: ELL padding is exact for nz far from 128 multiples.
    Every launch is counted by mode (here all interpreted)."""
    from repro.kernels.ops import KERNEL_STATS

    nets, x = _batch(31, 7, 3)                    # nz = 58
    ell = engine.assemble_batch_ell(nets)
    assert ell.n_states % 128 != 0
    launches = dict(KERNEL_STATS)
    steps, x_final, res, dt = engine.euler_settle_batch(
        ell, x, max_steps=40_000, interpret=True
    )
    assert KERNEL_STATS["interpreted"] > launches["interpreted"]
    assert KERNEL_STATS["compiled"] == launches["compiled"]
    assert np.all(steps < 40_000)
    np.testing.assert_allclose(x_final, x, rtol=0.02, atol=1e-3)
    assert np.all(res >= 0.0)


def test_ell_path_never_materializes_dense(monkeypatch):
    """Shape spy: the ELL assemble+sweep path allocates nothing of size
    (B, nz, nz) — in numpy or in jnp — and never calls to_dense."""
    nets, x = _batch(37, 12, 3)
    pat = engine.pattern_union(nets)
    nz = pat.n_states
    forbidden = []

    def spy(fn):
        def wrapped(shape, *a, **kw):
            s = tuple(shape) if isinstance(shape, (tuple, list)) else (shape,)
            if len(s) == 3 and s[1] >= nz and s[2] >= nz:
                forbidden.append(s)
            return fn(shape, *a, **kw)
        return wrapped

    monkeypatch.setattr(np, "zeros", spy(np.zeros))
    monkeypatch.setattr(np, "empty", spy(np.empty))
    monkeypatch.setattr(jnp, "zeros", spy(jnp.zeros))
    monkeypatch.setattr(
        engine.EllBatchedStateSpace, "to_dense",
        lambda self: (_ for _ in ()).throw(
            AssertionError("to_dense on the matrix-free path")),
    )

    ell = engine.assemble_batch_ell(nets)
    steps, x_final, _res, _dt = engine.euler_settle_batch(
        ell, x, max_steps=20_000, interpret=True
    )
    assert forbidden == []
    assert np.all(steps < 20_000)
    np.testing.assert_allclose(x_final, x, rtol=0.02, atol=1e-3)


def test_ell_dense_fallback_switch(monkeypatch):
    """With the fill cutoff forced to zero the ELL state space densifies
    and still produces identical settling."""
    from repro.kernels import ops

    nets, x = _batch(41, 10, 3)
    ell = engine.assemble_batch_ell(nets)
    s1, x1, _r1, dt1 = engine.euler_settle_batch(
        ell, x, max_steps=40_000, interpret=True
    )
    monkeypatch.setattr(ops, "ELL_FILL_CUTOFF", 0.0)
    s2, x2, _r2, dt2 = engine.euler_settle_batch(
        ell, x, max_steps=40_000, interpret=True
    )
    np.testing.assert_array_equal(s1, s2)
    np.testing.assert_allclose(dt1, dt2, rtol=1e-12)
    np.testing.assert_allclose(x1, x2, rtol=0.0, atol=2e-5)


def test_transient_batch_euler_matrix_free():
    """method='euler' with x_ref runs assembly+sweep matrix-free and
    converges to the reference."""
    nets, x = _batch(43, 12, 3)
    tr = engine.transient_batch(
        nets, method="euler", x_ref=x, interpret=True, max_steps=40_000
    )
    assert tr.method == "euler"
    assert np.all(tr.stable)
    np.testing.assert_allclose(tr.x_converged, x, rtol=0.02, atol=1e-3)


# ------------------------------------------------------------- spectral
def test_spectral_bounds_against_exact_eig():
    """Power-iteration rate within ~15% of |lambda|_max; the deflated
    slow-mode estimate within the 2x accuracy contract (see
    tests/test_spectral_settling.py for the full contract suite)."""
    nets, x = _batch(47, 14, 4)
    dense = engine.assemble_batch(nets)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell)

    lam = np.linalg.eigvals(dense.m)
    true_rate = np.abs(lam).max(axis=1)
    # for a non-normal operator the power-iteration norm ratio sits
    # between |lambda|_max and sigma_max — overestimates are the safe
    # direction (smaller dt)
    assert np.all(sb.rate_max > 0.6 * true_rate)
    assert np.all(sb.rate_max < 3.0 * true_rate)
    # forward-Euler stability: dt * |lambda|_max < 2, per-mode circle
    # condition over the exact spectrum
    assert np.all(sb.dt * true_rate < 2.0)
    for b in range(len(nets)):
        assert np.abs(1.0 + sb.dt[b] * lam[b]).max() <= 1.0 + 1e-9
    assert np.all(sb.stable)

    true_slow = np.array([la.real[la.real < 0].max() for la in lam])
    assert np.all(sb.slow_re < 0)
    ratio = sb.slow_re / true_slow
    assert np.all((ratio > 0.5) & (ratio < 2.0))

    # settling prediction vs the exact modal settling criterion: the
    # e-folding estimate is amplitude-blind, so this band stays wider
    # than the eigenvalue band — but orders of magnitude tighter than
    # the old estimator's
    tr = engine.transient_batch(nets, method="eig")
    ratio_t = sb.settle_time / tr.settle_time
    assert np.all((ratio_t > 0.2) & (ratio_t < 5.0))


def test_spectral_flags_unstable_system():
    nets, x = _batch(53, 10, 4, with_non_pd=True)
    ell = engine.assemble_batch_ell(nets)
    sb = spectral.spectral_bounds(ell)
    assert not sb.stable[1]
    assert np.isinf(sb.settle_time[1])
    assert sb.stable[[0, 2, 3]].all()


def test_transient_batch_spectral_method():
    nets, x = _batch(59, 12, 4, with_non_pd=True)
    tr = engine.transient_batch(nets, method="spectral", x_ref=x)
    assert tr.method == "spectral"
    assert not tr.stable[1]
    assert tr.settle_time[1] == np.inf
    assert tr.stable[[0, 2, 3]].all()
    assert np.all(np.isfinite(tr.settle_time[[0, 2, 3]]))
    np.testing.assert_allclose(tr.x_converged[0], x[0])
    assert np.all(np.isnan(tr.x_converged[1]))


def test_euler_spectral_dt_policy():
    """The spectral dt rule integrates stably and settles to the same
    solution (often in fewer steps than the diagonal rule)."""
    nets, x = _batch(61, 12, 3)
    ell = engine.assemble_batch_ell(nets)
    sd, xd, _r, dt_d = engine.euler_settle_batch(
        ell, x, max_steps=60_000, interpret=True, dt_policy="diag"
    )
    ss, xs_, _r, dt_s = engine.euler_settle_batch(
        ell, x, max_steps=60_000, interpret=True, dt_policy="spectral"
    )
    assert np.all(sd < 60_000) and np.all(ss < 60_000)
    np.testing.assert_allclose(xd, x, rtol=0.02, atol=1e-3)
    np.testing.assert_allclose(xs_, x, rtol=0.02, atol=1e-3)
    assert np.all(dt_s > 0) and np.all(np.isfinite(dt_s))


def test_solve_batch_spectral_settle_method():
    """solve_batch(settle_method='spectral') returns stability flags and
    settle estimates without integrating."""
    from repro.core.solver import solve_batch

    rng = np.random.default_rng(67)
    a = np.stack([random_spd(rng, 10) for _ in range(3)])
    x = np.stack([rng.uniform(-0.5, 0.5, 10) for _ in range(3)])
    b = np.einsum("bij,bj->bi", a, x)
    out = solve_batch(
        a, b, compute_settling=True, settle_method="spectral", x_ref=x
    )
    assert out.info["settle_method"] == "spectral"
    assert np.all(out.stable)
    assert np.all(np.isfinite(out.settle_time))

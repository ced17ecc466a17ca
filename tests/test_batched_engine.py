"""Batched engine: batch/single parity, Pallas sweep kernels, stamp cache."""

import numpy as np
import pytest

from repro.core import engine
from repro.core.network import build_preliminary, build_proposed
from repro.core.operating_point import DEFAULT_NONIDEAL, operating_point
from repro.core.solver import solve, solve_batch
from repro.core.transient import lti_transient
from repro.data.spd import random_sdd, random_spd, random_rhs_from_solution


def _batch(seed, n, count, *, with_non_pd=False, with_sdd=False):
    """Stacked paper-protocol systems, optionally salted with edge cases."""
    rng = np.random.default_rng(seed)
    a_list, x_list, b_list = [], [], []
    for _ in range(count):
        a = random_spd(rng, n)
        x, b = random_rhs_from_solution(rng, a)
        a_list.append(a), x_list.append(x), b_list.append(b)
    if with_non_pd:
        # Fig. 8 protocol: flipping the sign destabilizes the circuit
        a_list[1], b_list[1] = -a_list[1], -b_list[1]
        x_list[1] = np.linalg.solve(a_list[1], b_list[1])
    if with_sdd:
        a_sdd = random_sdd(rng, n)
        x_sdd, b_sdd = random_rhs_from_solution(rng, a_sdd)
        a_list[2], x_list[2], b_list[2] = a_sdd, x_sdd, b_sdd
    return np.stack(a_list), np.stack(x_list), np.stack(b_list)


@pytest.mark.parametrize("method", ["analog_2n", "analog_n"])
def test_solve_batch_matches_solve(method):
    """Acceptance: a 64-system n=20 batch matches per-system solve to
    1e-8 on x (and on stability/settle_time), non-PD system included."""
    count = 64 if method == "analog_2n" else 16   # analog_n is O(n^2) states
    a, x, b = _batch(7, 20, count, with_non_pd=True, with_sdd=True)
    batch = solve_batch(
        a, b, method=method, x_ref=x, compute_settling=True,
        settle_method="eig",
    )
    assert len(batch) == count
    for k in range(count):
        single = solve(
            a[k], b[k], method=method, x_ref=x[k], compute_settling=True
        )
        np.testing.assert_allclose(
            batch.x[k], single.x, rtol=0.0, atol=1e-8
        )
        assert bool(batch.stable[k]) == single.stable
        st_b, st_s = float(batch.settle_time[k]), float(single.settle_time)
        if np.isfinite(st_s):
            np.testing.assert_allclose(st_b, st_s, rtol=1e-6)
        else:
            assert not np.isfinite(st_b)
        np.testing.assert_allclose(
            batch.info["err_fullscale"][k],
            single.info["err_fullscale"],
            rtol=1e-6, atol=1e-12,
        )


def test_solve_batch_flags_non_pd_unstable():
    a, x, b = _batch(11, 10, 4, with_non_pd=True)
    batch = solve_batch(a, b, method="analog_2n", compute_settling=True)
    assert not batch.stable[1]
    assert batch.settle_time[1] == np.inf
    assert np.all(batch.stable[[0, 2, 3]])
    assert np.all(np.isfinite(batch.settle_time[[0, 2, 3]]))


def test_operating_point_batch_nonideal_parity():
    """The hardware error model (quantization/offsets) draws per system
    exactly as the single path does."""
    from repro.core.operating_point import operating_point_batch

    a, x, b = _batch(13, 12, 6)
    nets = [build_proposed(a[k], b[k]) for k in range(6)]
    op_b = operating_point_batch(
        nets, nonideal=DEFAULT_NONIDEAL, x_ref=x
    )
    for k in range(6):
        op_s = operating_point(nets[k], nonideal=DEFAULT_NONIDEAL, x_ref=x[k])
        np.testing.assert_allclose(op_b.x[k], op_s.x, rtol=0.0, atol=1e-9)
        assert bool(op_b.amp_saturated[k]) == op_s.amp_saturated
        np.testing.assert_allclose(
            float(op_b.err_fullscale[k]), op_s.err_fullscale, rtol=1e-6
        )


@pytest.mark.parametrize("builder", [build_proposed, build_preliminary])
def test_dc_solve_refines_f32_lu_to_f64(builder):
    """The device DC solve (f32 LU + f64 refinement) matches host f64
    ``np.linalg.solve(M, -c)`` far inside the service's 1e-9 parity,
    with no system left for the host repair path."""
    a, x, b = _batch(23, 16, 6, with_sdd=True)
    bss = engine.assemble_batch([builder(a[k], b[k]) for k in range(6)])
    resolves = engine.DC_STATS["host_resolves"]
    z = engine.dc_solve_batch(bss)
    ref = np.linalg.solve(bss.m, -bss.c[..., None])[..., 0]
    rel = np.max(np.abs(z - ref), axis=1) / np.max(np.abs(ref), axis=1)
    assert np.max(rel) <= 1e-12
    assert engine.DC_STATS["host_resolves"] == resolves


def test_dc_solve_counts_host_resolves_of_singular_systems():
    """A singular operator cannot refine: its row comes back non-finite
    from the device, is re-solved with the tiny leakage on the host and
    counted; its batch-mates keep their fp64 device result."""
    import dataclasses

    a, x, b = _batch(29, 8, 3)
    bss = engine.assemble_batch([build_proposed(a[k], b[k]) for k in range(3)])
    m = bss.m.copy()
    m[1, :, 0] = 0.0                     # state 0 of system 1 floats
    singular = dataclasses.replace(bss, m=m)
    resolves = engine.DC_STATS["host_resolves"]
    z = engine.dc_solve_batch(singular)
    assert engine.DC_STATS["host_resolves"] == resolves + 1
    assert np.all(np.isfinite(z))
    ref = np.linalg.solve(bss.m[[0, 2]], -bss.c[[0, 2], :, None])[..., 0]
    rel = np.max(np.abs(z[[0, 2]] - ref), axis=1) / np.max(np.abs(ref), axis=1)
    assert np.max(rel) <= 1e-12


def test_pattern_cache_reused_across_batches():
    """Proposed-design patterns depend only on (n, design)."""
    a1, x1, b1 = _batch(17, 8, 3)
    a2, x2, b2 = _batch(19, 8, 5)
    nets1 = [build_proposed(a1[k], b1[k]) for k in range(3)]
    nets2 = [build_proposed(a2[k], b2[k]) for k in range(5)]
    p1 = engine.pattern_union(nets1)
    p2 = engine.pattern_union(nets2)
    assert p1 is p2          # cache hit: same object
    assert p1.n_pair_slots == 8


def test_mixed_cell_population_under_union_pattern():
    """A batch mixing fully-passive (SDD) and cell-bearing systems uses
    the same pattern; inactive slots must not perturb the physics."""
    a, x, b = _batch(23, 10, 4, with_sdd=True)
    nets = [build_proposed(a[k], b[k]) for k in range(4)]
    assert any(net.is_passive for net in nets)
    assert any(not net.is_passive for net in nets)
    tr = engine.transient_batch(nets, method="eig")
    for k in range(4):
        single = lti_transient(nets[k])
        np.testing.assert_allclose(
            tr.x_converged[k], single.x_converged, rtol=0.0, atol=1e-8
        )
        np.testing.assert_allclose(
            tr.settle_time[k], single.settle_time, rtol=1e-6
        )


def test_euler_sweep_settles_to_reference():
    """The Pallas forward-Euler path (interpret mode on CPU) drives the
    batch to the mathematical solution."""
    a, x, b = _batch(29, 16, 4)
    nets = [build_proposed(a[k], b[k]) for k in range(4)]
    bss = engine.assemble_batch(nets)
    steps, x_final, res, dt = engine.euler_settle_batch(
        bss, x, max_steps=40_000, interpret=True
    )
    assert np.all(steps < 40_000)
    np.testing.assert_allclose(x_final, x, rtol=0.02, atol=1e-3)
    assert np.all(res >= 0.0)
    assert np.all(dt > 0.0)


def test_transient_batch_euler_method():
    """method='euler' end-to-end (assemble -> vmapped OP -> Pallas sweep)."""
    a, x, b = _batch(31, 12, 3)
    nets = [build_proposed(a[k], b[k]) for k in range(3)]
    tr = engine.transient_batch(nets, method="euler", interpret=True)
    assert tr.method == "euler"
    assert np.all(tr.stable)
    assert np.all(np.isfinite(tr.settle_time))
    np.testing.assert_allclose(tr.x_converged, x, rtol=0.02, atol=1e-3)


def test_batched_kernels_non_multiple_n():
    """Regression: all transient kernels auto-pad non-block-multiple n."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.ops import (
        transient_step, transient_step_batched, transient_sweep,
    )

    rng = np.random.default_rng(5)
    bsz, n = 3, 137          # 137 is far from any block multiple
    m = jnp.asarray(rng.standard_normal((bsz, n, n)) * 0.05, jnp.float32)
    z = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)

    out, res = transient_step_batched(m, z, c, 1e-2, interpret=True)
    want, wres = ref.transient_step_batched_ref(m, z, c, 1e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(wres),
                               rtol=2e-5, atol=2e-5)

    # unequal block dims: padding must reach a multiple of lcm(bm, bk)
    out_u, res_u = transient_step_batched(
        m, z, c, 1e-2, block=(64, 128), interpret=True
    )
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    out2, res2 = transient_sweep(m, z, c, n_steps=5, dt=1e-2, interpret=True)
    want2, wres2 = ref.transient_sweep_ref(m, z, c, n_steps=5, dt=1e-2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res2), np.asarray(wres2),
                               rtol=2e-5, atol=2e-5)

    # single-system wrapper on odd shapes (the legacy hard-assert path)
    out3 = transient_step(m[0], z[0], c[0], 1e-2, interpret=True)
    want3 = ref.transient_step_ref(m[0], z[0][:, None], c[0][:, None], 1e-2)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(want3)[:, 0],
                               rtol=2e-5, atol=2e-5)


def test_preliminary_union_pattern():
    """Preliminary-design batches share the union of cell positions."""
    a, x, b = _batch(37, 8, 3)
    nets = [build_preliminary(a[k], b[k]) for k in range(3)]
    pat = engine.pattern_union(nets)
    for net in nets:
        assert np.sum(net.cell_j >= 0) <= pat.n_pair_slots
    tr = engine.transient_batch(nets, method="eig")
    for k in range(3):
        single = lti_transient(nets[k])
        np.testing.assert_allclose(
            tr.settle_time[k], single.settle_time, rtol=1e-6
        )

"""The VMEM-resident dense settle sweep.

Every dense settle whose double-buffered operator fits the chip's VMEM
(``ops.sweep_state_limit()``) runs its chunks through
:func:`repro.kernels.transient_step.transient_sweep_pallas`: the
operator stays on-chip for the whole chunk and each step is an exact-f32
VPU matvec.  These checks hold it to the pure-jnp oracle and to the tiled
per-step program it replaces, in interpret mode.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis.runtime import CompileWatch
from repro.core import engine
from repro.core.network import build_proposed
from repro.data.spd import random_rhs_from_solution, random_spd
from repro.kernels import ops, ref
from repro.kernels.transient_step import (
    tiled_transient_sweep_pallas, transient_sweep_pallas,
)

DT = 1e-2


def _operands(seed, bsz, n):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.standard_normal((bsz, n, n)) * 0.05, jnp.float32)
    z = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    return m, z, c


def _padded(m, z, c):
    n = m.shape[1]
    size = n + (-n) % 128
    return (ops._pad_to(m, (1, size, size)), ops._pad_to(z, (1, size)),
            ops._pad_to(c, (1, size)))


def _agrees_with_oracle_and_tiled(n):
    """States and final-state residual match the oracle and the tiled
    per-step program (padded rows stay zero).  Four systems: no other
    test compiles the tiled program at that batch, so its compile cache
    stays as ``tests/test_tiled_sweep.py`` expects it."""
    m, z, c = _operands(7, 4, n)
    mp, zp, cp = _padded(m, z, c)
    out, res = transient_sweep_pallas(mp, zp, cp, jnp.int32(6), dt=DT,
                                      interpret=True)
    assert out.shape == zp.shape and res.shape == (4,)
    np.testing.assert_array_equal(np.asarray(out[:, n:]), 0.0)
    want, wres = ref.transient_sweep_ref(m, z, c, n_steps=6, dt=DT)
    np.testing.assert_allclose(np.asarray(out[:, :n]), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(wres),
                               rtol=2e-5, atol=2e-5)
    tiled, tres = tiled_transient_sweep_pallas(
        mp, zp[:, None, :], cp[:, None, :], jnp.int32(6), dt=DT,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(tiled[:, 0]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(tres),
                               rtol=2e-5, atol=2e-5)


def agrees_at_128():
    _agrees_with_oracle_and_tiled(128)


def agrees_at_137_padded():
    _agrees_with_oracle_and_tiled(137)


def second_step_count_compiles_nothing():
    """``n_steps`` is traced: one executable serves every chunk length."""
    m, z, c = _padded(*_operands(5, 2, 137))
    for n_steps, compiles in ((3, True), (8, False)):
        with CompileWatch() as watch:
            out, _ = transient_sweep_pallas(m, z, c, jnp.int32(n_steps),
                                            dt=DT, interpret=True)
        assert (watch.count > 0) == compiles, watch.names
        want, _ = ref.transient_sweep_ref(m, z, c, n_steps=n_steps, dt=DT)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def settle_agrees_across_the_switch():
    """A whole settle (nz = 48, padded to 128) on the resident path and,
    with the limit forced to 0, on the tiled path: the same settle steps
    within one chunk, x within the settle band of each other."""
    rng = np.random.default_rng(3)
    nets, xs = [], []
    for _ in range(3):
        a = random_spd(rng, 6)
        x, b = random_rhs_from_solution(rng, a)
        nets.append(build_proposed(a, b))
        xs.append(x)
    x_ref = np.stack(xs)
    bss = engine.assemble_batch(nets)
    assert ops.sweep_backend(bss.n_states, None) == "dense"

    before = dict(ops.DENSE_SWEEP_CHUNKS)
    steps, x_res, _, dt = engine.euler_settle_batch(
        bss, x_ref, max_steps=40_000, interpret=True)
    resident = ops.DENSE_SWEEP_CHUNKS["resident"] - before["resident"]
    assert resident > 0 and ops.DENSE_SWEEP_CHUNKS["tiled"] == before["tiled"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "sweep_state_limit", lambda: 0)
        assert ops.sweep_backend(bss.n_states, None) == "dense-step"
        want_steps, x_tiled, _, want_dt = engine.euler_settle_batch(
            bss, x_ref, max_steps=40_000, interpret=True)
    assert ops.DENSE_SWEEP_CHUNKS["tiled"] - before["tiled"] == resident

    assert np.all(steps < 40_000)
    assert np.all(np.abs(steps - want_steps) <= 50), (steps, want_steps)
    np.testing.assert_array_equal(dt, want_dt)
    band = np.maximum(0.01 * np.abs(x_ref), 1e-4)
    assert np.all(np.abs(x_res - x_tiled) <= band)
    assert np.all(np.abs(x_res - x_ref) <= band)


@pytest.mark.parametrize("check", [
    agrees_at_128, agrees_at_137_padded, second_step_count_compiles_nothing,
    settle_agrees_across_the_switch,
], ids=lambda f: f.__name__)
def test_resident_sweep(check):
    check()


def test_state_limit_follows_vmem_capacity(monkeypatch):
    """The path rule reads the chip's VMEM: the v5e's 128 MiB where no
    TPU is attached, and a smaller VMEM moves the switch down."""
    assert ops.vmem_capacity_bytes() == ops.DEFAULT_VMEM_BYTES
    assert ops.sweep_state_limit() == 3712
    assert ops.sweep_backend(3712, None) == "dense"
    assert ops.sweep_backend(3713, None) == "dense-step"
    monkeypatch.setattr(ops, "vmem_capacity_bytes", lambda: 64 << 20)
    assert ops.sweep_state_limit() == 2432
    assert ops.sweep_backend(2400, None) == "dense"
    assert ops.sweep_backend(2433, None) == "dense-step"

"""The tiled dense settle sweep: one compiled device program per chunk.

Past ``ops.SWEEP_STATE_LIMIT`` states the dense operator no longer fits
in VMEM and each Euler step is one launch of the tiled ``dense_step``
kernel.  :func:`repro.kernels.transient_step.tiled_transient_sweep_pallas`
loops those launches on the device.  These tests hold it to the
per-step loop it replaced: the same kernel on the same values in the
same order, so the same states and settle step counts.
"""

import math

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis.runtime import CompileWatch
from repro.core import engine
from repro.core.network import build_proposed
from repro.data.spd import random_rhs_from_solution, random_spd
from repro.kernels import ops, ref
from repro.kernels.transient_step import (
    DEFAULT_BATCHED_BLOCK, tiled_transient_sweep_pallas,
)


def _operands(seed, bsz, n):
    rng = np.random.default_rng(seed)
    m = jnp.asarray(rng.standard_normal((bsz, n, n)) * 0.05, jnp.float32)
    z = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    return m, z, c


def _per_step_sweep(m, z, c, *, n_steps, dt=1.0, interpret=None,
                    m_transposed=False):
    """The tiled branch of ``ops.transient_sweep`` as it was: one
    ``transient_step_batched`` launch per step, then a ``dt = 0`` launch
    for the residual at the final state."""
    n = m.shape[1]
    bm, bk = DEFAULT_BATCHED_BLOCK
    size = n + (-n) % math.lcm(bm, bk)
    m = ops._pad_to(m, (1, size, size))
    z = ops._pad_to(z, (1, size))
    c = ops._pad_to(c, (1, size))
    for _ in range(n_steps):
        z, _ = ops.transient_step_batched(m, z, c, dt, interpret=interpret)
    _zf, res = ops.transient_step_batched(m, z, c, 0.0, interpret=interpret)
    return z[:, :n], res


@pytest.mark.parametrize("n", [128, 137])
def test_chunk_program_matches_per_step_reference(n):
    """States and final-state residual equal the per-step loop's, and
    match the pure-jnp oracle; a second chunk length compiles nothing."""
    m, z, c = _operands(7, 3, n)
    size = n + (-n) % 128
    mp = ops._pad_to(m, (1, size, size))
    zp = ops._pad_to(z, (1, size))[:, None, :]
    cp = ops._pad_to(c, (1, size))[:, None, :]

    for n_steps in (4, 7):
        with CompileWatch() as watch:
            out, res = tiled_transient_sweep_pallas(
                mp, zp, cp, jnp.int32(n_steps), dt=1e-2, interpret=True)
        # the first length compiles the program, the second reuses it
        assert (watch.count == 0) == (n_steps == 7), watch.names
        want, wres = _per_step_sweep(m, z, c, n_steps=n_steps, dt=1e-2,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(out[:, 0, :n]),
                                      np.asarray(want))
        np.testing.assert_array_equal(np.asarray(res), np.asarray(wres))
        oracle, ores = ref.transient_sweep_ref(m, z, c, n_steps=n_steps,
                                               dt=1e-2)
        np.testing.assert_allclose(np.asarray(out[:, 0, :n]),
                                   np.asarray(oracle), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(res), np.asarray(ores),
                                   rtol=2e-5, atol=2e-5)


def test_tiled_transient_sweep_is_one_launch(monkeypatch):
    """``ops.transient_sweep`` past the fused limit counts one launch
    per call, whatever ``n_steps``."""
    monkeypatch.setattr(ops, "SWEEP_STATE_LIMIT", 0)
    m, z, c = _operands(11, 2, 137)
    for n_steps in (1, 9):
        launches = dict(ops.KERNEL_STATS)
        out, res = ops.transient_sweep(m, z, c, n_steps=n_steps, dt=1e-2,
                                       interpret=True)
        assert ops.KERNEL_STATS["interpreted"] == launches["interpreted"] + 1
        assert ops.KERNEL_STATS["compiled"] == launches["compiled"]
        want, wres = _per_step_sweep(m, z, c, n_steps=n_steps, dt=1e-2,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(res), np.asarray(wres))


def test_euler_settle_on_tiled_path_matches_per_step_loop(monkeypatch):
    """A whole settle on the tiled path (nz = 48, padded to 128) gives
    the per-step loop's step counts and final unknowns, bit for bit."""
    rng = np.random.default_rng(3)
    nets, xs = [], []
    for _ in range(3):
        a = random_spd(rng, 6)
        x, b = random_rhs_from_solution(rng, a)
        nets.append(build_proposed(a, b))
        xs.append(x)
    x_ref = np.stack(xs)
    bss = engine.assemble_batch(nets)
    monkeypatch.setattr(ops, "SWEEP_STATE_LIMIT", 0)

    steps, x_final, res, dt = engine.euler_settle_batch(
        bss, x_ref, max_steps=40_000, interpret=True)
    with monkeypatch.context() as mp:
        mp.setattr(ops, "transient_sweep", _per_step_sweep)
        want_steps, want_x, want_res, want_dt = engine.euler_settle_batch(
            bss, x_ref, max_steps=40_000, interpret=True)

    assert np.all(steps < 40_000)
    np.testing.assert_array_equal(steps, want_steps)
    np.testing.assert_array_equal(x_final, want_x)
    np.testing.assert_array_equal(res, want_res)
    np.testing.assert_array_equal(dt, want_dt)

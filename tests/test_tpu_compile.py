"""Compile the served device programs for a described TPU v5e chip.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  Each test lowers one program at its served
size for one chip of a ``v5e:2x2`` description and checks that Mosaic
accepted the Pallas kernels (``tpu_custom_call`` in the compiled text).
The topology is described inside a module fixture — never at import —
and every test here skips, together, where it cannot be described.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401  (enables x64, as the served process does)

BATCH = 8
ELL_NZ = 16384           # n = 2048 sparse systems (nz = 8n)
ELL_WIDTH = 35           # their ELL width (row degree 16)
DC_NZ = 1536             # the n=192 analog_2n bucket
DENSE_SWEEP_NZ = 1792    # ops.SWEEP_STATE_LIMIT, the largest fused size
DENSE_STEP_NZ = 2048     # first padded size past the fused limit


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # the described chip's compiler would otherwise log under the temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_sweep_compiles(one_chip, dtype):
    from repro.kernels.ell_transient import ell_sweep_pallas

    slots = (BATCH, ELL_WIDTH, ELL_NZ)
    state = (BATCH, 1, ELL_NZ)
    text = _compile(
        lambda i, w, z, c, n: ell_sweep_pallas(i, w, z, c, n),
        _spec(one_chip, slots, jnp.int32), _spec(one_chip, slots, dtype),
        _spec(one_chip, state, jnp.float32), _spec(one_chip, state, jnp.float32),
        _spec(one_chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_ell_step_kernel_compiles(one_chip):
    from repro.kernels.ell_transient import ell_step_pallas

    slots = (BATCH, ELL_WIDTH, ELL_NZ)
    state = (BATCH, 1, ELL_NZ)
    text = _compile(
        lambda w, g, z, c: ell_step_pallas(w, g, z, c, 1.0),
        _spec(one_chip, slots, jnp.float32), _spec(one_chip, slots, jnp.float32),
        _spec(one_chip, state, jnp.float32), _spec(one_chip, state, jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_dense_sweep_compiles_at_fused_limit(one_chip):
    from repro.kernels.ops import SWEEP_STATE_LIMIT, sweep_backend
    from repro.kernels.transient_step import transient_sweep_pallas

    assert DENSE_SWEEP_NZ == SWEEP_STATE_LIMIT
    assert sweep_backend(DENSE_SWEEP_NZ, None) == "dense"
    state = (BATCH, 1, DENSE_SWEEP_NZ)
    text = _compile(
        lambda m, z, c: transient_sweep_pallas(m, z, c, n_steps=50),
        _spec(one_chip, (BATCH, DENSE_SWEEP_NZ, DENSE_SWEEP_NZ), jnp.float32),
        _spec(one_chip, state, jnp.float32), _spec(one_chip, state, jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_dense_step_compiles(one_chip):
    from repro.kernels.ops import sweep_backend
    from repro.kernels.transient_step import transient_step_batched_pallas

    assert sweep_backend(DENSE_STEP_NZ, None) == "dense-step"
    state = (BATCH, 1, DENSE_STEP_NZ)
    text = _compile(
        lambda m, z, c: transient_step_batched_pallas(m, z, c, 1.0),
        _spec(one_chip, (BATCH, DENSE_STEP_NZ, DENSE_STEP_NZ), jnp.float32),
        _spec(one_chip, state, jnp.float32), _spec(one_chip, state, jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_tiled_sweep_compiles(one_chip):
    """The dense-step path's chunk program: the tiled kernel under a
    device-side loop with a traced step count."""
    from repro.kernels.transient_step import tiled_transient_sweep_pallas

    state = (BATCH, 1, DENSE_STEP_NZ)
    text = _compile(
        lambda m, z, c, n: tiled_transient_sweep_pallas(m, z, c, n),
        _spec(one_chip, (BATCH, DENSE_STEP_NZ, DENSE_STEP_NZ), jnp.float32),
        _spec(one_chip, state, jnp.float32), _spec(one_chip, state, jnp.float32),
        _spec(one_chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_dc_solve_compiles_without_f64_lu(one_chip):
    """The operating point at the n=192 analog_2n bucket, in the donated
    form the device streams run: f64 LU is unimplemented on TPU, so the
    solve must factor in f32."""
    from repro.core import engine

    engine._dc_solve_vmapped_donated.lower(
        _spec(one_chip, (BATCH, DC_NZ, DC_NZ), jnp.float64),
        _spec(one_chip, (BATCH, DC_NZ), jnp.float64),
    ).compile()


def test_cholesky_baseline_compiles(one_chip):
    from repro.core.baselines import cholesky_solve_batch

    n = 192
    cholesky_solve_batch.lower(
        _spec(one_chip, (BATCH, n, n), jnp.float64),
        _spec(one_chip, (BATCH, n), jnp.float64),
    ).compile()


def test_lane_block_fits_budget():
    """The ELL row block stays inside its VMEM budget at every width."""
    from repro.kernels.ell_transient import ELL_BLOCK_BYTES, lane_block

    for nz, k in ((ELL_NZ, ELL_WIDTH), (1536, 768), (128, 5), (4096, 4000)):
        bn = lane_block(nz, k)
        assert bn % 128 == 0 and nz % bn == 0
        assert bn == 128 or 2 * 2 * (k + (-k) % 8) * bn * 4 <= ELL_BLOCK_BYTES
    assert np.log2(lane_block(ELL_NZ, ELL_WIDTH)).is_integer()

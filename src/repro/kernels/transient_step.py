"""Fused transient-integration step kernel.

One forward-Euler (or exponential-Euler via premultiplied operator)
step of the circuit ODE for a *batch* of state vectors:

    Z' = Z + dt * (M @ Z + C)

The fusion point: the matmul accumulator, the state tile and the
constant tile are combined in VMEM — Z' never round-trips to HBM
between the MXU contraction and the AXPY update.  This is the TPU
analogue of "the physics does the iteration": per step, one pass over
M at the memory-bandwidth roofline.

Grid: (m_blocks, n_blocks, k_blocks), k innermost (revisiting-output).
The Z operand is passed twice — once indexed by the contraction block
(kk) for the matmul, once by the row block (i) for the update — so
both views stream through VMEM with no gather.

Batched variants (one state vector per system, per-system operator):

* :func:`transient_step_batched_pallas` — one step for a batch
  ``Z'_b = Z_b + dt (M_b Z_b + C_b)`` that also emits ``dz = M_b z_b +
  c_b`` (the steady-state residual; zero exactly at the operating
  point), so the driving sweep can test convergence without a second
  pass over M.
* :func:`transient_sweep_pallas` — ``n_steps`` fused steps with the
  whole per-system operator VMEM-resident (grid over the batch only):
  the physics iterates on-chip and M crosses HBM once per *chunk*
  instead of once per step.  Usable while the double-buffered
  ``n^2 * 4``-byte operator fits in VMEM (``sweep_vmem_bytes``).
* :func:`tiled_transient_sweep_pallas` — the fallback beyond that:
  ``n_steps`` launches of the tiled batched step kernel inside one
  compiled device program (a device-side loop), so a chunk costs the
  host one dispatch however many steps it runs.  M crosses HBM once
  per step.

The batched kernels run their matvecs on the MXU at ``HIGHEST``
precision (f32 semantics; the default single bf16 pass would move the
settle point by ~1e-3) and take states as ``(B, 1, n)``.  Callers go
through the auto-padding wrappers in :mod:`repro.kernels.ops`; the raw
kernels assert block-multiple shapes.

Dense <-> ELL crossover
-----------------------
These dense kernels are one side of a backend switch
(:func:`repro.kernels.ops.sweep_backend`); the other side is the
matrix-free ELL sweep (:mod:`repro.kernels.ell_transient`).  The
crossover model:

* **traffic** — per step the dense sweep reads ``nz^2`` f32 weights;
  the ELL sweep reads ``nz * K`` (f32 weight, i32 index) pairs, i.e.
  ``2 K / nz`` of the dense bytes.  With the circuit's bounded amp
  rows (<= 4 stamps) and node rows (1 + cells + branch degree), ``K``
  is ~``deg(A) + 3``: even a *dense* system matrix gives ``K ~ n``
  against ``nz ~ 8n`` — an ~8x reduction — and sparse systems scale as
  their true degree.  The switch picks ELL whenever
  ``K < ELL_FILL_CUTOFF * nz`` (cutoff 0.5 = the break-even of the
  2-arrays-per-slot format).
* **VMEM budget** — the fused dense sweep holds the double-buffered
  ``nz^2 * 4``-byte operator per system on-chip (``SWEEP_STATE_LIMIT``)
  and degrades to its per-step tiled kernel beyond; the ELL step
  streams one row block of slots at a time and has no size limit.
* **gather cost** — the ELL row reduction pays one gather per slot; on
  sparse systems the traffic win dominates, at fill ratios near the
  cutoff the dense MXU/VPU stream wins, which is why the switch is by
  fill ratio rather than "always ELL".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK = (128, 128, 128)


def _step_kernel(m_ref, zk_ref, zi_ref, c_ref, out_ref, acc_ref, *, n_k_blocks: int, dt: float):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        m_ref[...], zk_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k_blocks - 1)
    def _update():
        z = zi_ref[...].astype(jnp.float32)
        c = c_ref[...].astype(jnp.float32)
        out_ref[...] = (z + dt * (acc_ref[...] + c)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dt", "block", "interpret"))
def transient_step_pallas(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    dt: float,
    *,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """``z + dt * (m @ z + c)`` for m (n, n), z (n, b), c (n, b)."""
    n, n2 = m.shape
    nz, nb = z.shape
    assert n == n2 == nz and c.shape == z.shape, (m.shape, z.shape, c.shape)
    bm, bn, bk = block
    assert n % bm == 0 and nb % bn == 0 and n % bk == 0, (m.shape, z.shape, block)
    n_k_blocks = n // bk

    return pl.pallas_call(
        functools.partial(_step_kernel, n_k_blocks=n_k_blocks, dt=float(dt)),
        grid=(n // bm, nb // bn, n_k_blocks),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),   # M tile
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),   # Z for matmul
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # Z for update
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),    # C tile
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, nb), z.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(m, z, z, c)


# ---------------------------------------------------------------------------
# Batched step (per-system operators) with fused settling-check output
# ---------------------------------------------------------------------------
#
# Batched layouts: per-system states and constants are (B, 1, n) — the
# unit sublane axis makes a one-system block ``(1, 1, bn)`` legal under
# Mosaic's (8, 128) tiling rule without padding the batch (a dense
# operator block per system is already as large as VMEM allows).

DEFAULT_BATCHED_BLOCK = (128, 128)

# constant block index for index maps: a Python 0 traces as int64 under
# the package's global x64 mode, which Mosaic refuses to lower
_I0 = np.int32(0)

_HIGHEST = jax.lax.Precision.HIGHEST


def _row_matvec(z, m_t):
    """``z @ m_t`` for a row vector: ``(1, k) x (k, bm) -> (1, bm)``, f32."""
    return jnp.dot(z, m_t, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def _step_batched_kernel(
    m_ref, zk_ref, zi_ref, c_ref, out_ref, dz_ref, acc_ref,
    *, n_k_blocks: int, dt: float
):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # acc[0, i] += sum_k M[b, i, k] z[b, k]  (M tile read transposed)
    m = m_ref[0].astype(jnp.float32)                  # (bm, bk)
    zk = zk_ref[0].astype(jnp.float32)                # (1, bk)
    acc_ref[...] += jax.lax.dot_general(
        zk, m, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k_blocks - 1)
    def _update():
        dz = acc_ref[...] + c_ref[0].astype(jnp.float32)
        z = zi_ref[0].astype(jnp.float32)
        out_ref[0] = (z + dt * dz).astype(out_ref.dtype)
        dz_ref[0] = dz


@functools.partial(jax.jit, static_argnames=("dt", "block", "interpret"))
def transient_step_batched_pallas(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    dt: float,
    *,
    block: tuple[int, int] = DEFAULT_BATCHED_BLOCK,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused Euler step per system: m (B, n, n), z/c (B, 1, n).

    Returns ``(z + dt * dz, dz)`` with ``dz = M z + c`` at the input
    state — reduce ``max |dz|`` for the per-system settling check.
    """
    bsz, n, n2 = m.shape
    assert n == n2 and z.shape == (bsz, 1, n) and c.shape == z.shape, (
        m.shape, z.shape, c.shape)
    bm, bk = block
    assert n % bm == 0 and n % bk == 0, (m.shape, block)
    n_k_blocks = n // bk
    row = pl.BlockSpec((1, 1, bm), lambda b, i, kk: (b, _I0, i))

    return pl.pallas_call(
        functools.partial(
            _step_batched_kernel, n_k_blocks=n_k_blocks, dt=float(dt)
        ),
        grid=(bsz, n // bm, n_k_blocks),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, kk: (b, i, kk)),   # M tile
            pl.BlockSpec((1, 1, bk), lambda b, i, kk: (b, _I0, kk)),  # Z (matmul)
            row,                                                      # Z (update)
            row,                                                      # C tile
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bm), jnp.float32)],
        name="dense_step",
        interpret=interpret,
    )(m, z, z, c)


# ---------------------------------------------------------------------------
# Fused multi-step sweep: whole per-system operator VMEM-resident
# ---------------------------------------------------------------------------


# operator rows per MXU pass of the fused sweep's matvec: bounds the
# in-kernel temporaries (the f32 HIGHEST-precision split of one chunk)
# instead of materializing the whole (n, n) operator as a value
_SWEEP_ROW_CHUNK = 128


def _sweep_kernel(mt_ref, z_ref, c_ref, out_ref, dz_ref, *, n_steps: int, dt: float):
    n = mt_ref.shape[1]
    c = c_ref[0].astype(jnp.float32)                  # (1, n)

    def residual(zz):                                 # M z + c, (1, n)
        acc = c
        for k0 in range(0, n, _SWEEP_ROW_CHUNK):
            rows = slice(k0, k0 + _SWEEP_ROW_CHUNK)
            acc = acc + _row_matvec(
                zz[:, rows], mt_ref[0, rows, :].astype(jnp.float32)
            )
        return acc

    z = jax.lax.fori_loop(
        0, n_steps, lambda _, zz: zz + dt * residual(zz),
        z_ref[0].astype(jnp.float32),
    )
    out_ref[0] = z.astype(out_ref.dtype)
    dz_ref[0] = residual(z)


def sweep_vmem_bytes(n: int) -> int:
    """Scoped VMEM the fused sweep needs: the double-buffered ``(n, n)``
    f32 operator block plus headroom for the state blocks and the
    per-chunk matvec temporaries."""
    return 2 * n * n * 4 + (16 << 20)


@functools.partial(jax.jit, static_argnames=("n_steps", "dt", "interpret"))
def transient_sweep_pallas(
    m_t: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    *,
    n_steps: int,
    dt: float = 1.0,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``n_steps`` fused Euler steps per system, operator VMEM-resident.

    ``m_t`` is the batch of *transposed* operators (``m_t[b] = M_b.T``)
    so the in-kernel update is a plain row-vector matmul; ``z``/``c``
    are ``(B, 1, n)``.  Returns ``(z', dz)`` with ``dz = M z' + c`` at
    the final state — reduce ``max |dz|`` for the settling check.
    """
    bsz, n, n2 = m_t.shape
    assert n == n2 and z.shape == (bsz, 1, n) and c.shape == z.shape, (
        m_t.shape, z.shape, c.shape)
    assert n % 128 == 0, m_t.shape
    row = pl.BlockSpec((1, 1, n), lambda b: (b, _I0, _I0))

    return pl.pallas_call(
        functools.partial(_sweep_kernel, n_steps=int(n_steps), dt=float(dt)),
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, n, n), lambda b: (b, _I0, _I0)), row, row],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=sweep_vmem_bytes(n)
        ),
        name="dense_sweep",
        interpret=interpret,
    )(m_t, z, c)


# ---------------------------------------------------------------------------
# Tiled multi-step sweep: the per-step kernel under a device-side loop
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def tiled_transient_sweep_pallas(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    n_steps,
    *,
    dt: float = 1.0,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``n_steps`` tiled Euler steps per system in one dispatch.

    ``m`` is ``(B, n, n)`` (not transposed), ``z``/``c`` ``(B, 1, n)``,
    ``n`` a multiple of the default blocks.  Each step is one launch of
    :func:`transient_step_batched_pallas`, looped on the device.
    ``n_steps`` is a traced count, so every chunk length shares one
    executable.  Returns ``(z', res)`` with ``res[b] = max_i |M_b z'_b
    + c_b|_i`` — the settling-check reduction at the final state, from
    one more ``dt = 0`` pass.
    """
    def body(_, zz):
        return transient_step_batched_pallas(m, zz, c, dt,
                                             interpret=interpret)[0]

    z = jax.lax.fori_loop(0, n_steps, body, z)
    _, dz = transient_step_batched_pallas(m, z, c, 0.0, interpret=interpret)
    return z, jnp.max(jnp.abs(dz), axis=(1, 2))

"""Matrix-free ELL-format transient sweep kernels.

The circuit operator ``M`` of :mod:`repro.core.engine` is inherently
sparse: only the ``n`` node rows carry the branch network of the system
matrix, while every buffer/amp row holds at most four stamps.  The
batched engine therefore stores ``M`` in ELL (padded sparse-row) form —
per row, a fixed-width list of ``(column, weight)`` slots:

    dz[i] = sum_k  w[i, k] * z[idx[i, k]]          (+ c[i])

Unused slots carry ``(idx=0, w=0)`` and are exact no-ops, so the same
gathered row reduction serves every row type.  Per step the sweep
touches ``nz * K`` weights instead of ``nz^2`` — for the proposed
design (``nz ~ 8n``, amp rows bounded) that is an ~8x traffic reduction
even for a dense system matrix and orders of magnitude for sparse ones.

Kernel layout (slot-major)
--------------------------
The kernels take the slot arrays transposed to ``(B, K, nz)``: the row
axis ``nz`` (padded to 128) runs along the TPU lanes and the slot axis
``K`` along sublanes, so a block ``(1, K, bn)`` satisfies Mosaic's
(8, 128) tiling rule for any ``K`` and the slot reduction is a plain
sublane sum.  States and constants are ``(B, 1, nz)``: the unit
sublane axis makes a one-system block legal without padding the batch.

The gather ``z[idx]`` is done by XLA between kernel launches: Mosaic
only gathers within one vreg (8 x 128), and the state vector spans
``nz / 128`` of them.  One step is therefore an XLA gather producing
the ``(B, K, nz)`` slot values followed by :func:`ell_step_pallas`, the
Pallas kernel that multiplies them by the slot weights, reduces over
slots, applies the Euler update and emits ``M z + c``.
:func:`ell_sweep_pallas` runs ``n_steps`` such steps inside one jitted
``fori_loop`` (one host dispatch per chunk) and evaluates the settling
residual ``max |M z' + c|`` at the final state.  Only one row block of
the slot arrays is VMEM-resident at a time, so every ``nz`` takes the
same path.

Both take a ``sweep_dtype`` knob (``"float32"`` default, or
``"bfloat16"``): the slot weights (and the gathered slot values) are
stored and multiplied at that precision while the slot-axis
*accumulation*, the state vector and the settling residual stay float32
(bf16 storage / fp32 accumulate — the mixed-precision contract the
refinement layer in :mod:`repro.core.refine` assumes).  bf16 halves the
per-step weight traffic — the dominant bytes of the sweep — at ~3
decimal digits of weight precision, which the 1 %-band settling check
tolerates; anything tighter than the band must come from refinement,
not the sweep.  Callers go through the wrapper in
:mod:`repro.kernels.ops`; the raw kernels assert pre-padded shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


# sweep_dtype values accepted by the sweep kernels and their wrappers
SWEEP_DTYPES = ("float32", "bfloat16")

# bytes of the double-buffered weight + slot-value blocks one grid step
# may hold in VMEM (well inside the 16 MiB default scoped limit)
ELL_BLOCK_BYTES = 8 * 1024 * 1024
_MAX_LANE_BLOCK = 8192

# constant block index for index maps: a Python 0 traces as int64 under
# the package's global x64 mode, which Mosaic refuses to lower
_I0 = np.int32(0)


def lane_block(nz: int, k: int) -> int:
    """Row block (lanes) for the ELL step: the largest power-of-two
    multiple of 128 dividing ``nz`` whose blocks fit
    :data:`ELL_BLOCK_BYTES` (never below 128)."""
    assert nz % 128 == 0, nz
    k_pad = k + (-k) % 8
    bn = 128
    while (bn * 2 <= _MAX_LANE_BLOCK and nz % (bn * 2) == 0
           and 2 * 2 * k_pad * (bn * 2) * 4 <= ELL_BLOCK_BYTES):
        bn *= 2
    return bn


def _ell_step_kernel(w_ref, g_ref, z_ref, c_ref, out_ref, dz_ref, *, dt: float):
    w = w_ref[0]                                       # (K, bn) sweep dtype
    g = g_ref[0].astype(w.dtype)                       # (K, bn) z[idx]
    dz = jnp.sum((w * g).astype(jnp.float32), axis=0, keepdims=True) \
        + c_ref[0].astype(jnp.float32)                 # (1, bn)
    out_ref[0] = (z_ref[0].astype(jnp.float32) + dt * dz).astype(out_ref.dtype)
    dz_ref[0] = dz


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def ell_step_pallas(
    w: jnp.ndarray,
    g: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    dt: float = 1.0,
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One ELL Euler step from pre-gathered slot values.

    ``w``/``g`` are ``(B, K, nz)`` slot weights and slot values
    ``g[b, k, i] = z[b, idx[b, k, i]]``; ``z``/``c`` are ``(B, 1, nz)``.
    Returns ``(z + dt * dz, dz)`` with ``dz = M z + c`` at the input
    state.
    """
    bsz, k, nz = w.shape
    assert g.shape == w.shape and z.shape == (bsz, 1, nz) and c.shape == z.shape, (
        w.shape, g.shape, z.shape, c.shape)
    bn = lane_block(nz, k)
    state = pl.BlockSpec((1, 1, bn), lambda b, i: (b, _I0, i))
    slots = pl.BlockSpec((1, k, bn), lambda b, i: (b, _I0, i))
    return pl.pallas_call(
        functools.partial(_ell_step_kernel, dt=float(dt)),
        grid=(bsz, nz // bn),
        in_specs=[slots, slots, state, state],
        out_specs=[state, state],
        out_shape=[
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct(z.shape, jnp.float32),
        ],
        name="ell_step",
        interpret=interpret,
    )(w, g, z, c)


def _gather_slots(z: jnp.ndarray, idx: jnp.ndarray, dtype) -> jnp.ndarray:
    """``(B, 1, nz)`` state, ``(B, K, nz)`` indices -> slot values."""
    return jax.vmap(lambda zz, ii: zz[0][ii])(z.astype(dtype), idx)


@functools.partial(jax.jit, static_argnames=("dt", "interpret"))
def ell_sweep_pallas(
    idx: jnp.ndarray,
    w: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    n_steps,
    *,
    dt: float = 1.0,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``n_steps`` ELL Euler steps per system in one dispatch.

    ``idx``/``w`` are ``(B, K, nz)`` slot-major ELL arrays (``w`` at
    the sweep dtype), ``z``/``c`` ``(B, 1, nz)``.  ``n_steps`` is a
    traced count, so every chunk length shares one executable.  Returns
    ``(z', res)`` with ``res[b] = max_i |M_b z'_b + c_b|_i`` — the
    settling-check reduction at the final state.
    """
    def body(_, zz):
        return ell_step_pallas(w, _gather_slots(zz, idx, w.dtype), zz, c, dt,
                               interpret=interpret)[0]

    z = jax.lax.fori_loop(0, n_steps, body, z)
    _, dz = ell_step_pallas(w, _gather_slots(z, idx, w.dtype), z, c, 0.0,
                            interpret=interpret)
    return z, jnp.max(jnp.abs(dz), axis=(1, 2))

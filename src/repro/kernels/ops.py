"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
* pad inputs to block multiples (zero padding is exact for all three
  kernels: matmul/reduction zeros are neutral, and the assembly kernel's
  padded diagonal region is sliced away);
* choose interpret mode automatically off-TPU (the CPU test path), and
  count every launch by mode in :data:`KERNEL_STATS`, so a run on the
  chip can prove that no kernel ran interpreted;
* present clean shapes (vectors in, vectors out).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import crosspoint_mvm as _mvm
from repro.kernels import ell_transient as _ell
from repro.kernels import spd_transform as _tr
from repro.kernels import transient_step as _st


# kernel launches through these wrappers, by execution mode
KERNEL_STATS = {"compiled": 0, "interpreted": 0}


def _resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode unless on TPU (or as the caller forces); counted."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    KERNEL_STATS["interpreted" if interpret else "compiled"] += 1
    return interpret


def _pad_to(x: jnp.ndarray, mults: tuple[int, ...]) -> jnp.ndarray:
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def crosspoint_mvm(
    g: jnp.ndarray,
    v: jnp.ndarray,
    *,
    block: tuple[int, int, int] = _mvm.DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Crossbar currents I = G @ V.  v may be (k,) or (k, batch)."""
    interpret = _resolve_interpret(interpret)
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    m, k = g.shape
    bm, bn, bk = block
    gp = _pad_to(g, (bm, bk))
    vp = _pad_to(v, (bk, bn))
    out = _mvm.crosspoint_mvm_pallas(gp, vp, block=block, interpret=interpret)
    out = out[:m, : v.shape[1]]
    return out[:, 0] if squeeze else out


def transient_step(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    dt: float,
    *,
    block: tuple[int, int, int] = _st.DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One fused Euler step z + dt (M z + c); z may be (n,) or (n, b)."""
    interpret = _resolve_interpret(interpret)
    squeeze = z.ndim == 1
    if squeeze:
        z = z[:, None]
        c = c[:, None]
    n = m.shape[0]
    bm, bn, bk = block
    mp = _pad_to(m, (bm, bk))
    # square pad: the contraction dim must match the padded row dim
    size = max(mp.shape)
    mp = _pad_to(mp, (size, size)) if mp.shape[0] != mp.shape[1] else mp
    zp = _pad_to(z, (size, bn))
    cp = _pad_to(c, (size, bn))
    out = _st.transient_step_pallas(mp, zp, cp, dt, block=block, interpret=interpret)
    out = out[:n, : z.shape[1]]
    return out[:, 0] if squeeze else out


def transient_step_batched(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    dt: float = 1.0,
    *,
    block: tuple[int, int] = _st.DEFAULT_BATCHED_BLOCK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched fused Euler step: m (B, n, n), z/c (B, n).

    Returns ``(z', res)`` with ``res`` the per-system fused
    settling-check reduction ``max_i |M z + c|_i``.
    """
    interpret = _resolve_interpret(interpret)
    bsz, n, _ = m.shape
    bm, bk = block
    mult = math.lcm(bm, bk)
    size = n + (-n) % mult
    mp = _pad_to(m, (1, size, size))
    zp = _pad_to(z, (1, size))[:, None, :]
    cp = _pad_to(c, (1, size))[:, None, :]
    out, dz = _st.transient_step_batched_pallas(
        mp, zp, cp, dt, block=block, interpret=interpret
    )
    return out[:, 0, :n], jnp.max(jnp.abs(dz), axis=(1, 2))


# fused-sweep VMEM budget: the double-buffered (n, n) f32 operator of one
# system must fit on-chip (see transient_step.sweep_vmem_bytes)
SWEEP_STATE_LIMIT = 1792

# ---------------------------------------------------------------------------
# Dense <-> ELL crossover model
# ---------------------------------------------------------------------------
#
# Per Euler step the dense sweep reads nz^2 f32 weights; the ELL sweep
# reads nz*K (weight, index) pairs — 2x the bytes per slot.  ELL
# therefore wins on traffic whenever the ELL width K is below
# ELL_FILL_CUTOFF * nz, and it additionally removes the O(B nz^2) host
# assembly and transfer.  The ELL step streams its slot arrays through
# VMEM one row block at a time, so it has no size limit.
ELL_FILL_CUTOFF = 0.5


def sweep_chunk_schedule(
    predicted_steps,
    max_steps: int,
    *,
    floor: int = 50,
    ceil: int = 4096,
    splits: int = 8,
) -> int:
    """Fused-sweep chunk length from a spectral settling prediction.

    Every chunk boundary costs a kernel launch plus a host sync for the
    settling check, so a sweep that is predicted to run N steps should
    not poll every 50: the chunk is sized to ``median(N) / splits`` —
    launches amortized across the predicted horizon while the settling
    time stays resolved to ~1/``splits`` of it (and over-integration
    past the settle point is bounded by one chunk).  Non-finite
    predictions (unstable systems) are ignored; with no finite
    prediction the conservative ``floor`` is returned.
    """
    p = np.asarray(predicted_steps, dtype=np.float64).reshape(-1)
    p = p[np.isfinite(p)]
    if p.size == 0:
        return floor
    target = int(np.median(p) / max(splits, 1))
    return int(np.clip(target, floor, max(min(ceil, max_steps), floor)))


def sweep_backend(nz: int, k: int | None) -> str:
    """Pick the transient-sweep backend for an operator family.

    ``k`` is the ELL slot width (None for a dense-only caller).
    Returns ``"ell"`` (ELL-SpMV sweep), ``"dense"`` (fused dense sweep,
    operator VMEM-resident) or ``"dense-step"`` (tiled dense step
    kernel, looped on the device).
    """
    if k is not None and k < ELL_FILL_CUTOFF * nz:
        return "ell"
    return "dense" if nz <= SWEEP_STATE_LIMIT else "dense-step"


def ell_kernel_operands(
    idx: jnp.ndarray, w: jnp.ndarray, c: jnp.ndarray,
    sweep_dtype: str = "float32",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Row-major ELL ``(B, nz, K)`` slots + ``(B, nz)`` constants ->
    the kernels' slot-major layout: ``(B, K, nz_p)`` indices and weights
    (at the sweep dtype) and ``(B, nz_p)`` f32 constants, ``nz_p`` the
    next multiple of 128 (padded rows are zero-weight no-ops)."""
    bsz, nz, k = idx.shape
    size = nz + (-nz) % 128
    w_dtype = jnp.bfloat16 if sweep_dtype == "bfloat16" else jnp.float32
    idx_t = _pad_to(idx.transpose(0, 2, 1), (1, 1, size))
    w_t = _pad_to(w.astype(w_dtype).transpose(0, 2, 1), (1, 1, size))
    return idx_t, w_t, _pad_to(c.astype(jnp.float32), (1, size))


def ell_transient_sweep(
    idx: jnp.ndarray,
    w: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    *,
    n_steps: int,
    dt: float = 1.0,
    interpret: bool | None = None,
    padded: bool = False,
    sweep_dtype: str = "float32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``n_steps`` ELL Euler steps; idx/w (B, nz, K), z/c (B, nz).

    Converts to the kernels' slot-major, 128-padded layout
    (:func:`ell_kernel_operands`) and runs the sweep
    (:func:`repro.kernels.ell_transient.ell_sweep_pallas`).  Returns
    ``(z', res)`` with the per-system residual ``max_i |M z' + c|_i``
    at the final state.

    ``padded=True`` asserts the caller already passed
    :func:`ell_kernel_operands` output for ``idx``/``w``/``c`` and a
    ``(B, nz_p)`` state — the loop-hoisted fast path for settling
    sweeps that launch many chunks over the same operator batch.

    ``sweep_dtype="bfloat16"`` runs the bf16-weight / fp32-accumulate
    variant: the slot weights are stored in bf16 (so the per-step
    weight traffic halves) while the state, the slot-axis accumulation
    and the settling residual stay float32.
    """
    interpret = _resolve_interpret(interpret)
    assert sweep_dtype in _ell.SWEEP_DTYPES, sweep_dtype
    nz = z.shape[1]
    if not padded:
        idx, w, c = ell_kernel_operands(idx, w, c, sweep_dtype)
        z = _pad_to(z, (1, idx.shape[2]))
    out, res = _ell.ell_sweep_pallas(
        idx, w, z[:, None, :], c[:, None, :], jnp.int32(n_steps), dt=dt,
        interpret=interpret,
    )
    return out[:, 0, :nz], res


def transient_sweep(
    m: jnp.ndarray,
    z: jnp.ndarray,
    c: jnp.ndarray,
    *,
    n_steps: int,
    dt: float = 1.0,
    interpret: bool | None = None,
    m_transposed: bool = False,
    sweep_dtype: str = "float32",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``n_steps`` fused batched Euler steps; m (B, n, n), z/c (B, n).

    Uses the VMEM-resident sweep kernel while the per-system operator
    fits on-chip, else falls back to the tiled batched step kernel
    looped on the device (:func:`repro.kernels.transient_step.
    tiled_transient_sweep_pallas`).  Either way one call is one launch,
    whatever ``n_steps``.  Returns ``(z', res)`` with the per-system
    residual ``max_i |M z' + c|_i`` evaluated at the final state.

    ``m_transposed=True`` asserts the caller already block-padded every
    operand and passed ``m[b] = M_b.T`` — the loop-hoisted fast path for
    sweeps that launch many chunks over the same operator batch (that
    path expects the caller to have applied ``sweep_dtype`` rounding to
    ``m`` once, outside the chunk loop).

    ``sweep_dtype="bfloat16"`` rounds the dense operator through bf16
    before the f32 sweep — the same storage-precision semantics as the
    ELL bf16 kernels (the dense MXU kernels accumulate in f32 either
    way, so rounding the weights is the entire dtype effect).
    """
    interpret = _resolve_interpret(interpret)
    assert sweep_dtype in _ell.SWEEP_DTYPES, sweep_dtype
    if sweep_dtype == "bfloat16" and not m_transposed:
        m = m.astype(jnp.bfloat16).astype(jnp.float32)
    bsz, n, _ = m.shape
    if m_transposed:
        out, dz = _st.transient_sweep_pallas(
            m, z[:, None, :], c[:, None, :], n_steps=n_steps, dt=dt,
            interpret=interpret,
        )
        return out[:, 0], jnp.max(jnp.abs(dz), axis=(1, 2))
    if n > SWEEP_STATE_LIMIT:
        bm, bk = _st.DEFAULT_BATCHED_BLOCK
        size = n + (-n) % math.lcm(bm, bk)
        out, res = _st.tiled_transient_sweep_pallas(
            _pad_to(m, (1, size, size)), _pad_to(z, (1, size))[:, None, :],
            _pad_to(c, (1, size))[:, None, :], jnp.int32(n_steps), dt=dt,
            interpret=interpret,
        )
        return out[:, 0, :n], res
    size = n + (-n) % 128
    mp = _pad_to(m, (1, size, size))
    zp = _pad_to(z, (1, size))
    cp = _pad_to(c, (1, size))
    out, dz = _st.transient_sweep_pallas(
        mp.transpose(0, 2, 1), zp[:, None, :], cp[:, None, :],
        n_steps=n_steps, dt=dt, interpret=interpret,
    )
    return out[:, 0, :n], jnp.max(jnp.abs(dz), axis=(1, 2))


def spd_transform_arrays(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    supply_v: float = 4.0,
    block: tuple[int, int] = _tr.DEFAULT_BLOCK,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Kernel-fused proposed transform: returns (K_A, K_B, D, K_s).

    Semantics identical to :func:`repro.core.transform.transform_2n`
    with ``d_policy="proposed"`` — the Eq. 22 D built from the fused
    column-|A| reduction; Eqs. 15-16 assembled tile by tile.
    """
    interpret = _resolve_interpret(interpret)
    n = a.shape[0]
    br, bc = block
    ap = _pad_to(a, (br, bc))
    size = max(ap.shape)
    if ap.shape[0] != ap.shape[1]:
        ap = _pad_to(ap, (size, size))

    colsum = _tr.colabs_pallas(ap, block=block, interpret=interpret)[0, :n]
    k_s = jnp.abs(b.astype(jnp.float32)) / supply_v                 # Eq. 13
    d = 0.5 * k_s + 0.5 * colsum                                    # Eq. 22
    d = d.at[0].add(0.5 * k_s[0])

    dp = _pad_to(d[None, :], (1, bc))[0]
    ksp = _pad_to(k_s[None, :], (1, bc))[0]
    ka, kb = _tr.assemble_pallas(
        ap, dp.astype(ap.dtype), ksp.astype(ap.dtype), block=block, interpret=interpret
    )
    return ka[:n, :n], kb[:n, :n], d, k_s

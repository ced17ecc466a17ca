"""Batched spectral bounds — the O(nz^3)-free settling estimator.

The stacked eigendecomposition (:func:`repro.core.engine._transient_batch_eig`)
is exact but O(nz^3) per system and dense-only; past a few hundred
states it dominates the sweep wall-clock and caps the size sweeps.
This module estimates the spectral quantities the transient path needs
with matrix-free matvecs, batched and device-resident throughout:

* ``|lambda|_max`` — plain power iteration on ``M`` (:func:`power_rate`).
  For non-normal operators the norm ratio sits between ``|lambda|_max``
  and ``sigma_max`` — overestimates are the safe direction for a step
  bound.
* **exterior Ritz modes** — Rayleigh-Ritz over an m-step Krylov space
  (:func:`krylov_ritz`).  The fast exterior eigenvalues (largest
  modulus) converge in a handful of matvecs and carry the *abscissa
  information* a modulus estimate cannot: the forward-Euler circle
  requires ``dt < 2 |Re lambda| / |lambda|^2`` **per mode**, which for
  an underdamped pair (``|Im| >> |Re|``) is far tighter than the
  ``2 / |lambda|_max`` real-spectrum rule.  :func:`spectral_bounds`
  combines both into the abscissa-aware step (:func:`mode_dt_limit`),
  so ``dt_policy="spectral"`` is valid for underdamped operators.
* **slow (rightmost) mode** — propagator-filtered deflated subspace
  iteration (:func:`slow_mode_ritz`).  A block of ``k`` vectors is
  repeatedly pushed through the Euler propagator ``P = I + tau M``
  (``tau`` chosen dt-stable by the abscissa-aware rule): ``p`` steps of
  the filter damp every fast mode by ``|1 + tau lambda|^p`` while the
  modes nearest the imaginary axis survive, so the block converges to
  the slow invariant subspace.  Rayleigh-Ritz on the block (a small
  ``(k, k)`` nonsymmetric eigenproblem per system) then *deflates* the
  slow cluster — the rightmost Ritz value is read off the projected
  operator rather than from a single power vector, which is what fixes
  the old estimator's ``mu ~ 1`` clustering (power iteration on ``P``
  cannot separate eigenvalues that the propagator maps within
  ``O(tau * gap)`` of each other; Rayleigh-Ritz separates them at the
  subspace level).  Per-pair residuals ``||M y - theta y||`` are
  tracked and iteration restarts (up to ``slow_iters`` cycles) until
  the rightmost pair converges.
* **stability certificate** — the global symmetric-part bound
  ``max Re lambda(M) <= lambda_max((M + M^T)/2)`` is strict but
  *vacuous* for these strongly non-normal circuit operators (the
  symmetric part is indefinite: ``sym_max ~ +1e7`` against a true
  abscissa of ``-1e5``).  The certificate reported instead is
  field-of-values-aware and restricted: ``fov_slow`` is the numerical
  abscissa ``lambda_max(sym(V^T M V))`` of ``M`` restricted to the
  extracted slow subspace ``V`` — the restricted numerical range
  contains every Ritz value of the restriction, so ``fov_slow < 0``
  certifies that the slow block is *monotonically contracting* (no
  transient growth within the settling modes), a strictly stronger
  statement than ``Re theta < 0`` and a non-vacuous one (typically
  within a small factor of ``slow_re``).  ``certified`` additionally
  requires the rightmost residual to be small against ``|slow_re|``
  (the eigenvalue-perturbation scale), so a certificate is only issued
  for a *converged* estimate.  The global Lanczos bound stays
  available (``lanczos_iters > 0``) for operators where it is not
  vacuous.

Accuracy contract (enforced by ``tests/test_spectral_settling.py``,
``test_slow_mode_within_2x_of_eig*``): on the tier-1 reference
matrices — both circuit designs, non-diagonally-dominant SPD included —
the slow-mode estimate lands within 2x of the exact-eig reference
(observed: within ~2% once the rightmost residual converges), and
unstable systems are flagged by sign.  ``t_settle`` defaults to the
amplitude-blind e-folding estimate ``ln(1/rtol) / |Re lambda_slow|``;
when the initial error state is known (warm starts, refinement
re-settles) :func:`amplitude_settle_steps` projects it onto the
extracted slow subspace (``SpectralBounds.slow_basis``) and replaces
the blind horizon with the actual slow-mode amplitude's e-fold count.
The exact modal path is the small-nz reference for the paper's
settling criterion.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# power-iteration estimates converge from below; inflate the rate by
# this margin before using it in a stability-critical step bound
RATE_MARGIN = 1.10
# margin on the per-mode abscissa rule (Ritz values of exterior modes
# converge fast but carry a few-percent error before the residual dies)
MODE_MARGIN = 1.25
# the mode rule never tightens dt below this fraction of the modulus
# rule — a guard against an unconverged near-imaginary Ritz value
# collapsing the step (supports damped resonances up to Q ~ 5e5)
MODE_DT_FLOOR = 1e-6
_TINY = 1e-300


@dataclasses.dataclass
class SpectralBounds:
    """Batched spectral estimates of ``dz/dt = M z + c``.

    ``dt`` is the abscissa-aware forward-Euler step
    ``dt_safety * min(2 / |lambda|_max, min_modes 2|Re|/|lambda|^2)``
    (margins applied), valid for underdamped operators.  ``slow_re`` is
    the rightmost-eigenvalue estimate with its Rayleigh-Ritz residual
    ``slow_residual`` (relative to ``|slow_re|``); ``fov_slow`` the
    restricted numerical abscissa of the slow subspace (the
    certificate); ``sym_max`` the strict global symmetric-part bound
    (``None`` unless requested — vacuous for the circuit operators).
    """

    rate_max: np.ndarray        # (B,) |lambda|_max estimate (>= 0)
    slow_re: np.ndarray         # (B,) Re of the rightmost mode (< 0: stable)
    slow_residual: np.ndarray   # (B,) Ritz residual of that pair / |slow_re|
    fov_slow: np.ndarray | None  # (B,) restricted numerical abscissa
    sym_max: np.ndarray | None  # (B,) lambda_max of (M+M^T)/2; None if skipped
    dt_limit: np.ndarray        # (B,) Euler stability limit (no safety factor)
    dt: np.ndarray              # (B,) dt_safety * dt_limit
    settle_time: np.ndarray     # (B,) ln(1/rtol)/|Re slow|; inf if unstable
    settle_steps: np.ndarray    # (B,) ceil(settle_time / dt)
    certified: np.ndarray       # (B,) converged + contracting slow subspace
    slow_basis: np.ndarray | None = None  # (B, k, nz) orthonormal slow block

    @property
    def stable(self) -> np.ndarray:
        return self.slow_re < 0.0


# ---------------------------------------------------------------------------
# Operator adapters
# ---------------------------------------------------------------------------


def _dense_block_mv(m, z):
    return jnp.einsum("bij,bkj->bki", m, z)


def ell_block_matvec(
    indices: jnp.ndarray, weights: jnp.ndarray, z: jnp.ndarray
) -> jnp.ndarray:
    """Block ELL-SpMV ``(B, k, nz) -> (B, k, nz)`` — one gathered row
    reduction over the whole block.  The single canonical
    implementation: :meth:`repro.core.engine.EllBatchedStateSpace.
    matvec_block` delegates here, and the subspace iteration wraps it
    in a :class:`jax.tree_util.Partial`."""
    gathered = jnp.take_along_axis(
        z[:, :, None, :],
        jnp.broadcast_to(
            indices[:, None], (z.shape[0], z.shape[1]) + indices.shape[1:]
        ),
        axis=3,
    )
    return jnp.sum(weights[:, None] * gathered, axis=3)


def _matvec_pair(bss):
    """``(matvec, matvec_t, matvec_block, batch, n_states)`` for dense
    arrays, :class:`~repro.core.engine.BatchedStateSpace` or
    :class:`~repro.core.engine.EllBatchedStateSpace` input.

    ``matvec_block`` maps ``(B, k, nz) -> (B, k, nz)`` — the block form
    the subspace iteration runs on.  For the known operator forms it is
    a :class:`jax.tree_util.Partial` over the operator arrays, so the
    jitted propagator filter's compilation cache keys on (function,
    shapes) and is reused across ``spectral_bounds`` calls instead of
    retracing per call.
    """
    if isinstance(bss, np.ndarray) or (
        hasattr(bss, "ndim") and getattr(bss, "ndim", 0) == 3
    ):
        m = jnp.asarray(bss)
    elif hasattr(bss, "matvec"):
        if hasattr(bss, "indices") and hasattr(bss, "weights"):
            mvb = jax.tree_util.Partial(
                ell_block_matvec, bss.indices, bss.weights
            )
        else:
            # generic operator: wrap the per-vector matvec (no shared
            # compilation cache — keyed per closure)
            mv_one = bss.matvec
            mvb = jax.tree_util.Partial(
                lambda z: jnp.stack(
                    [mv_one(z[:, j]) for j in range(z.shape[1])], axis=1
                )
            )
        return (
            bss.matvec,
            bss.matvec_t if hasattr(bss, "matvec_t") else None,
            mvb,
            bss.batch,
            bss.n_states,
        )
    else:
        m = jnp.asarray(bss.m)                      # BatchedStateSpace

    def mv(z):
        return jnp.einsum("bij,bj->bi", m, z)

    def mvt(z):
        return jnp.einsum("bij,bi->bj", m, z)

    return (
        mv,
        mvt,
        jax.tree_util.Partial(_dense_block_mv, m),
        m.shape[0],
        m.shape[1],
    )


def _init_vec(b: int, nz: int) -> jnp.ndarray:
    """Deterministic, fully-supported start vector (no RNG: results are
    reproducible across runs and backends)."""
    ramp = jnp.linspace(0.3, 1.0, nz, dtype=jnp.float64)
    flip = jnp.where(jnp.arange(nz) % 2 == 0, 1.0, -1.0)
    return jnp.broadcast_to(ramp * flip, (b, nz))


def _init_block(b: int, nz: int, k: int) -> jnp.ndarray:
    """Deterministic full-support block: k cosine probes with distinct
    frequencies (mutually independent, every state excited)."""
    i = jnp.arange(nz, dtype=jnp.float64)
    cols = jnp.stack(
        [
            jnp.cos((j + 1) * (i + 0.5) * (np.pi / nz)) + 0.01 * (j + 1)
            for j in range(k)
        ],
        axis=0,
    )
    return jnp.broadcast_to(cols[None], (b, k, nz))


def _norm(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt(jnp.sum(v * v, axis=1))


def _orthonormalize_rows(v: jnp.ndarray) -> jnp.ndarray:
    """Batched thin-QR orthonormalization of the (B, k, nz) block rows."""
    q, _ = jnp.linalg.qr(jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(q, 1, 2)


# ---------------------------------------------------------------------------
# Estimators
# ---------------------------------------------------------------------------


def power_rate(matvec, b: int, nz: int, iters: int = 32):
    """Dominant ``(|lambda|, Rayleigh)`` of a batched linear operator."""
    v = _init_vec(b, nz)
    v = v / jnp.maximum(_norm(v), _TINY)[:, None]
    w = matvec(v)
    for _ in range(max(iters - 1, 0)):
        v = w / jnp.maximum(_norm(w), _TINY)[:, None]
        w = matvec(v)
    rate = _norm(w) / jnp.maximum(_norm(v), _TINY)
    rayleigh = jnp.sum(v * w, axis=1) / jnp.maximum(jnp.sum(v * v, axis=1), _TINY)
    return np.asarray(rate), np.asarray(rayleigh)


def _rayleigh_ritz(qs: jnp.ndarray, ws: jnp.ndarray):
    """Ritz values and per-pair residual norms of a projected operator.

    ``qs`` is an orthonormal basis block ``(B, k, nz)``, ``ws = M qs``.
    Returns ``(b_proj, theta, res)``: the ``(B, k, k)`` projection
    ``Q^T M Q``, its eigenvalues (complex, ``(B, k)``), and the
    residual norms ``||M y - theta y||`` of each Ritz pair ``y = Q u``
    (via the small Gram matrix of the residual block — only the two
    ``(k, k)`` matrices ever cross to the host).
    """
    b_proj_dev = jnp.einsum("bin,bjn->bij", qs, ws)
    # residual block R_j = (M q)_j - sum_i Q_i B_ij, Gram'd on device
    r = ws - jnp.einsum("bij,bin->bjn", b_proj_dev, qs)
    gram = np.asarray(jnp.einsum("bjn,bkn->bjk", r, r))
    b_proj = np.asarray(b_proj_dev)
    theta, u = np.linalg.eig(b_proj)
    quad = np.einsum("bjk,bkm->bjm", gram, u)
    res = np.sqrt(np.maximum(np.einsum("bjm,bjm->bm", np.conj(u), quad).real, 0.0))
    return b_proj, theta, res


def krylov_ritz(matvec, b: int, nz: int, m: int = 24):
    """Rayleigh-Ritz over an m-step Krylov space of ``M``.

    The exterior (largest-modulus) eigenvalues converge in a handful of
    matvecs — these are the modes whose ``(Re, |lambda|)`` the
    abscissa-aware dt rule needs.  Returns ``(theta, res)`` with
    ``theta`` the complex Ritz values ``(B, m)`` and ``res`` their
    residual norms.
    """
    m = min(m, nz)
    v = _init_vec(b, nz)
    v = v / jnp.maximum(_norm(v), _TINY)[:, None]
    q = [v]
    w_list = []
    scale = None
    for j in range(m - 1):
        w = matvec(q[-1])
        w_list.append(w)
        if scale is None:
            scale = _norm(w)
        qs = jnp.stack(q, axis=1)
        for _ in range(2):                       # MGS x2 (reorthogonalized)
            coeff = jnp.einsum("bjn,bn->bj", qs, w)
            w = w - jnp.einsum("bjn,bj->bn", qs, coeff)
        nw = _norm(w)
        # breakdown (invariant subspace hit): continue from a fresh
        # deterministic probe orthogonalized against the basis
        fresh = _init_block(b, nz, j % 7 + 2)[:, -1]
        for _ in range(2):
            coeff = jnp.einsum("bjn,bn->bj", qs, fresh)
            fresh = fresh - jnp.einsum("bjn,bj->bn", qs, coeff)
        fresh = fresh / jnp.maximum(_norm(fresh), _TINY)[:, None]
        ok = nw > 1e-10 * jnp.maximum(scale, _TINY)
        q.append(
            jnp.where(
                ok[:, None], w / jnp.maximum(nw, _TINY)[:, None], fresh
            )
        )
    w_list.append(matvec(q[-1]))
    qs = jnp.stack(q, axis=1)
    ws = jnp.stack(w_list, axis=1)
    _b_proj, theta, res = _rayleigh_ritz(qs, ws)
    return theta, res


def mode_dt_limit(
    theta: np.ndarray, res: np.ndarray, rate: np.ndarray
) -> np.ndarray:
    """Abscissa-aware forward-Euler stability limit from Ritz modes.

    The Euler circle requires ``dt < 2 |Re lambda| / |lambda|^2`` for
    *every* eigenvalue; for a (near-)real spectrum this reduces to the
    modulus rule ``2 / |lambda|_max``, but an underdamped pair
    (``|Im| >> |Re|``) binds much tighter.  The minimum is taken over
    trusted stable Ritz modes (residual below ``0.1 |theta|`` — the
    exterior modes that bind converge quickly), combined with the
    margined modulus rule, and floored at ``MODE_DT_FLOOR`` times the
    modulus rule so an unconverged near-imaginary Ritz value cannot
    collapse the step.  Returns the per-system limit (no safety factor
    applied).
    """
    rate = np.maximum(np.asarray(rate, dtype=np.float64), _TINY)
    modulus = 2.0 / (rate * RATE_MARGIN)
    absq = np.abs(theta) ** 2
    trusted = (
        (theta.real < 0.0)
        & (res < 0.1 * np.maximum(np.abs(theta), _TINY))
        & (absq > _TINY)
    )
    with np.errstate(divide="ignore", invalid="ignore"):
        per_mode = np.where(
            trusted, 2.0 * np.abs(theta.real) / np.maximum(absq, _TINY), np.inf
        )
    mode_rule = per_mode.min(axis=1) / MODE_MARGIN
    return np.maximum(np.minimum(modulus, mode_rule), MODE_DT_FLOOR * modulus)


@functools.partial(jax.jit, static_argnames=("steps",))
def _propagator_filter(matvec_block, tau, v0, *, steps: int):
    """``steps`` renormalized Euler-propagator applications of a block.

    ``matvec_block`` is a :class:`jax.tree_util.Partial` (its operator
    arrays trace as inputs, its function keys the compilation cache),
    so the filter compiles once per (operator form, shape) and is
    reused across calls.
    """

    def body(_, vv):
        vv = vv + tau * matvec_block(vv)
        nrm = jnp.sqrt(jnp.sum(vv * vv, axis=2, keepdims=True))
        return vv / jnp.maximum(nrm, _TINY)

    return jax.lax.fori_loop(0, steps, body, v0)


def slow_mode_ritz(
    matvec_block,
    rate: np.ndarray,
    b: int,
    nz: int,
    *,
    tau_limit: np.ndarray | None = None,
    block: int = 12,
    filter_steps: int = 64,
    max_cycles: int = 6,
    res_rtol: float = 1e-8,
):
    """Rightmost (slowest stable / most unstable) modes of ``M`` by
    propagator-filtered deflated subspace iteration.

    Each cycle pushes an orthonormal ``k``-block through ``p`` steps of
    the dt-stable Euler propagator ``P = I + tau M`` (``tau`` from the
    abscissa-aware limit, so the filter is contracting on every stable
    mode — including underdamped pairs — and *amplifying* exactly on
    unstable ones), re-orthonormalizes, and Rayleigh-Ritz-projects
    ``M`` onto the block.  The projection deflates the slow cluster:
    eigenvalues that the propagator maps within ``O(tau * gap)`` of
    each other — indistinguishable to power iteration — separate
    cleanly in the ``(k, k)`` projected eigenproblem.  Cycles repeat
    until the rightmost Ritz pair's residual drops below ``res_rtol``
    relative to ``rate`` (or ``max_cycles``).

    Returns ``(theta, res, fov_slow, cycles, basis)``: the final Ritz
    values ``(B, k)`` and residual norms, the restricted numerical
    abscissa ``lambda_max(sym(V^T M V))`` of the slow subspace, the
    cycle count used, and the final orthonormal block ``(B, k, nz)``
    spanning the slow subspace (rows are the basis vectors — the input
    of the amplitude projection in :func:`amplitude_settle_steps`).
    """
    k = min(block, nz)
    rate = np.maximum(np.asarray(rate, dtype=np.float64), _TINY)
    tau_np = 0.9 / rate
    if tau_limit is not None:
        tau_np = np.minimum(tau_np, 0.9 * np.asarray(tau_limit))
    tau = jnp.asarray(tau_np)[:, None, None]
    v = _orthonormalize_rows(_init_block(b, nz, k))

    theta = res = b_proj = None
    cycles = 0
    for cycles in range(1, max(max_cycles, 1) + 1):
        v = _orthonormalize_rows(
            _propagator_filter(matvec_block, tau, v, steps=filter_steps)
        )
        w = matvec_block(v)
        b_proj, theta, res = _rayleigh_ritz(v, w)
        i_right = np.argmax(theta.real, axis=1)
        r_right = res[np.arange(b), i_right] / rate
        if np.all(r_right < res_rtol):
            break
    fov_slow = np.linalg.eigvalsh(
        0.5 * (b_proj + b_proj.transpose(0, 2, 1))
    )[:, -1]
    return theta, res, fov_slow, cycles, np.asarray(v, dtype=np.float64)


def lanczos_sym_extreme(matvec_sym, b: int, nz: int, iters: int = 24):
    """Extreme eigenvalue estimates of a batched *symmetric* operator.

    Plain Lanczos (no reorthogonalization): ``iters`` matvecs, then an
    ``(iters, iters)`` tridiagonal eigenproblem per system.  Returns
    ``(theta_min, theta_max)`` as ``(B,)`` arrays.
    """
    m = min(iters, nz)
    q = _init_vec(b, nz)
    q = q / jnp.maximum(_norm(q), _TINY)[:, None]
    q_prev = jnp.zeros_like(q)
    beta_prev = jnp.zeros(b, dtype=jnp.float64)
    alphas, betas = [], []
    for _ in range(m):
        w = matvec_sym(q) - beta_prev[:, None] * q_prev
        alpha = jnp.sum(q * w, axis=1)
        w = w - alpha[:, None] * q
        beta = _norm(w)
        alphas.append(alpha)
        betas.append(beta)
        q_prev = q
        q = w / jnp.maximum(beta, _TINY)[:, None]
        beta_prev = beta
    a = np.stack([np.asarray(x) for x in alphas], axis=1)       # (B, m)
    beta = np.stack([np.asarray(x) for x in betas], axis=1)[:, : m - 1]
    t = np.zeros((b, m, m))
    ar = np.arange(m)
    t[:, ar, ar] = a
    if m > 1:
        t[:, ar[:-1], ar[1:]] = beta
        t[:, ar[1:], ar[:-1]] = beta
    theta = np.linalg.eigvalsh(t)
    return theta[:, 0], theta[:, -1]


# ---------------------------------------------------------------------------
# The combined estimate
# ---------------------------------------------------------------------------


def spectral_bounds(
    bss,
    *,
    iters: int = 32,
    krylov_m: int = 24,
    slow_iters: int = 6,
    slow_block: int = 12,
    filter_steps: int = 64,
    lanczos_iters: int = 0,
    dt_safety: float = 0.5,
    rtol: float = 0.01,
    res_rtol: float = 1e-8,
    cert_rtol: float = 0.5,
) -> SpectralBounds:
    """Spectral settling/stability estimates for a batch of LTI systems.

    ``bss`` is a dense ``(B, nz, nz)`` array, a
    :class:`repro.core.engine.BatchedStateSpace`, or an
    :class:`repro.core.engine.EllBatchedStateSpace` (matrix-free).

    ``slow_iters`` is the filter-cycle budget of the slow-mode
    extraction; ``slow_iters=0`` skips it (``slow_re`` NaN, ``settle_*``
    non-finite, ``stable``/``certified`` all-False) — the cheap
    configuration used for ``dt`` selection alone, which still runs the
    Krylov pass so the abscissa-aware step rule holds.
    ``lanczos_iters > 0`` additionally computes the strict global
    symmetric-part bound ``sym_max`` (vacuous for the circuit
    operators — kept for operators where it is not).

    ``certified[b]`` is True when system ``b``'s rightmost Ritz pair
    converged (residual below ``cert_rtol * |slow_re|``), its real part
    is negative, and the restricted numerical abscissa ``fov_slow`` is
    negative (the slow subspace contracts monotonically).  A False
    certificate does *not* mean unstable — it means the estimate did
    not converge tightly enough to certify.
    """
    mv, mvt, mvb, b, nz = _matvec_pair(bss)

    rate, _ray = power_rate(mv, b, nz, iters=iters)
    rate = np.maximum(rate, _TINY)

    theta_k, res_k = krylov_ritz(mv, b, nz, m=krylov_m)
    dt_limit = mode_dt_limit(theta_k, res_k, rate)
    dt = dt_safety * dt_limit

    slow = np.full(b, np.nan)
    slow_res = np.full(b, np.inf)
    fov_slow = None
    basis = None
    certified = np.zeros(b, dtype=bool)
    if slow_iters:
        theta_s, res_s, fov_slow, _cycles, basis = slow_mode_ritz(
            mvb,
            rate,
            b,
            nz,
            tau_limit=dt_limit,
            block=slow_block,
            filter_steps=filter_steps,
            max_cycles=slow_iters,
            res_rtol=res_rtol,
        )
        ar = np.arange(b)
        i_right = np.argmax(theta_s.real, axis=1)
        slow = theta_s.real[ar, i_right]
        slow_res = res_s[ar, i_right] / np.maximum(np.abs(slow), _TINY)
        certified = (slow < 0.0) & (slow_res < cert_rtol) & (fov_slow < 0.0)

    sym_max = None
    if lanczos_iters and mvt is not None:

        def mv_sym(z):
            return 0.5 * (mv(z) + mvt(z))

        _lo, sym_max = lanczos_sym_extreme(mv_sym, b, nz, iters=lanczos_iters)

    stable = slow < 0.0
    with np.errstate(divide="ignore", over="ignore"):
        settle = np.where(
            stable, np.log(1.0 / rtol) / np.maximum(-slow, _TINY), np.inf
        )
        steps = np.where(
            np.isfinite(settle), np.ceil(settle / dt), np.inf
        )
    return SpectralBounds(
        rate_max=rate,
        slow_re=slow,
        slow_residual=slow_res,
        fov_slow=fov_slow,
        sym_max=sym_max,
        dt_limit=dt_limit,
        dt=dt,
        settle_time=settle,
        settle_steps=steps,
        certified=certified,
        slow_basis=basis,
    )


# ---------------------------------------------------------------------------
# Amplitude-aware settling correction
# ---------------------------------------------------------------------------


def amplitude_settle_steps(
    bounds: SpectralBounds,
    z_err: np.ndarray,
    *,
    rtol: float = 0.01,
    x_scale: np.ndarray | None = None,
) -> np.ndarray:
    """Amplitude-corrected settle-step prediction ``(B,)``.

    ``SpectralBounds.settle_steps`` is amplitude-blind: it assumes the
    initial slow-mode amplitude equals the solution scale, i.e. a cold
    ``z0 = 0`` start (``ln(1/rtol)`` e-folds).  Given an estimate of the
    *initial error state* ``z_err = z0 - z*`` ``(B, nz)``, this projects
    it onto the extracted slow subspace and predicts
    ``ceil(ln(amp_slow / (rtol * x_scale)) / (|Re lambda_slow| dt))``
    steps instead — near zero for a warm start whose error has little
    slow-mode content, and tighter than the blind bound whenever the
    initial amplitude differs from the solution scale.

    ``x_scale`` ``(B,)`` is the per-system magnitude the convergence
    band is relative to (``max |x_ref|`` in the settle loop); defaults
    to ``max |z_err|`` per system.  At least one e-fold is always
    predicted (fast modes outside the slow subspace still need a few
    steps to die; the settle loop's converged check — not this
    prediction — decides actual termination, so the prediction only
    steers ``sweep_chunk_schedule`` and the refinement stopping rule).
    Unstable/uncertified systems keep the blind ``settle_steps``.
    """
    z = np.asarray(z_err, dtype=np.float64)
    if bounds.slow_basis is None:
        return np.asarray(bounds.settle_steps, dtype=np.float64)
    coeff = np.einsum("bkn,bn->bk", bounds.slow_basis, z)
    amp = np.linalg.norm(coeff, axis=1)
    if x_scale is None:
        x_scale = np.max(np.abs(z), axis=1)
    tol_abs = np.maximum(np.asarray(rtol, dtype=np.float64) * x_scale, _TINY)
    decay = np.maximum(-bounds.slow_re, _TINY) * np.asarray(bounds.dt)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        efolds = np.log(np.maximum(amp / tol_abs, np.e))
        steps = np.ceil(efolds / np.maximum(decay, _TINY))
    blind = np.asarray(bounds.settle_steps, dtype=np.float64)
    ok = bounds.stable & np.isfinite(steps)
    return np.where(ok, steps, blind)

"""Public solve API — the paper's technique as a composable module.

``solve(A, b, method=...)`` dispatches between the analog designs and
the digital baselines:

* ``analog_2n``   — the proposed 2n-design (Sec. IV).  Builds the
  netlist, runs the (non-ideal) operating point, optionally the LTI
  settling analysis.  This is the paper-faithful path.
* ``analog_n``    — the preliminary n-design (Sec. III) baseline.
* ``cholesky`` / ``cg`` / ``jacobi`` — digital baselines.

The analog paths execute the *simulated physics* of the circuit; the
result therefore carries the circuit's error model (op-amp offsets,
digital-pot quantization) and its settling time — the quantities the
paper evaluates.

``solve_batch(A, b)`` is the batched entry point: ``A`` is ``(B, n, n)``
and ``b`` ``(B, n)``; the netlists are built per system (vectorized
structure-of-arrays stamping) and then assembled, DC-solved (batched
f32 LU refined to fp64) and transient-analyzed as one batch on a shared stamp
pattern (see :mod:`repro.core.engine`).  ``solve`` is a thin B=1
wrapper over the same machinery for the analog methods, so single and
batched results agree by construction.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import span
from repro.core import baselines, engine, refine as refine_mod
from repro.core.network import (
    Netlist,
    build_preliminary,
    build_preliminary_batch,
    build_proposed,
    build_proposed_batch,
)
from repro.core.operating_point import (
    DEFAULT_NONIDEAL,
    IDEAL,
    NonIdealities,
    operating_point_batch,
    operating_point_batch_submit,
)
from repro.core.refine import RefineSpec  # noqa: F401  (re-export for callers)
from repro.core.specs import OPAMPS, CircuitParams, DEFAULT_PARAMS, OpAmpSpec


@dataclasses.dataclass
class SolveResult:
    x: np.ndarray
    method: str
    stable: bool = True
    settle_time: float | None = None
    info: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class BatchSolveResult:
    """Batched :class:`SolveResult`: every field is a (B, ...) array.

    ``info`` maps metric name -> (B,) array (or a scalar shared by the
    batch).  ``__getitem__`` recovers a per-system :class:`SolveResult`.
    """

    x: np.ndarray                     # (B, n)
    method: str
    stable: np.ndarray                # (B,) bool
    settle_time: np.ndarray | None    # (B,) or None
    info: dict[str, Any] = dataclasses.field(default_factory=dict)

    def __len__(self) -> int:
        return self.x.shape[0]

    @staticmethod
    def _info_entry(v, b: int):
        """Per-system view of one ``info`` entry.

        Per-system arrays are indexed; shared values (python scalars,
        0-d arrays, strings) pass through — and anything that lands as
        a numpy scalar (0-d array or ``np.generic``) is normalized to
        the matching python scalar, so batched and single-system
        results round-trip identically regardless of how the metric was
        recorded.
        """
        if isinstance(v, np.ndarray) and v.ndim >= 1:
            v = v[b]
        if isinstance(v, np.ndarray) and v.ndim == 0:
            v = v[()]
        if isinstance(v, np.generic):
            v = v.item()
        return v

    def __getitem__(self, b: int) -> SolveResult:
        info = {k: self._info_entry(v, b) for k, v in self.info.items()}
        return SolveResult(
            x=self.x[b],
            method=self.method,
            stable=bool(self.stable[b]),
            settle_time=(
                None if self.settle_time is None
                else float(self.settle_time[b])
            ),
            info=info,
        )


ANALOG_METHODS = ("analog_2n", "analog_n")
DIGITAL_METHODS = ("cholesky", "cg", "jacobi")

# digital re-solve policies for degraded analog results ("none" disables)
FALLBACK_METHODS = ("cholesky", "cg", "none")
# relative-residual ceiling above which an *uncertified* analog result
# counts as degraded (non-finite results always do)
FALLBACK_RESIDUAL_TOL = 1e-6


def fallback_mask(
    x: np.ndarray,
    a: np.ndarray,
    b: np.ndarray,
    certified=None,
    *,
    residual_tol: float = FALLBACK_RESIDUAL_TOL,
) -> np.ndarray:
    """Which systems of an analog batch need the digital fallback.

    A system is degraded when its solution carries NaN/Inf, or when its
    settling analysis did NOT certify (``settle_certified=False`` from
    the spectral estimator) *and* its relative residual
    ``||A x - b|| / ||b||`` overflows ``residual_tol`` — an uncertified
    solve with a small residual is still a good solve (the paper's
    guarantee is SDD-only; general SPD systems routinely settle fine
    without a certificate), so certification alone never triggers the
    re-solve.
    """
    x = np.asarray(x, dtype=np.float64)
    bad = ~np.isfinite(x).all(axis=1)
    if certified is not None:
        cert = np.asarray(certified, dtype=bool).reshape(-1)
        check = (~cert) & (~bad)
        if check.any():
            r = np.einsum("bij,bj->bi", a[check], x[check]) - b[check]
            rel = np.linalg.norm(r, axis=1) / np.maximum(
                np.linalg.norm(b[check], axis=1), np.finfo(np.float64).tiny
            )
            bad[np.flatnonzero(check)[rel > residual_tol]] = True
    return bad


def _apply_digital_fallback(
    result: "BatchSolveResult",
    a: np.ndarray,
    b: np.ndarray,
    *,
    method: str,
    tol: float,
    max_iter: int,
    residual_tol: float,
) -> "BatchSolveResult":
    """Numerical graceful degradation: re-solve degraded analog systems
    with a digital baseline, in place on ``result``.

    The circuit metrics (``stable``, ``settle_time``, error model) keep
    describing the *analog* attempt; only ``x`` rows are replaced, and
    ``info["fallback"]`` records the per-system re-solve method (empty
    string = the analog solution was delivered as-is).
    """
    bad = fallback_mask(
        result.x, a, b, result.info.get("settle_certified"),
        residual_tol=residual_tol,
    )
    if not bad.any():
        return result
    x = np.array(result.x, dtype=np.float64, copy=True)
    x[bad] = _digital_resolve(a[bad], b[bad], method=method, tol=tol,
                              max_iter=max_iter)
    result.x = x
    result.info["fallback"] = np.where(bad, method, "")
    return result


def _digital_resolve(
    a: np.ndarray, b: np.ndarray, *, method: str, tol: float, max_iter: int
) -> np.ndarray:
    """Digital re-solve of a (sub)batch — the fallback workhorse."""
    if method == "cholesky":
        return np.asarray(
            baselines.cholesky_solve_batch(jnp.asarray(a), jnp.asarray(b))
        )
    return np.asarray(
        baselines.cg_solve_batch(
            jnp.asarray(a), jnp.asarray(b), tol=tol, max_iter=max_iter
        ).x
    )


# per-system delivery paths of the graded-recovery pipeline (recorded in
# info["precision_path"] when refine= is enabled):
#   "analog"    — the raw analog solve already met the refinement tol
#   "refined"   — iterative refinement converged to the tol
#   "fallback"  — refinement stalled / exhausted; digital re-solve delivered
#   "unrefined" — refinement failed and fallback="none": degraded result
PRECISION_PATHS = ("analog", "refined", "fallback", "unrefined")


@span("core.refine")
def _apply_graded_recovery(
    result: "BatchSolveResult",
    a: np.ndarray,
    b: np.ndarray,
    *,
    refspec: "refine_mod.RefineSpec",
    method: str,
    spec: OpAmpSpec,
    ni: NonIdealities,
    params: CircuitParams,
    d_policy: str,
    beta: float,
    alpha: float,
    pattern: "engine.StampPattern | None",
    mesh,
    device,
    fallback: str,
    tol: float,
    max_iter: int,
) -> "BatchSolveResult":
    """Residual-verified graded recovery: verify -> refine -> fall back.

    Replaces the binary fallback mask with a three-stage pipeline.  Every
    analog solution is *verified* against its fp64 relative residual; rows
    above ``refspec.tol`` enter mixed-precision iterative refinement
    (:mod:`repro.core.refine`) where each inner pass re-stamps and
    re-solves the *analog* circuit for the current residual — rescaled to
    the original right-hand side's full scale first, because the
    hardware's absolute error floor (op-amp offsets, supply-pot
    quantization) would otherwise swamp a tiny residual RHS — and only
    rows whose refinement stalls or exhausts its budget escalate to the
    digital ``fallback``.  The delivery route is recorded per system in
    ``info["precision_path"]`` (see :data:`PRECISION_PATHS`), alongside
    ``info["residual"]`` (final fp64 relative residual) and
    ``info["refine_iters"]`` (inner analog solves consumed).
    """
    b_count = a.shape[0]
    tiny = np.finfo(np.float64).tiny
    rel = refine_mod.relative_residuals(a, b, result.x)
    refine_iters = np.zeros(b_count, dtype=np.int64)
    path = np.full(b_count, "analog", dtype="<U9")
    need = rel > refspec.tol
    if need.any():
        sel = np.flatnonzero(need)
        bscale = np.maximum(np.max(np.abs(b), axis=1), tiny)

        def inner_solve(idx: np.ndarray, rhs: np.ndarray) -> np.ndarray:
            # analog inner pass: re-stamp the circuit for (A, r*s) with
            # the SAME error model (deterministic per-net perturbation
            # draws) and DC-solve it.  The residual is rescaled to the
            # original RHS's full scale so the hardware's absolute error
            # floor stays *relative* to the update being computed — the
            # property that makes each pass contract by ~eps_hw.
            rows = sel[np.asarray(idx)]
            s = bscale[rows] / np.maximum(np.max(np.abs(rhs), axis=1), tiny)
            nets_r = _build_nets(
                a[rows], rhs * s[:, None], method,
                d_policy=d_policy, beta=beta, alpha=alpha, params=params,
            )
            pat = (
                pattern
                if pattern is not None and engine.pattern_covers(pattern, nets_r)
                else None
            )
            op = operating_point_batch(
                nets_r, spec, nonideal=ni, pattern=pat, mesh=mesh,
                device=device,
            )
            return np.asarray(op.x, dtype=np.float64) / s[:, None]

        driver = refine_mod.refine_driver(refspec)
        rr = driver(a[sel], b[sel], result.x[sel], inner_solve, spec=refspec)
        x = np.array(result.x, dtype=np.float64, copy=True)
        x[sel] = rr.x
        rel[sel] = rr.residual
        refine_iters[sel] = rr.iters
        path[sel] = np.where(rr.converged, "refined", "unrefined")

        bad = sel[~rr.converged]
        if bad.size and fallback != "none":
            x[bad] = _digital_resolve(
                a[bad], b[bad], method=fallback, tol=tol, max_iter=max_iter
            )
            rel[bad] = refine_mod.relative_residuals(a[bad], b[bad], x[bad])
            path[bad] = "fallback"
        result.x = x
    result.info["residual"] = rel
    result.info["refine_iters"] = refine_iters
    result.info["precision_path"] = path
    # kept for callers of the binary-era contract (service counters):
    # per-system digital re-solve method, "" = analog/refined delivery
    result.info["fallback"] = np.where(path == "fallback", fallback, "")
    return result


@span("core.build_nets")
def _build_nets(
    a: np.ndarray,
    b: np.ndarray,
    method: str,
    *,
    d_policy: str,
    beta: float,
    alpha: float,
    params: CircuitParams,
) -> list[Netlist]:
    if method == "analog_2n":
        return build_proposed_batch(
            a, b, d_policy=d_policy, beta=beta, alpha=alpha, params=params
        )
    if method == "analog_n":
        return build_preliminary_batch(a, b, params=params)
    raise ValueError(f"unknown analog method {method!r}")


@dataclasses.dataclass
class PendingBatchSolve:
    """Handle to an in-flight batched solve on one device.

    :func:`solve_batch_submit` did the host-side work (netlist build,
    error model, assembly) and *dispatched* the device solve; under JAX
    async dispatch the device computes while the caller builds its next
    micro-batch — the solve service's overlap model.  :meth:`wait`
    blocks on the device result and materializes the
    :class:`BatchSolveResult`; it returns exactly what ``solve_batch``
    with the same arguments returns, because ``solve_batch`` *is*
    submit + wait.  ``wait()`` is idempotent.

    The analog paths are *two-phase*: ``_finalize`` harvests only the
    device's DC operating point (the part that occupies the stream),
    and ``_finish`` runs the post-DC analysis — the settling transient
    and the digital-fallback check — on the harvested result.
    :meth:`wait_dc` blocks on phase one alone, after which the stream
    that ran the solve is free for its next dispatch; :meth:`wait`
    composes both phases, so blocking callers see the exact pre-split
    semantics.  ``split`` tells a scheduler whether deferring the
    finish phase buys anything (digital handles are single-phase).
    """

    method: str
    _finalize: Callable[[], BatchSolveResult]
    _done: BatchSolveResult | None = None
    _finish: Callable[[BatchSolveResult], BatchSolveResult] | None = None
    _dc: BatchSolveResult | None = None

    @property
    def split(self) -> bool:
        """True when :meth:`wait_dc` frees the stream before the finish
        phase (settle sweep / fallback) has run."""
        return self._finish is not None

    def wait_dc(self) -> BatchSolveResult:
        """Block on the *device phase* only (DC solve harvest).

        For a split handle the returned result carries no settle
        metrics and no fallback yet — :meth:`wait` completes them.  For
        a single-phase handle this is :meth:`wait`.  Idempotent.
        """
        if self._done is not None:
            return self._done
        if self._finish is None:
            return self.wait()
        if self._dc is None:
            self._dc = self._finalize()
        return self._dc

    def wait(self) -> BatchSolveResult:
        if self._done is None:
            if self._finish is not None:
                self._done = self._finish(self.wait_dc())
            else:
                self._done = self._finalize()
        return self._done


def _solve_batch_digital_submit(
    a: np.ndarray,
    b: np.ndarray,
    method: str,
    *,
    tol: float,
    max_iter: int,
    mesh=None,
    device=None,
) -> PendingBatchSolve:
    """Batched digital-baseline dispatch (vmapped Cholesky, batched
    CG/Jacobi with per-system convergence freezing).

    Mirrors the single-system digital branch of :func:`solve` exactly:
    ``stable`` is all-True (the baselines carry no circuit stability
    notion) and ``info`` holds per-system ``iterations`` /
    ``residual_norm`` for the iterative methods, so
    ``solve_batch(...)[k]`` round-trips to what ``solve(a[k], b[k])``
    returns.  ``mesh`` (a 1-d solver mesh, see
    :func:`repro.distributed.sharding.solver_mesh`) shards the batch
    axis over devices; ``device`` places the whole batch on one device
    (the serving streams) — the jitted baselines dispatch async either
    way, and the returned handle materializes on ``wait()``.
    """
    with span("core.transfer"):
        if device is not None:
            aj = jax.device_put(a, device)
            bj = jax.device_put(b, device)
        else:
            aj = jnp.asarray(a)
            bj = jnp.asarray(b)
    if mesh is not None:
        from repro.distributed.sharding import shard_system_batch

        aj, bj = shard_system_batch(aj, bj, mesh=mesh)

    n_systems = a.shape[0]
    if method == "cholesky":
        x_dev = baselines.cholesky_solve_batch(aj, bj)

        def finalize() -> BatchSolveResult:
            return BatchSolveResult(
                x=np.asarray(x_dev),
                method=method,
                stable=np.ones(n_systems, dtype=bool),
                settle_time=None,
                info={},
            )

    else:
        fn = (
            baselines.cg_solve_batch
            if method == "cg"
            else baselines.jacobi_solve_batch
        )
        res = fn(aj, bj, tol=tol, max_iter=max_iter)

        def finalize() -> BatchSolveResult:
            return BatchSolveResult(
                x=np.asarray(res.x),
                method=method,
                stable=np.ones(n_systems, dtype=bool),
                settle_time=None,
                info={
                    "iterations": np.asarray(res.iterations, dtype=np.int64),
                    "residual_norm": np.asarray(
                        res.residual_norm, dtype=np.float64
                    ),
                },
            )

    return PendingBatchSolve(method=method, _finalize=finalize)


def solve_batch_submit(
    a,
    b,
    *,
    method: str = "analog_2n",
    opamp: str | OpAmpSpec = "AD712",
    nonideal: NonIdealities | None = None,
    params: CircuitParams = DEFAULT_PARAMS,
    d_policy: str = "proposed",
    beta: float = 0.5,
    alpha: float = 1.0,
    compute_settling: bool = False,
    settle_method: str = "auto",
    settle_max_steps: int = 200_000,
    settle_dt_policy: str = "diag",
    settle_matrix_free: bool = False,
    x_ref: np.ndarray | None = None,
    tol: float = 1e-10,
    max_iter: int = 10000,
    fallback: str = "cholesky",
    fallback_residual_tol: float = FALLBACK_RESIDUAL_TOL,
    refine=None,
    sweep_dtype: str = "float32",
    settle_x0: np.ndarray | None = None,
    pattern: "engine.StampPattern | None" = None,
    mesh=None,
    device=None,
    nets: list[Netlist] | None = None,
) -> PendingBatchSolve:
    """Host phase + async device dispatch of :func:`solve_batch`.

    Validates, builds the netlists, applies the error model and
    assembles the batch (host-side), then *dispatches* the device solve
    and returns a :class:`PendingBatchSolve` without blocking — the
    caller overlaps the device's factorization with its next
    micro-batch's host build (JAX async dispatch works on every
    backend, including forced host-platform devices).  ``device``
    places the whole batch on one device — the serving v2 per-device
    streams (mutually exclusive with ``mesh``, which shards the batch
    axis instead).  All other arguments match :func:`solve_batch`,
    which *is* ``solve_batch_submit(...).wait()`` — parity between the
    blocking and pipelined paths holds by construction.

    The analog handle is two-phase: ``wait_dc()`` harvests the DC
    operating point — the only part occupying the dispatch stream —
    and ``wait()`` additionally runs the finish phase
    (``compute_settling`` transient + digital fallback).  A pipelined
    caller (the solve service) harvests the DC phase, re-arms the
    stream, and defers the synchronous settle sweep; a blocking caller
    just calls ``wait()`` and sees the composed result.
    """
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.ndim != 3 or b.ndim != 2 or a.shape[:2] != (b.shape[0], b.shape[1]):
        raise ValueError(f"expected (B, n, n) and (B, n); got {a.shape}, {b.shape}")
    if mesh is not None and device is not None:
        raise ValueError("pass either mesh= or device=, not both")
    if method in DIGITAL_METHODS:
        return _solve_batch_digital_submit(
            a, b, method, tol=tol, max_iter=max_iter, mesh=mesh, device=device
        )
    if method not in ANALOG_METHODS:
        raise ValueError(
            f"unknown method {method!r}: expected one of "
            f"{ANALOG_METHODS + DIGITAL_METHODS}"
        )
    if fallback is None:
        fallback = "none"
    if fallback not in FALLBACK_METHODS:
        raise ValueError(
            f"unknown fallback {fallback!r}: expected one of "
            f"{FALLBACK_METHODS}"
        )
    refspec = refine_mod.as_refine_spec(refine)

    spec = OPAMPS[opamp] if isinstance(opamp, str) else opamp
    ni = IDEAL if nonideal is None else nonideal

    # device work outside the DC dispatch (the netlist transform, the
    # finish phase) runs on the stream's device, not the process default
    def on_device():
        return (jax.default_device(device) if device is not None
                else contextlib.nullcontext())

    if nets is None:
        with on_device():
            nets = _build_nets(
                a, b, method, d_policy=d_policy, beta=beta, alpha=alpha,
                params=params,
            )
    elif len(nets) != a.shape[0]:
        raise ValueError(f"got {len(nets)} nets for a batch of {a.shape[0]}")
    if pattern is None:
        pattern = engine.pattern_union(nets, spec)
    if compute_settling and settle_matrix_free and x_ref is None:
        # caller error: surface at submit time, not from inside wait()
        raise ValueError("settle_matrix_free requires x_ref")
    # non-idealities perturb conductance values, never the cell pattern,
    # so the clean-net pattern is shared with the OP assembly
    pending_op = operating_point_batch_submit(
        nets, spec, nonideal=ni, x_ref=x_ref, pattern=pattern, mesh=mesh,
        device=device,
    )

    def finalize_dc() -> BatchSolveResult:
        op = pending_op.wait()
        info: dict[str, Any] = {
            "design": np.asarray([net.design for net in nets]),
            "n_nodes": nets[0].n_nodes,
            "n_amps": np.asarray([net.n_amps for net in nets]),
            "n_branches": np.asarray([net.n_branches for net in nets]),
            "is_passive": np.asarray([net.is_passive for net in nets]),
            "max_conductance": np.asarray(
                [net.max_conductance() for net in nets]
            ),
            "max_rel_error": op.max_rel_error,
            "max_abs_error": op.max_abs_error,
            "err_fullscale": op.err_fullscale,
        }
        return BatchSolveResult(
            x=op.x,
            method=method,
            stable=~op.amp_saturated,
            settle_time=None,
            info=info,
        )

    def finish(result: BatchSolveResult) -> BatchSolveResult:
        with on_device():
            return _finish(result)

    def _finish(result: BatchSolveResult) -> BatchSolveResult:
        if compute_settling:
            # x_ref reaches the transient engine only on explicit opt-in
            # (or for the estimator-only spectral path, where it merely
            # fills x_converged): the default euler/auto path keeps its
            # settle-against-DC-fixed-point semantics
            settle_ref = (
                x_ref if (settle_matrix_free or settle_method == "spectral")
                else None
            )
            tr = engine.transient_batch(
                nets, spec, method=settle_method, pattern=pattern,
                max_steps=settle_max_steps,
                x_ref=settle_ref,
                dt_policy=settle_dt_policy,
                x0=settle_x0,
                sweep_dtype=sweep_dtype,
            )
            result.settle_time = tr.settle_time
            result.stable = result.stable & tr.stable
            result.info["max_re_eig"] = tr.max_re_eig
            result.info["dominant_tau"] = tr.dominant_tau
            result.info["mirror_residual"] = tr.mirror_residual
            result.info["settle_method"] = tr.method
            if tr.settle_steps is not None:
                result.info["settle_steps"] = np.asarray(
                    tr.settle_steps, dtype=np.int64
                )
            if tr.certified is not None:
                # spectral estimator: converged rightmost mode +
                # contracting slow subspace (see
                # repro.core.spectral.SpectralBounds)
                result.info["settle_certified"] = tr.certified
        if refspec is not None:
            # residual-verified graded recovery: fp64 verify -> analog
            # iterative refinement -> digital fallback only for rows
            # whose refinement stalls (see _apply_graded_recovery)
            return _apply_graded_recovery(
                result, a, b, refspec=refspec, method=method, spec=spec,
                ni=ni, params=params, d_policy=d_policy, beta=beta,
                alpha=alpha, pattern=pattern, mesh=mesh, device=device,
                fallback=fallback, tol=tol, max_iter=max_iter,
            )
        if fallback != "none":
            # numerical graceful degradation: non-finite (or
            # uncertified-with-residual-overflow) analog rows re-solve
            # digitally, recorded per system in info["fallback"]
            result = _apply_digital_fallback(
                result, a, b, method=fallback, tol=tol, max_iter=max_iter,
                residual_tol=fallback_residual_tol,
            )
        return result

    return PendingBatchSolve(method=method, _finalize=finalize_dc, _finish=finish)


def solve_batch(
    a,
    b,
    *,
    method: str = "analog_2n",
    opamp: str | OpAmpSpec = "AD712",
    nonideal: NonIdealities | None = None,
    params: CircuitParams = DEFAULT_PARAMS,
    d_policy: str = "proposed",
    beta: float = 0.5,
    alpha: float = 1.0,
    compute_settling: bool = False,
    settle_method: str = "auto",
    settle_max_steps: int = 200_000,
    settle_dt_policy: str = "diag",
    settle_matrix_free: bool = False,
    x_ref: np.ndarray | None = None,
    tol: float = 1e-10,
    max_iter: int = 10000,
    fallback: str = "cholesky",
    fallback_residual_tol: float = FALLBACK_RESIDUAL_TOL,
    refine=None,
    sweep_dtype: str = "float32",
    settle_x0: np.ndarray | None = None,
    pattern: "engine.StampPattern | None" = None,
    mesh=None,
    device=None,
    nets: list[Netlist] | None = None,
) -> BatchSolveResult:
    """Solve a batch of SPD systems ``A[k] x[k] = b[k]``.

    ``a`` is (B, n, n), ``b`` (B, n); all systems share one circuit
    design, so assembly, DC solve and settling run as single batched
    device calls.  ``method`` dispatches exactly like :func:`solve`:
    the analog designs run the batched circuit physics, while
    ``"cholesky"`` / ``"cg"`` / ``"jacobi"`` run the batched digital
    baselines (vmapped factorization, batched iterations with
    per-system convergence freezing — ``tol`` / ``max_iter`` apply to
    the iterative ones).  ``settle_method`` selects the transient path
    ("eig" — exact modal, the small-nz reference; "euler" — Pallas
    forward-Euler sweep; "spectral" — the matrix-free settling
    *estimate*, no integration: deflated rightmost-mode extraction
    within 2x of the exact slow mode plus ``settle_certified``
    stability flags in ``info``; "auto" — by state count).
    ``settle_dt_policy`` picks the euler step rule ("diag" |
    "spectral" — the abscissa-aware per-mode rule, valid for
    underdamped operators; see :func:`repro.core.engine._settle_dt`).

    ``settle_matrix_free=True`` opts the euler path into the ELL
    engine: assembly and sweep run device-resident with no
    ``(B, nz, nz)`` build, settling against ``x_ref`` (required)
    instead of the circuit's DC fixed point — semantics the default
    preserves for existing callers — and ``mirror_residual`` is NaN
    (there is no DC state to read the mirror nodes from).

    ``pattern`` pre-pins the shared stamp pattern (it must cover every
    system's cells — the solve service caches one per request bucket
    and reuses it across micro-batches); ``mesh`` shards the batch
    axis of the heavy device calls (DC solve / digital baselines) over
    a 1-d solver mesh (:func:`repro.distributed.sharding.solver_mesh`);
    ``device`` instead places the whole batch on one device (the
    serving streams' placement mode — see :func:`solve_batch_submit`
    for the non-blocking form this function wraps).
    ``nets`` hands over pre-built netlists for the analog methods (they
    MUST be the builders' output for exactly ``(a, b, method)`` and the
    design options — a performance passthrough for callers like the
    solve service that already built them, not a way to solve arbitrary
    netlists; use :func:`repro.core.engine.transient_batch` for that).

    ``fallback`` is the numerical graceful-degradation policy for the
    analog methods: a system whose analog solution comes back
    non-finite — or uncertified (``settle_certified=False``) with a
    relative residual above ``fallback_residual_tol`` — is re-solved
    by the named digital baseline (``"cholesky"`` default, ``"cg"``,
    or ``"none"`` to deliver the degraded analog result as-is), with
    the per-system re-solve recorded in ``info["fallback"]``.  The
    circuit diagnostics (``stable``, ``settle_time``, error model)
    keep describing the analog attempt.

    ``refine`` upgrades the binary fallback into *graded recovery*
    (``None``/``False`` — off, the pre-existing behavior; ``True`` —
    the default :class:`repro.core.refine.RefineSpec`; a driver name
    ``"ir"``/``"fcg"`` or a full spec): every analog solution is
    verified against its fp64 relative residual, rows above the
    refinement tol run mixed-precision iterative refinement with the
    analog circuit as the inner solve, and only stalled rows escalate
    to ``fallback``.  The result then carries ``info["residual"]``,
    ``info["refine_iters"]`` and ``info["precision_path"]`` (per
    system, one of :data:`PRECISION_PATHS`).

    ``sweep_dtype`` ("float32" | "bfloat16") selects the Euler settle
    sweep's weight precision (bf16 storage / fp32 accumulate — halves
    the dominant sweep traffic; the settling verdict then certifies
    only a widened band, ``engine.BF16_SETTLE_RTOL``, with fp64
    recovery delegated to ``refine``).  ``settle_x0`` ((B, n)) warm
    starts the settle sweep from a previous solution — the session
    warm-start path of the solve service.
    """
    return solve_batch_submit(
        a,
        b,
        method=method,
        opamp=opamp,
        nonideal=nonideal,
        params=params,
        d_policy=d_policy,
        beta=beta,
        alpha=alpha,
        compute_settling=compute_settling,
        settle_method=settle_method,
        settle_max_steps=settle_max_steps,
        settle_dt_policy=settle_dt_policy,
        settle_matrix_free=settle_matrix_free,
        x_ref=x_ref,
        tol=tol,
        max_iter=max_iter,
        fallback=fallback,
        fallback_residual_tol=fallback_residual_tol,
        refine=refine,
        sweep_dtype=sweep_dtype,
        settle_x0=settle_x0,
        pattern=pattern,
        mesh=mesh,
        device=device,
        nets=nets,
    ).wait()


def solve(
    a,
    b,
    *,
    method: str = "analog_2n",
    opamp: str | OpAmpSpec = "AD712",
    nonideal: NonIdealities | None = None,
    params: CircuitParams = DEFAULT_PARAMS,
    d_policy: str = "proposed",
    beta: float = 0.5,
    alpha: float = 1.0,
    compute_settling: bool = False,
    settle_method: str = "auto",
    settle_max_steps: int = 200_000,
    settle_dt_policy: str = "diag",
    settle_matrix_free: bool = False,
    x_ref: np.ndarray | None = None,
    tol: float = 1e-10,
    max_iter: int = 10000,
    fallback: str = "cholesky",
    fallback_residual_tol: float = FALLBACK_RESIDUAL_TOL,
    refine=None,
    sweep_dtype: str = "float32",
) -> SolveResult:
    """Solve the SPD system ``A x = b``.

    ``nonideal=None`` uses the ideal component model for the analog
    paths (still finite-gain/offset-free); pass
    :data:`repro.core.operating_point.DEFAULT_NONIDEAL` or a custom
    :class:`NonIdealities` to engage the hardware error model.

    The analog paths are thin wrappers over :func:`solve_batch` with a
    batch of one, and forward the settling controls unchanged —
    ``settle_method`` / ``settle_dt_policy`` / ``settle_matrix_free`` /
    ``settle_max_steps`` carry the same defaults and semantics as
    :func:`solve_batch`, so single and batched callers reach the
    euler/spectral paths identically.  ``"auto"`` resolves by state
    count exactly as in the batched path: the exact modal reference up
    to ``engine.EIG_STATE_LIMIT`` states, the f32 Euler sweep beyond
    (pass ``settle_method="eig"`` to force the exact path — the
    pre-PR-3 behavior — at any size).
    """
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)

    if method in DIGITAL_METHODS:
        if method == "cholesky":
            x = np.asarray(baselines.cholesky_solve(a, b))
            return SolveResult(x=x, method=method)
        fn = baselines.cg_solve if method == "cg" else baselines.jacobi_solve
        res = fn(a, b, tol=tol, max_iter=max_iter)
        return SolveResult(
            x=np.asarray(res.x),
            method=method,
            info={
                "iterations": int(res.iterations),
                "residual_norm": float(res.residual_norm),
            },
        )

    batch = solve_batch(
        a[None, :, :],
        b[None, :],
        method=method,
        opamp=opamp,
        nonideal=nonideal,
        params=params,
        d_policy=d_policy,
        beta=beta,
        alpha=alpha,
        compute_settling=compute_settling,
        settle_method=settle_method,
        settle_max_steps=settle_max_steps,
        settle_dt_policy=settle_dt_policy,
        settle_matrix_free=settle_matrix_free,
        x_ref=None if x_ref is None else np.asarray(x_ref)[None, :],
        tol=tol,
        max_iter=max_iter,
        fallback=fallback,
        fallback_residual_tol=fallback_residual_tol,
        refine=refine,
        sweep_dtype=sweep_dtype,
    )
    return batch[0]

"""TPU-side complexity benchmark — the hardware-adaptation claim.

DESIGN.md §3: the paper's O(1) wall-clock does not transfer to a digital
simulation, but its *structure* does — the number of transient steps to
settle is set by matrix properties (max transformed conductance /
deviation from diagonal dominance), NOT by n, while the per-step cost is
one SpMV at the memory roofline.

The sweep runs on the matrix-free engine: every system of a size class
is stamped onto the shared ``(n, design)`` pattern by the jitted ELL
scatter assembly (``assemble_batch_ell`` — device-resident, nothing of
size ``(B, nz, nz)`` is ever built) and integrated together by the
Pallas ELL-SpMV sweep kernel (forward Euler, gathered row reduction,
fused ``max |M z + c|`` settling-check).  On CPU the kernels execute in
interpret mode; on TPU they compile to the VPU gather path.

  * fixed max transformed conductance (the Fig. 13 protocol) across
    sizes -> step count flat in n  (the paper's claim, on TPU terms)
  * per-step cost: ELL touches ``nz * K`` (weight, index) pairs + O(nz)
    update -> bandwidth-bound; reported as bytes/step.  The dense sweep
    reads ``nz^2`` weights — the ELL path is what lets the size sweep
    reach n in the thousands (``sparse_sweep``), where the dense
    operators no longer fit memory at all.

Sub-benchmarks (all emitted by ``run`` / recorded in ``BENCH_pr2.json``
by ``benchmarks.run``):

  * :func:`run`            — the conductance-matched step-count sweep.
  * :func:`sparse_sweep`   — n into the thousands at fixed row degree,
                             with the spectral settling *prediction*
                             (deflated rightmost-mode estimate,
                             :mod:`repro.core.spectral`) recorded next
                             to the measured sweep steps at every size:
                             the predicted-vs-measured curve is the
                             end-to-end validation of the paper's
                             eigenvalue-governed settling law.
  * :func:`dense_vs_ell`   — wall-clock speedup at the largest size the
                             dense fused sweep still handles.
  * :func:`parity_check`   — CI guard: dense and ELL paths must agree
                             (assembly to f64 round-off, identical step
                             counts, f32-level states); exits non-zero
                             on drift.
  * :func:`settling_accuracy` — CI guard: the spectral slow-mode
                             estimate must stay within [0.5, 2.0]x of
                             the exact-eig reference on the small-nz
                             reference set (both designs, non-SDD SPD
                             included); exits non-zero outside the
                             band.

    PYTHONPATH=src:. python -m benchmarks.tpu_complexity [--full]
    PYTHONPATH=src:. python -m benchmarks.tpu_complexity --parity
    PYTHONPATH=src:. python -m benchmarks.tpu_complexity --settling
"""

from __future__ import annotations

import sys
import time
import zlib

import numpy as np

from benchmarks.common import US, emit, enable_compile_cache, stats
from repro.core import engine
from repro.core.network import build_proposed


def run(full: bool = False, interpret: bool | None = None) -> list[dict]:
    from repro.data.spd import random_spd_fixed_conductance

    rng = np.random.default_rng(77)
    sizes = (30, 60, 120) if not full else (30, 60, 120, 240)
    count = 3 if not full else 8
    rows = []
    for n in sizes:
        nets, xs = [], []
        for _ in range(count):
            out = random_spd_fixed_conductance(rng, n, g_target=800 * US)
            if out is None:
                continue
            a, x, b = out
            nets.append(build_proposed(a, b))
            xs.append(x)
        if not nets:
            rows.append({"name": f"tpu_complexity_n{n}", "count": 0})
            continue
        ell = engine.assemble_batch_ell(nets)
        t0 = time.perf_counter()
        steps, _x, res, _dt = engine.euler_settle_batch(
            ell, np.stack(xs), interpret=interpret
        )
        wall = time.perf_counter() - t0
        nz = ell.n_states
        k = ell.ell_width
        s = stats(list(steps))
        rows.append({
            "name": f"tpu_complexity_n{n}",
            "steps_median": s["median"],
            "steps_p90": s["p90"],
            "ell_width": k,
            "fill_ratio": k / nz,
            "flops_per_step": 2.0 * nz * k,
            "bytes_per_step": nz * k * 8 + 3 * nz * 4,
            "residual_max": float(np.max(res)),
            "batch_wall_s": wall,
            "count": s["n"],
        })
    return rows


def _sparse_systems(rng, n: int, count: int, row_degree: int = 16):
    """Sparse paper-protocol systems at a fixed expected row degree."""
    from repro.data.spd import random_spd, random_rhs_from_solution

    density = min(1.0, row_degree / max(n, 1))
    nets, xs = [], []
    for _ in range(count):
        a = random_spd(rng, n, density=density)
        x, b = random_rhs_from_solution(rng, a)
        nets.append(build_proposed(a, b))
        xs.append(x)
    return nets, np.stack(xs), density


def sparse_sweep(
    full: bool = False,
    interpret: bool | None = None,
    *,
    sizes: tuple[int, ...] | None = None,
    count: int = 2,
    max_steps: int = 30_000,
    check_every: int = 250,
) -> list[dict]:
    """Size sweep at fixed row degree — the O(1)-vs-n story at scale.

    The ELL operators keep per-system memory at O(nz * K), so the sweep
    reaches n = 2048 (nz = 16384; the dense ``(B, nz, nz)`` batch would
    need > 4 GB in f64 **per pair of systems** and is recorded as
    infeasible).
    """
    from repro.kernels.ops import sweep_backend

    rng = np.random.default_rng(99)
    if sizes is None:
        sizes = (128, 256, 512, 1024, 2048) if not full else (
            128, 256, 512, 1024, 2048, 4096)
    from repro.core import spectral

    rows = []
    for n in sizes:
        nets, x, density = _sparse_systems(rng, n, count)
        t0 = time.perf_counter()
        ell = engine.assemble_batch_ell(nets)
        ell.weights.block_until_ready()
        t_assemble = time.perf_counter() - t0
        nz, k = ell.n_states, ell.ell_width
        # the estimator's prediction, before (and independent of) the
        # measured integration: steps = ceil(t_settle / dt) at the
        # sweep's dt rule
        t0 = time.perf_counter()
        sb = spectral.spectral_bounds(ell)
        t_spectral = time.perf_counter() - t0
        t0 = time.perf_counter()
        steps, _xf, res, dt = engine.euler_settle_batch(
            ell, x, max_steps=max_steps, check_every=check_every,
            interpret=interpret,
        )
        t_sweep = time.perf_counter() - t0
        s = stats(list(steps))
        # compare in time units (the sweep's dt_policy="diag" step
        # differs from the spectral dt): measured settle time vs the
        # slow-mode prediction ln(1/rtol)/|Re lambda_slow|
        measured_t = np.where(steps < max_steps, steps * dt, np.nan)
        pred_t = np.where(np.isfinite(sb.settle_time), sb.settle_time, np.nan)
        with np.errstate(invalid="ignore"):
            ratio = pred_t / measured_t
        ratio = ratio[np.isfinite(ratio)]
        rows.append({
            "name": f"tpu_sparse_n{n}",
            "n": n,
            "batch": count,
            "nz": nz,
            "ell_width": k,
            "fill_ratio": k / nz,
            "density": density,
            "backend": sweep_backend(nz, k),
            "steps_median": s["median"],
            "steps_p90": s["p90"],
            "settled": int(np.sum(steps < max_steps)),
            "predicted_steps_median": float(np.median(sb.settle_steps)),
            "predicted_settle_s_median": float(np.median(sb.settle_time)),
            "measured_settle_s_median": float(np.nanmedian(measured_t)),
            "pred_over_measured_median": (
                float(np.median(ratio)) if ratio.size else float("nan")
            ),
            "slow_re_median": float(np.median(sb.slow_re)),
            "certified": int(np.sum(sb.certified)),
            "bytes_per_step": nz * k * 8 + 3 * nz * 4,
            "dense_bytes_f64": float(count) * nz * nz * 8,
            "dense_feasible": count * nz * nz * 8 < 2e9,
            "residual_max": float(np.max(res)),
            "assemble_wall_s": t_assemble,
            "spectral_wall_s": t_spectral,
            "sweep_wall_s": t_sweep,
        })
    return rows


def dense_vs_ell(
    n: int = 192,
    count: int = 2,
    *,
    max_steps: int = 20_000,
    check_every: int = 250,
    interpret: bool | None = None,
) -> dict:
    """Wall-clock speedup of the matrix-free path over the dense sweep
    at the largest size the dense *fused* kernel still handles
    (``SWEEP_STATE_LIMIT``); beyond it the dense path streams the whole
    operator from HBM every step and stops being a usable baseline.
    """
    rng = np.random.default_rng(55)
    nets, x, density = _sparse_systems(rng, n, count)

    t0 = time.perf_counter()
    ell = engine.assemble_batch_ell(nets)
    ell.weights.block_until_ready()
    t_ae = time.perf_counter() - t0
    t0 = time.perf_counter()
    se, xe, _re, _dt = engine.euler_settle_batch(
        ell, x, max_steps=max_steps, check_every=check_every,
        interpret=interpret,
    )
    t_se = time.perf_counter() - t0

    t0 = time.perf_counter()
    dense = engine.assemble_batch(nets)
    t_ad = time.perf_counter() - t0
    t0 = time.perf_counter()
    sd, xd, _rd, _dt = engine.euler_settle_batch(
        dense, x, max_steps=max_steps, check_every=check_every,
        interpret=interpret,
    )
    t_sd = time.perf_counter() - t0

    return {
        "name": f"dense_vs_ell_n{n}",
        "n": n,
        "batch": count,
        "nz": ell.n_states,
        "ell_width": ell.ell_width,
        "density": density,
        "steps": int(se.max()),
        "steps_match": bool(np.array_equal(sd, se)),
        "x_max_diff": float(np.abs(xd - xe).max()),
        "ell_assemble_s": t_ae,
        "ell_sweep_s": t_se,
        "dense_assemble_s": t_ad,
        "dense_sweep_s": t_sd,
        "sweep_speedup": t_sd / max(t_se, 1e-9),
        "end_to_end_speedup": (t_ad + t_sd) / max(t_ae + t_se, 1e-9),
    }


def parity_check(
    sizes: tuple[int, ...] = (16, 48),
    count: int = 3,
    *,
    max_steps: int = 40_000,
    atol_m_rel: float = 1e-12,
    atol_x: float = 2e-5,
    interpret: bool | None = None,
) -> list[str]:
    """Dense <-> ELL drift guard (the CI benchmark smoke).

    Runs the n-sweep on both operator forms and returns a list of
    failure strings (empty == parity holds): assembly must match to f64
    round-off, settling step counts must be identical, and the f32
    sweep states must agree to ``atol_x``.
    """
    from repro.data.spd import random_spd, random_rhs_from_solution

    rng = np.random.default_rng(123)
    failures = []
    for n in sizes:
        nets, xs = [], []
        for k in range(count):
            a = random_spd(rng, n)
            if k == 1:
                a = -a        # non-PD: parity must hold off the happy path
            # the generator draws x exactly and forms b = A x, so x IS
            # the solution — valid for the sign-flipped system too
            x, b = random_rhs_from_solution(rng, a)
            nets.append(build_proposed(a, b))
            xs.append(x)
        x = np.stack(xs)
        dense = engine.assemble_batch(nets)
        ell = engine.assemble_batch_ell(nets)
        scale = float(np.abs(dense.m).max())
        m_err = float(np.abs(ell.to_dense() - dense.m).max())
        if m_err > atol_m_rel * scale:
            failures.append(
                f"n={n}: assembly drift {m_err:.3e} > {atol_m_rel:.0e} * {scale:.3e}"
            )
        sd, xd, _r, _dt = engine.euler_settle_batch(
            dense, x, max_steps=max_steps, interpret=interpret
        )
        se, xe, _r, _dt = engine.euler_settle_batch(
            ell, x, max_steps=max_steps, interpret=interpret
        )
        if not np.array_equal(sd, se):
            failures.append(f"n={n}: step counts diverge {sd} vs {se}")
        x_err = float(np.abs(xd - xe).max())
        if x_err > atol_x:
            failures.append(f"n={n}: sweep state drift {x_err:.3e} > {atol_x:.0e}")
    return failures


def settling_accuracy(
    *,
    ratio_lo: float = 0.5,
    ratio_hi: float = 2.0,
) -> list[str]:
    """Spectral-vs-eig slow-mode guard (the CI settling-accuracy step).

    Runs the spectral estimator and the exact stacked eigendecomposition
    over the small-nz reference set — proposed and preliminary designs,
    non-diagonally-dominant SPD and SDD systems — and returns failure
    strings (empty == contract holds) whenever the slow-mode estimate
    ``Re lambda_slow`` leaves ``[ratio_lo, ratio_hi]`` times the exact
    rightmost eigenvalue, or an unstable system is not flagged.
    """
    from repro.core import spectral
    from repro.core.network import build_preliminary
    from repro.data.spd import (
        random_rhs_from_solution,
        random_sdd,
        random_spd,
    )

    failures = []
    cases = [
        ("proposed", build_proposed, 14, 4, dict()),
        ("proposed_sparse", build_proposed, 20, 3, dict(density=0.4)),
        ("preliminary", build_preliminary, 12, 3, dict()),
        ("sdd", build_proposed, 12, 3, dict(sdd=True)),
        ("non_pd", build_proposed, 10, 3, dict(non_pd=True)),
    ]
    for label, builder, n, count, opts in cases:
        rng = np.random.default_rng(zlib.crc32(label.encode()))
        nets = []
        for k in range(count):
            density = opts.get("density", 1.0)
            a = random_spd(rng, n, density=density)
            if opts.get("non_pd") and k == count - 1:
                a = -a
            if opts.get("sdd") and k == count - 1:
                a = random_sdd(rng, n)
            _x, b = random_rhs_from_solution(rng, a)
            nets.append(builder(a, b))
        dense = engine.assemble_batch(nets)
        ell = engine.assemble_batch_ell(nets)
        sb = spectral.spectral_bounds(ell)
        lam = np.linalg.eigvals(dense.m)
        abscissa = lam.real.max(axis=1)
        for k in range(count):
            if abscissa[k] >= 0:
                if sb.slow_re[k] < 0:
                    failures.append(
                        f"{label}[{k}]: unstable system (abscissa "
                        f"{abscissa[k]:.3e}) not flagged"
                    )
                continue
            true_slow = lam[k].real[lam[k].real < 0].max()
            ratio = sb.slow_re[k] / true_slow
            if not (ratio_lo <= ratio <= ratio_hi):
                failures.append(
                    f"{label}[{k}]: slow-mode ratio {ratio:.3f} outside "
                    f"[{ratio_lo}, {ratio_hi}] (est {sb.slow_re[k]:.4e} "
                    f"vs exact {true_slow:.4e})"
                )
    return failures


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--parity", action="store_true",
                    help="dense<->ELL drift guard; exit 1 on drift")
    ap.add_argument("--settling", action="store_true",
                    help="spectral-vs-eig slow-mode guard; exit 1 when "
                         "the ratio leaves [0.5, 2.0]")
    args = ap.parse_args()
    enable_compile_cache()
    if args.parity:
        fails = parity_check()
        for f in fails:
            print(f"PARITY DRIFT: {f}", file=sys.stderr)
        print(f"parity_check,failures,{len(fails)}")
        raise SystemExit(1 if fails else 0)
    if args.settling:
        fails = settling_accuracy()
        for f in fails:
            print(f"SETTLING DRIFT: {f}", file=sys.stderr)
        print(f"settling_accuracy,failures,{len(fails)}")
        raise SystemExit(1 if fails else 0)
    print("name,metric,value")
    emit(run(full=args.full))
    emit(sparse_sweep(full=args.full))
    emit([dense_vs_ell()])

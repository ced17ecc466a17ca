"""Newton-round + FEM-stream benchmarks (``BENCH_pr8.json``).

The two PR-8 workloads on the batched engine, with a regression
baseline gated exactly like the solve-service document:

* **newton** — B independent Newton minimizations driven in lockstep
  (:func:`repro.optim.batched_newton.newton_batch`: ONE ``solve_batch``
  round per iteration) against the one-system-at-a-time looped
  reference, per backend.  Reports wall clock per Newton iteration,
  the batched/looped speedup, and an iterate-parity audit (identical
  iteration counts; iterates equal to last-ulp LAPACK nondeterminism).
  A third executor point runs the same batched driver through a
  :class:`repro.serving.solve_service.SolveSession` — the serving
  round-trip price on top of the raw batched engine.
* **fem** — a seeded mixed-grid FEM Poisson stream
  (:func:`repro.data.fem.mesh_stream`) served through
  :class:`~repro.serving.solve_service.SolveService` one-shot tickets.
  Reports requests/sec and audits every delivered solution against the
  direct ``solve()`` of the same system (``PARITY_ATOL``); the error
  against the exact dense reference rides along as a diagnostic.

CLI: ``PYTHONPATH=src:. python -m benchmarks.newton_fem [--smoke]
[--json BENCH_pr8.json] [--baseline BENCH_pr8.json]`` — or through the
orchestrator, ``python -m benchmarks.run --newton --fem``.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import enable_compile_cache
from benchmarks.solve_service import REGRESSION_TOL

PARITY_ATOL = 1e-9
# batched-vs-looped iterate agreement: exact up to last-ulp LAPACK
# nondeterminism between the vmapped and single-system factorizations
ITERATE_ATOL = 1e-12
BENCH_SCHEMA = "bench_pr8.v1"


# ------------------------------------------------------------- newton
def newton_problem(bsz: int, n: int, seed: int):
    """B smooth nonquadratic minimizations with SPD Hessians:
    ``f_k(x) = sum_i [ (x_i - t_i)^2 / 2 + (x_i - t_i)^4 / 4 ]`` plus a
    random SPD coupling — several Newton iterations to converge, known
    curvature structure, iteration-invariant sparsity class."""
    rng = np.random.default_rng(seed)
    t = rng.normal(size=(bsz, n))
    q = rng.normal(size=(bsz, n, n)) / np.sqrt(n)
    q = 0.5 * np.einsum("bij,bkj->bik", q, q) + np.eye(n)

    def grad_hess(x):
        d = x - t
        g = np.einsum("bij,bj->bi", q, d) + d**3
        h = q.copy()
        idx = np.arange(n)
        h[:, idx, idx] += 3.0 * d**2
        return g, h

    return grad_hess, t


def newton_point(
    method: str, *, bsz: int, n: int, seed: int, repeats: int,
    executor: str = "batched",
) -> dict:
    """One (method, executor) measurement: best-of-``repeats`` wall for
    the batched driver, one looped-reference pass, parity audit."""
    from repro.optim.batched_newton import (
        BatchedNewtonConfig,
        newton_batch,
        newton_looped,
    )

    cfg = BatchedNewtonConfig(method=method, tol=1e-8)
    grad_hess, _t = newton_problem(bsz, n, seed)
    x0 = np.zeros((bsz, n))

    def run_batched():
        if executor == "service":
            from repro.serving.solve_service import SolveService

            svc = SolveService(batch_slots=bsz)
            return newton_batch(
                grad_hess, x0, cfg, rounds=svc.session(method=method)
            )
        return newton_batch(grad_hess, x0, cfg)

    tr = run_batched()                      # warm pass pays compilation
    wall = np.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        tr = run_batched()
        wall = min(wall, time.perf_counter() - t0)

    t0 = time.perf_counter()
    ref = newton_looped(grad_hess, x0, cfg)
    looped_wall = time.perf_counter() - t0

    iters_equal = bool(np.array_equal(tr.iterations, ref.iterations))
    maxdiff = float(np.abs(tr.x - ref.x).max())
    total_iters = int(tr.iterations.sum())
    return {
        "method": method,
        "executor": executor,
        "batch": bsz,
        "n": n,
        "wall_s": float(wall),
        "looped_wall_s": float(looped_wall),
        "speedup_vs_looped": float(looped_wall / wall),
        "newton_iterations": total_iters,
        "solve_rounds": int(tr.solve_rounds),
        "wall_per_round_ms": float(1e3 * wall / max(tr.solve_rounds, 1)),
        "converged": bool(tr.converged.all()),
        "iters_equal": iters_equal,
        "iterate_maxdiff": maxdiff,
        "parity_failures": (
            [] if iters_equal and maxdiff <= ITERATE_ATOL
            else [{"method": method, "executor": executor,
                   "iters_equal": iters_equal, "maxdiff": maxdiff}]
        ),
    }


def newton_sweep(*, smoke: bool, seed: int, repeats: int) -> list[dict]:
    bsz, n = (6, 8) if smoke else (16, 16)
    points = []
    for method in ("cholesky", "analog_2n"):
        points.append(newton_point(
            method, bsz=bsz, n=n, seed=seed, repeats=repeats,
        ))
    # the serving round-trip: same driver, rounds through SolveService
    points.append(newton_point(
        "analog_2n", bsz=bsz, n=n, seed=seed, repeats=repeats,
        executor="service",
    ))
    return points


# ---------------------------------------------------------------- fem
def fem_stream_point(
    *, smoke: bool, seed: int, repeats: int, n_devices: int = 1,
) -> dict:
    """Mixed-grid Poisson stream through SolveService one-shots.

    Every delivered solution is audited against the direct ``solve()``
    of the identical padded-free system (the service contract); the
    error against the exact dense reference is recorded as a
    diagnostic (the analog error model, not a service property).
    """
    from repro.core.solver import solve
    from repro.data.fem import mesh_stream
    from repro.serving.faults import SolveError
    from repro.serving.solve_service import SolveService

    grids = ((4, 4), (5, 5), (6, 6), (8, 8))
    count = 12 if smoke else 48
    meshes = list(mesh_stream(seed, count, grids=grids))
    svc = SolveService(batch_slots=4, n_devices=n_devices)

    def pass_once():
        rids = [svc.submit(m.a, m.b, method="analog_2n") for m in meshes]
        t0 = time.perf_counter()
        results = svc.drain()
        return rids, results, time.perf_counter() - t0

    rids, results, _ = pass_once()          # warmup + audit pass
    worst = 0.0
    ref_err = 0.0
    failures = []
    errors = 0
    for rid, m in zip(rids, meshes):
        r = results[rid]
        if isinstance(r, SolveError):
            errors += 1
            continue
        direct = solve(m.a, m.b, method="analog_2n")
        err = float(np.abs(r.x - direct.x).max())
        worst = max(worst, err)
        x_ref = np.linalg.solve(m.a, m.b)
        ref_err = max(ref_err, float(np.abs(r.x - x_ref).max()
                                     / np.abs(x_ref).max()))
        if err > PARITY_ATOL:
            failures.append({"rid": rid, "grid": (m.nx, m.ny), "err": err})

    wall = np.inf
    for _ in range(max(1, repeats)):
        _, _, w = pass_once()
        wall = min(wall, w)
    stats = svc.stats
    return {
        "meshes": len(meshes),
        "grids": sorted({(m.nx, m.ny) for m in meshes}),
        "devices": n_devices,
        "wall_s": float(wall),
        "requests_per_s": float(len(meshes) / wall),
        "pad_overhead": float(stats["pad_overhead"]),
        "pattern_derivations": sum(
            b["pattern_derivations"] for b in stats["buckets"].values()
        ),
        "parity_worst": worst,
        "rel_err_vs_dense": ref_err,
        "errors": errors,
        "parity_failures": failures,
    }


# ---------------------------------------------------------------- doc
def build_doc(
    *, smoke: bool, seed: int = 0, repeats: int = 3,
    newton: bool = True, fem: bool = True,
) -> dict:
    import jax

    doc: dict = {
        "schema": BENCH_SCHEMA,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "smoke": bool(smoke),
    }
    if newton:
        pts = newton_sweep(smoke=smoke, seed=seed, repeats=repeats)
        doc["newton_sweep"] = pts
        print("newton,method,executor,wall_per_round_ms,speedup_vs_looped")
        for p in pts:
            print(f"newton,{p['method']},{p['executor']},"
                  f"{p['wall_per_round_ms']:.2f},"
                  f"{p['speedup_vs_looped']:.2f}")
    if fem:
        pt = fem_stream_point(smoke=smoke, seed=seed + 1, repeats=repeats)
        doc["fem_stream"] = pt
        print(f"fem,requests_per_s,{pt['requests_per_s']:.3f}")
        print(f"fem,rel_err_vs_dense,{pt['rel_err_vs_dense']:.3g}")
    doc["parity_failures"] = [
        f
        for p in doc.get("newton_sweep", [])
        for f in p["parity_failures"]
    ] + list(doc.get("fem_stream", {}).get("parity_failures", []))
    return doc


# ------------------------------------------------------- baseline gate
def extract_series(doc: dict) -> tuple[dict, dict]:
    """``(contextual, free)`` series for the gate — same split as
    :func:`benchmarks.solve_service.extract_series`: absolutes only
    compare within a stream context (same ``smoke`` flag),
    dimensionless ratios compare across."""
    ctx: dict[str, float] = {}
    free: dict[str, float] = {}
    for p in doc.get("newton_sweep", []):
        if p["executor"] == "service":
            # per-round wall through the service is fixed host
            # round-trip overhead at bench sizes — run-to-run jitter
            # exceeds the gate tolerance; diagnostic only
            continue
        tag = f"{p['method']}@{p['executor']}"
        ctx[f"newton_wall_per_round_ms@{tag}"] = float(p["wall_per_round_ms"])
        free[f"newton_speedup@{tag}"] = float(p["speedup_vs_looped"])
    fs = doc.get("fem_stream")
    if fs:
        ctx["fem_requests_per_s"] = float(fs["requests_per_s"])
        ctx["fem_pad_overhead"] = float(fs["pad_overhead"])
    return ctx, free


def compare_to_baseline(
    current: dict, baseline: dict, *, tol: float = REGRESSION_TOL
) -> list[dict]:
    cur_ctx, cur_free = extract_series(current)
    base_ctx, base_free = extract_series(baseline)
    same_ctx = bool(current.get("smoke")) == bool(baseline.get("smoke"))
    violations: list[dict] = []

    def check(name: str, cur: float, base: float) -> None:
        higher_is_worse = "wall" in name or "pad_overhead" in name
        ok = (cur <= base * (1 + tol)) if higher_is_worse \
            else (cur >= base * (1 - tol))
        if not ok:
            violations.append(
                {"metric": name, "current": cur, "baseline": base,
                 "tolerance": tol}
            )

    if same_ctx:
        for k in sorted(cur_ctx.keys() & base_ctx.keys()):
            check(k, cur_ctx[k], base_ctx[k])
    for k in sorted(cur_free.keys() & base_free.keys()):
        check(k, cur_free[k], base_free[k])
    return violations


def apply_gate(doc: dict, baseline_path: str) -> list[dict]:
    if not baseline_path:
        return []
    with open(baseline_path) as fh:
        baseline = json.load(fh)
    return compare_to_baseline(doc, baseline)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default="BENCH_pr8.json",
                    help="output path ('' to skip)")
    ap.add_argument("--baseline", default="",
                    help="committed BENCH_pr8.json to gate against")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-newton", dest="newton", action="store_false")
    ap.add_argument("--no-fem", dest="fem", action="store_false")
    args = ap.parse_args()
    enable_compile_cache()

    doc = build_doc(smoke=args.smoke, seed=args.seed, repeats=args.repeats,
                    newton=args.newton, fem=args.fem)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True, default=str)
        print(f"bench_json,path,{args.json}")

    ok = not doc["parity_failures"]
    print(f"newton_fem,parity,{'OK' if ok else 'FAIL'}")
    violations = apply_gate(doc, args.baseline)
    for v in violations:
        print(f"newton_fem,regression,{v['metric']}: "
              f"{v['current']:.4g} vs baseline {v['baseline']:.4g}")
    if violations or not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

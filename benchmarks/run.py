"""Benchmark orchestrator — one function per paper table/figure.

Prints ``name,metric,value`` CSV rows.  ``--full`` reproduces the
paper-scale sweeps (slow); the default is a reduced CPU-friendly pass.

The figure sweeps run on the batched engine (``repro.core.engine``):
each size/parameter class is one batched operating-point call (fp64-refined
DC solve) plus one batched settling call (stacked-eig modal path, or
the matrix-free ELL sweep for ``tpu_complexity``), instead of
per-system Python loops.

Unfiltered invocations (no ``--only``; force with ``--pr2`` / suppress
with ``--no-pr2``) also write a machine-readable perf trajectory to
``BENCH_pr2.json`` (``--json`` to relocate): wall-clock per phase, the
sparse n/B sweep points (n up to 2048 on the ELL path — sizes the
dense operators cannot reach), the dense-vs-ELL speedup at the largest
dense-feasible size, and the parity-guard verdict.  Future PRs regress
against this file: with ``--baseline`` (bare form auto-picks the
committed ``BENCH_pr2.json``, loaded before ``--json`` overwrites it)
the fresh trajectory is diffed against it through the shared series
gate — per-size sparse-sweep walls compare within the same
``--full`` context, the dense-vs-ELL speedups always.

The ``service`` phase (gate with ``--service`` / ``--no-service``;
default mirrors the pr2 gate) runs the streamed solve-service
benchmark — slot sweep, device-stream sweep, overlap probe, plus
the seeded fault-injection sweep (req/s at 0%/5%/20% fault rates) —
and writes its throughput/parity baseline to ``BENCH_pr7.json``
(``--json-service`` to relocate).  ``--baseline PATH`` additionally
diffs that document against a committed prior ``BENCH_pr*.json`` and
fails the run on a >25% regression of requests/sec, pad overhead,
sweep wall time or fault-mode throughput retention (the
device-scaling monotonicity check runs whether or not a baseline file
is given); ``--smoke`` shrinks the service stream to the CI-sized
pass.

The ``--newton`` / ``--fem`` phases (default: unfiltered runs) run the
PR-8 workloads — batched-vs-looped Newton per-iteration wall (plus the
service-session round-trip) and the mixed-grid FEM Poisson stream
through the solve service — writing ``BENCH_pr8.json``
(``--json-newton-fem`` to relocate) and gating against a committed
``BENCH_pr8.json`` under the same ``--baseline`` machinery.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig12,...]
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python -m benchmarks.run --only none \
        --service --smoke --json-service "" --baseline BENCH_pr6.json
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.common import enable_compile_cache

BENCH_SCHEMA = "bench_pr2.v1"


def _pr2_trajectory(full: bool) -> dict:
    """The PR-2 perf baseline: matrix-free sweep points + speedup."""
    from benchmarks.tpu_complexity import dense_vs_ell, parity_check, sparse_sweep

    out: dict = {}
    t0 = time.time()
    out["sparse_sweep"] = sparse_sweep(full=full)
    out["sparse_sweep_wall_s"] = time.time() - t0
    t0 = time.time()
    out["dense_vs_ell"] = dense_vs_ell()
    out["dense_vs_ell_wall_s"] = time.time() - t0
    t0 = time.time()
    out["parity_failures"] = parity_check(sizes=(16,), max_steps=20_000)
    out["parity_wall_s"] = time.time() - t0
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig12,fig13")
    ap.add_argument("--json", default="BENCH_pr2.json",
                    help="perf-baseline output path ('' to skip)")
    ap.add_argument("--pr2", default=None, action=argparse.BooleanOptionalAction,
                    help="run the PR-2 perf trajectory (sparse n-sweep, "
                         "dense-vs-ELL, parity); default: only on "
                         "unfiltered runs")
    ap.add_argument("--json-service", default="BENCH_pr7.json",
                    help="solve-service baseline output path ('' to skip)")
    ap.add_argument("--service", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="run the solve-service phase (streamed "
                         "throughput sweeps + parity); default: only on "
                         "unfiltered runs")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized service stream (full mix, 1 repeat)")
    ap.add_argument("--baseline", default=None, nargs="?", const="auto",
                    help="gate each phase against a committed "
                         "BENCH_*.json (>25%% regression fails); bare "
                         "--baseline auto-picks per phase: BENCH_pr2.json "
                         "for the pr2 trajectory, the newest "
                         "BENCH_pr7/pr6/pr5.json for the service phase, "
                         "BENCH_pr8.json for newton/fem")
    ap.add_argument("--newton", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="run the batched-Newton phase (batched vs "
                         "looped per-iteration wall + service-session "
                         "round-trip); default: only on unfiltered runs")
    ap.add_argument("--fem", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="run the FEM mesh-stream phase (mixed-grid "
                         "Poisson through the solve service); default: "
                         "only on unfiltered runs")
    ap.add_argument("--json-newton-fem", default="BENCH_pr8.json",
                    help="newton/fem baseline output path ('' to skip)")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks.common import emit
    from benchmarks.paper_figs import ALL

    only = set(filter(None, args.only.split(",")))
    t0 = time.time()
    phases: dict[str, float] = {}
    print("name,metric,value")
    for key, fn in ALL.items():
        if only and key not in only:
            continue
        t = time.time()
        try:
            rows = fn(full=args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{key},ERROR,{e!r}", file=sys.stderr)
            raise
        emit(rows)
        phases[key] = time.time() - t
        print(f"{key},wall_s,{phases[key]:.1f}")

    want_pr2 = args.pr2 if args.pr2 is not None else not only
    if want_pr2:
        import os

        import jax

        from benchmarks.solve_service import compare_to_baseline

        # resolve and LOAD the committed baseline before --json
        # overwrites it with the fresh trajectory
        pr2_baseline = args.baseline or ""
        if pr2_baseline == "auto":
            pr2_baseline = ("BENCH_pr2.json"
                            if os.path.exists("BENCH_pr2.json") else "")
        base_doc = None
        if pr2_baseline:
            with open(pr2_baseline) as fh:
                base_doc = json.load(fh)
            print(f"pr2,baseline_file,{pr2_baseline}")

        doc = {
            "schema": BENCH_SCHEMA,
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "full": bool(args.full),
            "phases_wall_s": phases,
            **_pr2_trajectory(args.full),
        }
        doc["total_wall_s"] = time.time() - t0
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(doc, fh, indent=2, sort_keys=True)
            print(f"bench_json,path,{args.json}")
        # the drift gate fails the run whether or not the baseline
        # file was written: parity first, then the series regression
        # diff (sparse-sweep walls contextual, dense-vs-ELL speedups
        # always compared) through the shared PR-6 gate machinery
        violations = compare_to_baseline(doc, base_doc) if base_doc else []
        for v in violations:
            print(f"pr2,regression,{v['metric']}: "
                  f"{v['current']:.4g} vs baseline {v['baseline']:.4g}",
                  file=sys.stderr)
        if doc["parity_failures"] or violations:
            print("bench_json,parity,FAIL", file=sys.stderr)
            raise SystemExit(1)
        print("bench_json,pr2_gate,OK")

    want_service = args.service if args.service is not None else not only
    if want_service:
        import os

        from benchmarks.solve_service import apply_gate, build_doc

        t5 = time.time()
        doc_svc = build_doc(smoke=bool(args.smoke or not args.full),
                            faults=True)
        print(f"service,wall_s,{time.time() - t5:.1f}")
        if args.json_service:
            with open(args.json_service, "w") as fh:
                json.dump(doc_svc, fh, indent=2, sort_keys=True, default=str)
            print(f"bench_json,path,{args.json_service}")
        baseline_path = args.baseline or ""
        if baseline_path == "auto":
            baseline_path = next(
                (p for p in ("BENCH_pr7.json", "BENCH_pr6.json",
                              "BENCH_pr5.json")
                 if os.path.exists(p)), "",
            )
            if baseline_path:
                print(f"service,baseline_file,{baseline_path}")
        violations = apply_gate(doc_svc, baseline_path)
        for v in violations:
            print(f"service,regression,{v['metric']}: "
                  f"{v['current']:.4g} vs baseline {v['baseline']:.4g}",
                  file=sys.stderr)
        if doc_svc["parity_failures"] or violations:
            print("bench_json,service_gate,FAIL", file=sys.stderr)
            raise SystemExit(1)
        print("bench_json,service_gate,OK")

    want_newton = args.newton if args.newton is not None else not only
    want_fem = args.fem if args.fem is not None else not only
    if want_newton or want_fem:
        import os

        from benchmarks.newton_fem import apply_gate as nf_gate, build_doc as nf_doc

        t8 = time.time()
        doc_nf = nf_doc(smoke=bool(args.smoke or not args.full),
                        newton=want_newton, fem=want_fem)
        print(f"newton_fem,wall_s,{time.time() - t8:.1f}")
        if args.json_newton_fem:
            with open(args.json_newton_fem, "w") as fh:
                json.dump(doc_nf, fh, indent=2, sort_keys=True, default=str)
            print(f"bench_json,path,{args.json_newton_fem}")
        nf_baseline = args.baseline or ""
        if nf_baseline == "auto":
            nf_baseline = "BENCH_pr8.json" if os.path.exists(
                "BENCH_pr8.json") else ""
            if nf_baseline:
                print(f"newton_fem,baseline_file,{nf_baseline}")
        violations = nf_gate(doc_nf, nf_baseline)
        for v in violations:
            print(f"newton_fem,regression,{v['metric']}: "
                  f"{v['current']:.4g} vs baseline {v['baseline']:.4g}",
                  file=sys.stderr)
        if doc_nf["parity_failures"] or violations:
            print("bench_json,newton_fem_gate,FAIL", file=sys.stderr)
            raise SystemExit(1)
        print("bench_json,newton_fem_gate,OK")
    print(f"total,wall_s,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()

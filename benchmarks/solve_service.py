"""Solve-service throughput benchmark (streaming + fault tolerance).

Streams a mixed-size request set (n in {16, 64, 192}, both analog
designs plus a digital baseline) through :class:`repro.serving.SolveService`
and records steady-state requests/sec versus batch-slot count and
device-stream count into ``BENCH_pr7.json``.  Every request's solution
is checked against a direct :func:`repro.core.solver.solve` — any
mismatch beyond tolerance is a benchmark *failure* (nonzero exit),
which is how the CI forced-multi-device smoke job guards the streamed
dispatch path.  ``--faults`` adds the degraded-mode sweep: the same
stream under a seeded chaos injector at 0%/5%/20% fault rates,
recording the throughput retained while the retry/bisection/breaker
machinery keeps delivery exactly-once (delivered solutions still
parity-audit; un-savable tickets land as counted structured errors).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python -m benchmarks.solve_service --smoke --faults

Measurement protocol (v2):

* every ``run_service`` point runs the stream TWICE on one service —
  an untimed warmup pass compiles each bucket's executable on each
  device it streams to (per-device placement means per-device
  executables), then the timed pass measures the steady state the
  serving story is about.  The v1 numbers timed first-pass compiles.
* the ``device_sweep`` scales the round-robin stream count
  (``n_devices=1, 2, all``) with whole micro-batches per device — the
  GSPMD within-micro-batch sharding whose measured scaling *inverted*
  (BENCH_pr5.json: 15.2 -> 3.5 -> 0.67 req/s at 1 -> 2 -> 8) is gone.
  The gate checks requests/sec is non-decreasing in the stream count.
* the ``overlap_probe`` compares ``inflight_per_device=1`` (serial
  build -> solve -> unpack) against ``2`` (double-buffered) on one
  device — the host-build/device-solve overlap in isolation.
* each point reports the wall-clock split from ``SolveService.stats``
  (``host_build_s`` / ``device_wait_s`` / ``unpack_s``, timed pass
  only): on a saturated stream ``device_wait_s`` is the device time
  the overlapped host phases could not hide.

``--baseline BENCH_pr6.json`` (or any prior ``BENCH_pr*.json``) gates
the run against a committed baseline: >25% regression on
requests/sec, pad overhead, sweep wall time or fault-mode throughput
retention fails the run.
Absolute series — and the device-scaling curve, whose honest value
depends on the stream size — compare only between runs of the same
``--smoke`` context; the overlap speedup and fault-mode throughput
retention always compare, and the device-scaling *monotonicity* check
guards the v1 inversion anti-result in every run regardless of
context.  ``--smoke`` shrinks the stream (CI wall-clock) but
keeps the full size/method mix and the >= 2-device sweep point.  The
analog_n design rides at n=16 only: its preliminary netlist carries
O(n^2) cells, so larger sizes belong to the 2n design by construction
(Table 2).

``--precision`` runs the mixed-precision recovery sweep instead of the
throughput sweeps: quantization bits x conductance tolerance x sweep
dtype cells, each solving the same fixed SPD batch on the degraded
hardware model twice — raw (the analog answer as-is) and under graded
recovery (``refine=`` iterative refinement with the analog settle as
inner solve, digital fallback only past the budget).  The document
(``BENCH_pr9.json``, schema ``bench_pr9.v1``) records accuracy
recovered vs refinement cost per cell; the acceptance cell (8-bit
pots, 1% tolerance) must recover every system to rel residual <=
1e-10 *without* digital fallback, or the run fails.  The accuracy
series are context-free under ``--baseline`` (the system set is
identical in smoke and full runs — only the cell grid shrinks); cell
walls stay contextual.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import enable_compile_cache

PARITY_ATOL = 1e-9
BENCH_SCHEMA = "bench_pr7.v1"
PRECISION_SCHEMA = "bench_pr9.v1"
# the residual-verified precision contract: graded recovery must land
# every delivered solution at or below this fp64 relative residual
PRECISION_TOL = 1e-10
# refinement budget for the precision sweep: the worst int8+1% rows
# contract ~0.3x per pass and need ~16 inner solves, so the sweep runs
# a research budget above the serving default (RefineSpec.max_iters=12,
# a latency contract that escalates slow rows to digital fallback)
PRECISION_BUDGET = 24
# degraded-throughput sweep points for --faults mode
FAULT_RATES = (0.0, 0.05, 0.20)
# baseline gate: fail on >25% regression of any compared series
REGRESSION_TOL = 0.25
# device-scaling monotonicity: allow this much timing noise per step.
# Calibrated to the smoke stream, where a single point is ~0.7 s of
# wall clock and best-of-N repeats still carry ~10% machine noise; the
# anti-result this check guards (the v1 GSPMD inversion) was a 4-20x
# collapse, far outside any noise band.
SCALING_DIP_TOL = 0.15


def build_stream(seed: int, repeat: int) -> list[dict]:
    """The mixed request stream: (n, method) mix x ``repeat``."""
    from repro.data.spd import random_rhs_from_solution, random_sdd, random_spd

    mix = [
        (16, "analog_2n", "spd"),
        (16, "analog_2n", "sdd"),
        (16, "analog_n", "spd"),
        (16, "cholesky", "spd"),
        (24, "analog_2n", "spd"),     # off-grid: pads into the n=32 bucket
        (64, "analog_2n", "spd"),
        (64, "cholesky", "spd"),
        (192, "analog_2n", "spd"),
        (192, "cholesky", "spd"),
    ]
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(repeat):
        for n, method, kind in mix:
            a = random_sdd(rng, n) if kind == "sdd" else random_spd(rng, n)
            x, b = random_rhs_from_solution(rng, a)
            out.append({"a": a, "b": b, "x": x, "n": n, "method": method})
    return out


def run_service(
    systems: list[dict],
    *,
    batch_slots: int,
    n_devices: int = 1,
    inflight: int = 2,
    warmup: bool = True,
    check_parity: bool = True,
    fault_rate: float = 0.0,
    fault_seed: int = 0,
) -> dict:
    """One steady-state service pass; returns throughput + parity stats.

    ``warmup=True`` first streams the whole request set untimed through
    the same service so every (bucket, device) executable is compiled;
    the timed pass then measures serving, not compilation.  The
    round-robin assignment is deterministic, so the warmup pass touches
    exactly the (bucket, device) pairs the timed pass uses.

    ``fault_rate`` arms a seeded chaos injector for the timed pass
    (warmup stays clean): the total rate splits 50/25/25 over device
    faults, NaN solutions and host build errors.  Tickets the retry
    machinery could not save land as structured ``SolveError`` answers
    and are counted (``errors``), not parity-audited; every *delivered*
    solution must still match the direct solve exactly.
    """
    from repro.core.solver import solve
    from repro.serving.faults import FaultInjector, FaultPlan, SolveError
    from repro.serving.solve_service import SolveService

    svc = SolveService(
        batch_slots=batch_slots,
        n_devices=n_devices,
        inflight_per_device=inflight,
        breaker_backoff_s=0.01,
    )
    if warmup:
        for s in systems:
            svc.submit(s["a"], s["b"], method=s["method"])
        svc.drain()
    if fault_rate > 0.0:
        svc.fault_injector = FaultInjector(FaultPlan(
            seed=fault_seed,
            rates={
                "device_fault": fault_rate * 0.50,
                "nonfinite": fault_rate * 0.25,
                "build_error": fault_rate * 0.25,
            },
        ))
    base = svc.stats
    rids = [svc.submit(s["a"], s["b"], method=s["method"]) for s in systems]
    t0 = time.perf_counter()
    results = svc.drain()
    wall = time.perf_counter() - t0

    worst = 0.0
    failures = []
    errors = sum(isinstance(r, SolveError) for r in results.values())
    if check_parity:
        for rid, s in zip(rids, systems):
            if isinstance(results[rid], SolveError):
                continue
            direct = solve(s["a"], s["b"], method=s["method"])
            err = float(np.abs(results[rid].x - direct.x).max())
            worst = max(worst, err)
            if err > PARITY_ATOL:
                failures.append(
                    {"rid": rid, "n": s["n"], "method": s["method"],
                     "err": err}
                )
    stats = svc.stats
    return {
        "requests": len(systems),
        "batch_slots": stats["batch_slots"],
        "devices": stats["devices"],
        "inflight_per_device": stats["inflight_per_device"],
        "warmup": bool(warmup),
        "wall_s": wall,
        "requests_per_s": len(systems) / wall,
        "pad_overhead": stats["pad_overhead"],
        "fill_slots": stats["fill_slots"],
        # timed-pass decomposition (warmup accumulation subtracted)
        "host_build_s": stats["host_build_s"] - base["host_build_s"],
        "device_wait_s": stats["device_wait_s"] - base["device_wait_s"],
        "unpack_s": stats["unpack_s"] - base["unpack_s"],
        "pattern_derivations": sum(
            b["pattern_derivations"] for b in stats["buckets"].values()
        ),
        "parity_worst": worst,
        "parity_failures": failures,
        # degraded-mode accounting (all zero on a fault-free pass)
        "fault_rate": float(fault_rate),
        "fault_injections": stats["fault_injections"],
        "errors": errors,
        "retries": stats["retries"],
        "bisections": stats["bisections"],
        "quarantines": stats["quarantines"],
        "fallbacks": stats["fallbacks"],
    }


def build_doc(
    *, smoke: bool, seed: int = 0, slots: str = "", repeats: int = 3,
    faults: bool = False,
) -> dict:
    """Run the full benchmark (slot sweep, device sweep, overlap probe,
    and — with ``faults`` — the degraded-throughput sweep) and return
    the ``bench_pr7.v1`` document.  Shared by this CLI and the
    ``benchmarks.run`` service phase.

    Each point is best-of-``repeats``: repeat 1 pays warmup + the
    per-request parity audit, later repeats re-measure the already-hot
    pipeline (the jit cache is process-global, so neither warmup nor
    re-auditing is needed) and the point reports the best throughput
    with every sample recorded — single-sample timing noise on a
    loaded host is larger than the effects the device sweep resolves.
    """
    import jax

    n_dev = len(jax.devices())
    repeat = 1 if smoke else 4
    systems = build_stream(seed, repeat)
    if slots:
        slot_sweep = [int(s) for s in slots.split(",")]
    else:
        slot_sweep = [2, 4] if smoke else [1, 2, 4, 8]

    def measure(stream: list | None = None, **kw) -> dict:
        req = systems if stream is None else stream
        point = run_service(req, **kw)
        samples = [point["requests_per_s"]]
        for _ in range(max(0, repeats - 1)):
            again = run_service(
                req, warmup=False, check_parity=False, **kw
            )
            samples.append(again["requests_per_s"])
            if again["requests_per_s"] > point["requests_per_s"]:
                for k in ("wall_s", "requests_per_s", "host_build_s",
                          "device_wait_s", "unpack_s"):
                    point[k] = again[k]
        point["samples_requests_per_s"] = samples
        return point

    doc: dict = {
        "schema": BENCH_SCHEMA,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "smoke": bool(smoke),
        "n_devices_visible": n_dev,
        "stream": sorted({(s["n"], s["method"]) for s in systems}),
        "slot_sweep": [],
        "device_sweep": [],
    }

    print("sweep,slots,devices,inflight,requests_per_s,parity_worst")

    def emit(kind, r):
        print(f"{kind},{r['batch_slots']},{r['devices']},"
              f"{r['inflight_per_device']},"
              f"{r['requests_per_s']:.3f},{r['parity_worst']:.3g}")

    for slots_n in slot_sweep:
        r = measure(batch_slots=slots_n)
        doc["slot_sweep"].append(r)
        emit("slots", r)

    # device sweep at the largest slot count; the >= 2-device point is
    # the streamed-dispatch guard (CI forces 8 host devices)
    dev_sweep = sorted({1, n_dev} | ({2} if n_dev >= 2 else set()))
    for dev in dev_sweep:
        r = measure(batch_slots=max(slot_sweep), n_devices=dev)
        doc["device_sweep"].append(r)
        emit("devices", r)

    # host-build/device-solve overlap in isolation: serial vs
    # double-buffered dispatch on ONE stream
    serial = measure(
        batch_slots=max(slot_sweep), n_devices=1, inflight=1
    )
    overlapped = measure(
        batch_slots=max(slot_sweep), n_devices=1, inflight=2
    )
    emit("overlap", serial)
    emit("overlap", overlapped)
    doc["overlap_probe"] = {
        "serial": serial,
        "overlapped": overlapped,
        "overlap_speedup": (
            overlapped["requests_per_s"] / serial["requests_per_s"]
        ),
    }

    # degraded-mode throughput: the same stream under a seeded chaos
    # injector at increasing fault rates, over every visible stream —
    # retries/bisections/quarantines are the throughput price paid for
    # exactly-once delivery; delivered solutions still parity-audit
    if faults:
        doc["faults_sweep"] = []
        # the per-dispatch injector needs enough micro-batches for a 5%
        # rate to fire at all: triple the smoke stream for this sweep
        fault_stream = build_stream(seed, repeat * 3) if smoke else systems
        for rate in FAULT_RATES:
            r = measure(
                stream=fault_stream,
                batch_slots=max(slot_sweep), n_devices=n_dev,
                fault_rate=rate, fault_seed=seed + 1,
            )
            doc["faults_sweep"].append(r)
            print(f"faults,rate={rate:.0%},{r['requests_per_s']:.3f} req/s,"
                  f"injected={r['fault_injections']},"
                  f"retries={r['retries']},errors={r['errors']}")

    doc["parity_failures"] = [
        f
        for r in (doc["slot_sweep"] + doc["device_sweep"]
                  + [serial, overlapped] + doc.get("faults_sweep", []))
        for f in r["parity_failures"]
    ]
    doc["streamed_point_ran"] = any(
        r["devices"] >= 2 for r in doc["device_sweep"]
    )
    return doc


# -------------------------------------------------- precision sweep
def build_precision_systems(seed: int) -> tuple:
    """The fixed SPD batch every precision cell solves.

    Deliberately identical in smoke and full contexts (the grids
    differ, the systems never do) so the accuracy series compare as
    context-free under ``--baseline``.  General SPD, not SDD — the
    recovery story must hold off the paper's O(1)-settling class.
    """
    from repro.data.spd import random_rhs_from_solution, random_spd

    rng = np.random.default_rng(seed)
    aa, bb, xx = [], [], []
    for _ in range(6):
        a = random_spd(rng, 24, density=0.6)
        x, b = random_rhs_from_solution(rng, a)
        aa.append(a)
        bb.append(b)
        xx.append(x)
    return np.stack(aa), np.stack(bb), np.stack(xx)


def run_precision_cell(
    systems: tuple,
    *,
    bits: int,
    pot_tol: float,
    sweep_dtype: str,
    seed: int,
) -> dict:
    """One (bits, tolerance, sweep dtype) cell of the precision sweep.

    Two passes over the same systems on the same degraded hardware
    model: the *raw* pass delivers the analog operating point as-is
    (its fp64 relative residual is what refinement must recover from);
    the *refined* pass enables graded recovery plus the bf16/fp32
    matrix-free settling probe (``compute_settling`` against the raw
    DC point as reference, so certification measures the sweep — not
    the hardware offset from the exact solution).
    """
    from repro.core.operating_point import NonIdealities
    from repro.core.refine import RefineSpec, relative_residuals
    from repro.core.solver import solve_batch

    a, b, _ = systems
    ni = NonIdealities(pot_bits=bits, pot_tol=pot_tol, seed=seed)

    raw = solve_batch(a, b, method="analog_2n", nonideal=ni,
                      fallback="none")
    raw_rel = relative_residuals(a, b, raw.x)

    t0 = time.perf_counter()
    res = solve_batch(
        a, b, method="analog_2n", nonideal=ni,
        refine=RefineSpec(tol=PRECISION_TOL, max_iters=PRECISION_BUDGET),
        fallback="cholesky",
        compute_settling=True, settle_method="euler",
        settle_matrix_free=True, x_ref=raw.x,
        settle_max_steps=100_000, sweep_dtype=sweep_dtype,
    )
    wall = time.perf_counter() - t0

    rel = np.asarray(res.info["residual"], dtype=np.float64)
    iters = np.asarray(res.info["refine_iters"], dtype=np.int64)
    path = np.asarray(res.info["precision_path"])
    steps = res.info.get("settle_steps")
    return {
        "bits": int(bits),
        "pot_tol": float(pot_tol),
        "sweep_dtype": sweep_dtype,
        "systems": int(a.shape[0]),
        "raw_rel_max": float(raw_rel.max()),
        "raw_rel_mean": float(raw_rel.mean()),
        "refined_rel_max": float(rel.max()),
        "refined_rel_mean": float(rel.mean()),
        "recovered_frac": float(np.mean(rel <= PRECISION_TOL)),
        "analog_frac": float(np.mean(np.isin(path, ("analog", "refined")))),
        "refine_iters": [int(i) for i in iters],
        "refine_iters_mean": float(iters.mean()),
        "refine_iters_max": int(iters.max()),
        "precision_paths": {
            k: int(np.sum(path == k)) for k in np.unique(path).tolist()
        },
        "settle_steps_mean": (
            None if steps is None else float(np.mean(steps))
        ),
        "wall_s": wall,
    }


def build_precision_doc(*, smoke: bool, seed: int = 0) -> dict:
    """The ``bench_pr9.v1`` document: the precision-recovery grid.

    Full grid: bits {4, 6, 8} x tolerance {0, 1, 5}% x sweep dtype
    {float32, bfloat16}.  Smoke keeps bits {4, 8} x tolerance {0, 1}%
    (both dtypes) — the acceptance cell (8, 1%) rides in every
    context.  The acceptance check is the PR's headline claim: on
    8-bit 1%-tolerance hardware, refinement alone (no digital
    fallback) recovers every system to ``PRECISION_TOL``.
    """
    import jax

    from repro.kernels.ell_transient import SWEEP_DTYPES

    bits_axis = (4, 8) if smoke else (4, 6, 8)
    tol_axis = (0.0, 0.01) if smoke else (0.0, 0.01, 0.05)
    systems = build_precision_systems(seed)

    doc: dict = {
        "schema": PRECISION_SCHEMA,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "smoke": bool(smoke),
        "precision_tol": PRECISION_TOL,
        "refine_budget": PRECISION_BUDGET,
        "cells": [],
    }
    print("sweep,bits,pot_tol,dtype,raw_rel_max,refined_rel_max,"
          "iters_mean,analog_frac")
    for bits in bits_axis:
        for pot_tol in tol_axis:
            for dt in SWEEP_DTYPES:
                c = run_precision_cell(
                    systems, bits=bits, pot_tol=pot_tol,
                    sweep_dtype=dt, seed=seed,
                )
                doc["cells"].append(c)
                print(f"precision,{bits},{pot_tol:.2f},{dt},"
                      f"{c['raw_rel_max']:.3g},{c['refined_rel_max']:.3g},"
                      f"{c['refine_iters_mean']:.1f},"
                      f"{c['analog_frac']:.2f}")

    # acceptance: the int8 + 1% cells must recover every system to
    # PRECISION_TOL through the analog path alone (no fallback rows)
    failures = []
    for c in doc["cells"]:
        if c["bits"] == 8 and c["pot_tol"] == 0.01:
            if c["refined_rel_max"] > PRECISION_TOL:
                failures.append({
                    "cell": f"b8t1d{c['sweep_dtype']}",
                    "metric": "refined_rel_max",
                    "value": c["refined_rel_max"],
                })
            if c["analog_frac"] < 1.0:
                failures.append({
                    "cell": f"b8t1d{c['sweep_dtype']}",
                    "metric": "analog_frac",
                    "value": c["analog_frac"],
                })
    doc["acceptance_failures"] = failures
    # lets main() reuse the parity fail path for the acceptance gate
    doc["parity_failures"] = failures
    return doc


# ------------------------------------------------------- baseline gate
def extract_series(doc: dict) -> tuple[dict, dict]:
    """Named scalar series for the baseline gate.

    Returns ``(contextual, free)``: *contextual* series are only
    comparable between runs of the same stream context (same ``smoke``
    flag) — the absolute ones (requests/sec, pad overhead, sweep wall)
    and the device-scaling ratios, whose true value depends on the
    stream size; *free* series are dimensionless ratios (overlap
    speedup, fault-mode throughput retention) comparable across
    contexts.  Understands the ``bench_pr5.v1`` through
    ``bench_pr7.v1`` document shapes (absent sections contribute no
    series, so old baselines gate only what they measured), plus the
    ``bench_pr2.v1`` perf trajectory (sparse-sweep walls contextual,
    dense-vs-ELL speedups free) and the ``bench_pr9.v1`` precision
    grid (accuracy fractions and refinement cost free — the system
    set is context-independent — cell walls contextual).
    """
    schema = str(doc.get("schema", ""))
    if schema.startswith("bench_pr2"):
        return _extract_pr2_series(doc)
    if schema.startswith("bench_pr9"):
        return _extract_precision_series(doc)
    ctx: dict[str, float] = {}
    free: dict[str, float] = {}
    sweep = doc.get("device_sweep") or []
    rps1 = None
    wall = 0.0
    for r in sweep:
        d = r["devices"]
        ctx[f"requests_per_s@dev{d}"] = float(r["requests_per_s"])
        ctx[f"pad_overhead@dev{d}"] = float(r["pad_overhead"])
        wall += float(r["wall_s"])
        if d == 1:
            rps1 = float(r["requests_per_s"])
    if sweep:
        ctx["sweep_wall_s"] = wall
    if rps1:
        for r in sweep:
            # contextual, not free: the scaling ratio's TRUE value
            # depends on the stream size (a smoke stream has too little
            # work per point to amortize multi-stream dispatch, so its
            # honest ratio sits near 1.0 while a full run's exceeds
            # 1.2).  Comparing a smoke run against a full baseline on
            # this ratio produced noise-driven false failures; the
            # inversion anti-result is guarded in EVERY run, context
            #-free, by check_device_scaling's monotonicity test.
            ctx[f"scaling@dev{r['devices']}"] = (
                float(r["requests_per_s"]) / rps1
            )
    probe = doc.get("overlap_probe")
    if probe:
        free["overlap_speedup"] = float(probe["overlap_speedup"])
    fs = doc.get("faults_sweep") or []
    rps0 = None
    for r in fs:
        p = float(r.get("fault_rate", 0.0))
        tag = f"fault{int(round(p * 100))}"
        ctx[f"requests_per_s@{tag}"] = float(r["requests_per_s"])
        if p == 0.0:
            rps0 = float(r["requests_per_s"])
    if rps0:
        for r in fs:
            p = float(r.get("fault_rate", 0.0))
            if p > 0.0:
                # throughput retained under faults, dimensionless
                free[f"fault_retention@fault{int(round(p * 100))}"] = (
                    float(r["requests_per_s"]) / rps0
                )
    return ctx, free


def _extract_pr2_series(doc: dict) -> tuple[dict, dict]:
    """Series for a ``bench_pr2.v1`` perf-trajectory document.

    Per-size sparse-sweep walls are contextual (the full sweep runs
    more steps per point); the dense-vs-ELL speedups are dimensionless
    and always compare.
    """
    ctx: dict[str, float] = {}
    free: dict[str, float] = {}
    for p in doc.get("sparse_sweep") or []:
        ctx[f"sparse_wall_s@n{p['n']}"] = float(p["sweep_wall_s"])
    dv = doc.get("dense_vs_ell")
    if dv:
        free["end_to_end_speedup"] = float(dv["end_to_end_speedup"])
        free["ell_sweep_speedup"] = float(dv["sweep_speedup"])
    return ctx, free


def _extract_precision_series(doc: dict) -> tuple[dict, dict]:
    """Series for a ``bench_pr9.v1`` precision-grid document.

    Accuracy and refinement-cost series are *free*: every context
    solves the identical system set under the identical seeded
    hardware model, so recovered/analog fractions and iteration counts
    are deterministic cell properties, not stream-size artifacts.
    Only the walls are contextual.  Raw/refined residual magnitudes
    are recorded in the document but deliberately NOT gated — ratios
    of ~1e-11 residuals are all noise at any useful tolerance.
    """
    ctx: dict[str, float] = {}
    free: dict[str, float] = {}
    wall = 0.0
    for c in doc.get("cells") or []:
        tag = (f"b{c['bits']}t{int(round(c['pot_tol'] * 100))}"
               f"d{c['sweep_dtype']}")
        free[f"recovered_frac@{tag}"] = float(c["recovered_frac"])
        free[f"analog_frac@{tag}"] = float(c["analog_frac"])
        free[f"refine_iters_mean@{tag}"] = float(c["refine_iters_mean"])
        wall += float(c["wall_s"])
    if doc.get("cells"):
        ctx["precision_wall_s"] = wall
    return ctx, free


def _context_tag(doc: dict) -> str:
    """The stream-size context a document's contextual series ran in.

    Throughput/precision documents carry ``smoke``; the pr2 perf
    trajectory carries ``full`` instead — map both onto one tag so
    cross-schema comparisons only gate like against like.
    """
    if "smoke" in doc:
        return "smoke" if doc.get("smoke") else "full"
    return "full" if doc.get("full") else "smoke"


def compare_to_baseline(
    current: dict, baseline: dict, *, tol: float = REGRESSION_TOL
) -> list[dict]:
    """Gate the current run against a committed baseline document.

    Returns the violations (empty = pass).  Lower-is-worse metrics
    (requests/sec, scaling, overlap speedup) fail when current drops
    below ``(1 - tol) x baseline``; higher-is-worse (pad overhead,
    sweep wall) fail when current exceeds ``(1 + tol) x baseline``.
    Absolute series are skipped when the two documents ran different
    stream contexts (``smoke`` mismatch) — the dimensionless series
    still gate.
    """
    cur_ctx, cur_free = extract_series(current)
    base_ctx, base_free = extract_series(baseline)
    same_ctx = _context_tag(current) == _context_tag(baseline)
    violations: list[dict] = []

    def check(name: str, cur: float, base: float) -> None:
        higher_is_worse = (
            name.startswith(("pad_overhead", "refine_iters"))
            or name.endswith("wall_s")
        )
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


def check_device_scaling(
    doc: dict, *, dip_tol: float = SCALING_DIP_TOL
) -> list[dict]:
    """Requests/sec must be non-decreasing in the stream count (within
    ``dip_tol`` timing noise) — the v1 anti-result this PR removes
    regressed 15.2 -> 0.67 req/s going 1 -> 8 devices."""
    sweep = sorted(
        doc.get("device_sweep") or [], key=lambda r: r["devices"]
    )
    violations = []
    for prev, cur in zip(sweep, sweep[1:]):
        if cur["requests_per_s"] < prev["requests_per_s"] * (1 - dip_tol):
            violations.append({
                "metric": (
                    f"monotone requests_per_s "
                    f"dev{prev['devices']}->dev{cur['devices']}"
                ),
                "current": cur["requests_per_s"],
                "baseline": prev["requests_per_s"],
                "tolerance": dip_tol,
            })
    return violations


def apply_gate(doc: dict, baseline_path: str) -> list[dict]:
    """Monotone-scaling check plus (when a baseline file is given) the
    regression diff.  Returns all violations."""
    violations = check_device_scaling(doc)
    if baseline_path:
        with open(baseline_path) as fh:
            baseline = json.load(fh)
        violations += compare_to_baseline(doc, baseline)
    return violations


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced stream for CI wall-clock")
    ap.add_argument("--faults", action="store_true",
                    help="add the degraded-throughput sweep: req/s at "
                         "0%%/5%%/20%% seeded fault injection")
    ap.add_argument("--precision", action="store_true",
                    help="run the mixed-precision recovery grid (bits x "
                         "tolerance x sweep dtype) instead of the "
                         "throughput sweeps; writes BENCH_pr9.json")
    ap.add_argument("--json", default="BENCH_pr7.json",
                    help="output path ('' to skip; --precision defaults "
                         "to BENCH_pr9.json)")
    ap.add_argument("--slots", default="",
                    help="comma-separated slot counts (default by mode)")
    ap.add_argument("--baseline", default="",
                    help="committed BENCH_*.json to gate against (>25% "
                         "regression fails); device-scaling monotonicity "
                         "is checked regardless")
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of-N timing repeats per point")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.precision:
        doc = build_precision_doc(smoke=args.smoke, seed=args.seed)
        out = ("BENCH_pr9.json" if args.json == "BENCH_pr7.json"
               else args.json)
    else:
        doc = build_doc(smoke=args.smoke, seed=args.seed, slots=args.slots,
                        repeats=args.repeats, faults=args.faults)
        out = args.json

    if out:
        with open(out, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True, default=str)
        print(f"bench_json,path,{out}")

    ok = True
    label = "acceptance" if args.precision else "parity"
    if doc["parity_failures"]:
        print(f"service,{label},FAIL "
              f"({len(doc['parity_failures'])} failures)")
        ok = False
    else:
        print(f"service,{label},OK")
    violations = apply_gate(doc, args.baseline)
    for v in violations:
        print(f"service,regression,{v['metric']}: "
              f"{v['current']:.4g} vs baseline {v['baseline']:.4g}")
    if violations:
        print(f"service,baseline,FAIL ({len(violations)} regressions)")
        ok = False
    else:
        print("service,baseline,OK")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

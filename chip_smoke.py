"""Bring-up smoke run of the solve service and the settle sweep on a TPU.

Drives the system's main paths once, in one process, through the entry
points a user calls, and checks every answer against a plain float64
reference computed on the host:

(a) device check — the first JAX device must be a TPU, else exit 2;
(b) service stream — the 36-ticket stream of :func:`build_stream`, its
    nine-entry ``MIX`` four times over (n in {16, 24, 64, 192};
    analog_2n, analog_n, cholesky) through ``SolveService(batch_slots=8)``:
    a warm drain, then a timed drain.
    Every DC operating point the device computes must match host
    ``np.linalg.solve(M, -c)`` to 1e-9 relative, and every answer,
    analog (ideal hardware) or Cholesky, host ``np.linalg.solve(a, b)``
    to 1e-9 relative.  A second service
    with ``refine=True`` (warm + timed drain) must bring every ticket
    to fp64 relative residual <= 1e-10, analog tickets via the
    ``analog`` or ``refined`` precision path;
(c) ELL settle sweep — B=8 sparse systems at n=2048 (expected row
    degree 16, :func:`sparse_systems`) through ``engine.assemble_batch_ell``
    and ``engine.euler_settle_batch``: every system must settle before
    ``max_steps`` through compiled Pallas kernels;
(d) failure counts — SolveErrors, digital fallbacks, host DC re-solves,
    compiles after warmup and interpreted kernel launches, each printed
    and each required to be zero.

The last line of standard output is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``; timings and
the counts above come on earlier lines.  Any failed check exits 1
without that line.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # only the stream phase on four
                                     # device streams vs one

``--allow-cpu`` relaxes the device check for a rehearsal on the CPU
(kernels then run in interpret mode, which the counts report); it is a
test switch, never a default.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SEED = 0
STREAM_REPEAT = 4
BATCH_SLOTS = 8
DC_RTOL = 1e-9            # the service's PARITY_ATOL, relative
X_RTOL = 1e-9             # every x against host np.linalg.solve(a, b)
REFINE_TOL = 1e-10        # the precision contract's fp64 residual
SETTLE_N = 2048
SETTLE_B = 8
SETTLE_MAX_STEPS = 30_000
SETTLE_CHECK_EVERY = 250
SETTLE_ROW_DEGREE = 16
# (n, method, kind) of one pass of the service stream; n = 24 is off the
# padding grid and pads into the n = 32 bucket
MIX = (
    (16, "analog_2n", "spd"),
    (16, "analog_2n", "sdd"),
    (16, "analog_n", "spd"),
    (16, "cholesky", "spd"),
    (24, "analog_2n", "spd"),
    (64, "analog_2n", "spd"),
    (64, "cholesky", "spd"),
    (192, "analog_2n", "spd"),
    (192, "cholesky", "spd"),
)


def build_stream(seed: int, repeat: int) -> list[dict]:
    """``MIX`` ``repeat`` times over, every system drawn from one
    generator seeded with ``seed``: paper-protocol SPD (or SDD) A and
    b = A x for x ~ U[-0.5, 0.5] V."""
    from repro.data.spd import random_rhs_from_solution, random_sdd, random_spd

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(repeat):
        for n, method, kind in MIX:
            a = random_sdd(rng, n) if kind == "sdd" else random_spd(rng, n)
            _x, b = random_rhs_from_solution(rng, a)
            out.append({"a": a, "b": b, "method": method})
    return out


def sparse_systems(rng, n: int, count: int):
    """``count`` proposed-design netlists of sparse paper-protocol SPD
    systems at expected row degree ``SETTLE_ROW_DEGREE``, and their
    solutions ``(count, n)``."""
    from repro.core.network import build_proposed
    from repro.data.spd import random_rhs_from_solution, random_spd

    density = min(1.0, SETTLE_ROW_DEGREE / n)
    nets, xs = [], []
    for _ in range(count):
        a = random_spd(rng, n, density=density)
        x, b = random_rhs_from_solution(rng, a)
        nets.append(build_proposed(a, b))
        xs.append(x)
    return nets, np.stack(xs)


def report(**fields) -> None:
    print(json.dumps(fields, default=float), flush=True)


def device_check(allow_cpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if dev["platform"] != "tpu" and not allow_cpu:
        print(f"chip_smoke: no TPU found ({dev})", file=sys.stderr)
        sys.exit(2)
    if dev["count"] < chips:
        print(f"chip_smoke: --chips {chips} but {dev['count']} devices",
              file=sys.stderr)
        sys.exit(2)
    return dev


class DcStateAudit:
    """Checks every device DC solve the service harvests against host
    f64 ``np.linalg.solve(M, -c)`` while installed."""

    def __init__(self):
        self.worst = 0.0
        self.states = 0

    def __enter__(self):
        from repro.core import engine

        self._orig = engine.dc_solve_batch_finalize

        def audited(z_dev, bss):
            z = self._orig(z_dev, bss)
            ref = np.linalg.solve(bss.m, -bss.c[..., None])[..., 0]
            rel = np.max(np.abs(z - ref), axis=1) / np.max(np.abs(ref), axis=1)
            self.worst = max(self.worst, float(np.max(rel)))
            self.states += len(rel)
            return z

        engine.dc_solve_batch_finalize = audited
        return self

    def __exit__(self, *exc):
        from repro.core import engine

        engine.dc_solve_batch_finalize = self._orig


def drain(svc, stream: list[dict]) -> tuple[list, float]:
    rids = [svc.submit(s["a"], s["b"], method=s["method"]) for s in stream]
    t0 = time.perf_counter()
    out = svc.drain()
    return [out[r] for r in rids], time.perf_counter() - t0


def serve(stream: list[dict], *, n_devices: int = 1, refine=None) -> dict:
    """Warm drain + timed drain on one service; returns results, walls,
    the post-warmup compile count and the DC-state audit."""
    from repro.analysis.runtime import CompileWatch
    from repro.serving import SolveService

    svc = SolveService(batch_slots=BATCH_SLOTS, n_devices=n_devices,
                       refine=refine)
    with CompileWatch() as warm_watch, DcStateAudit() as audit:
        _, warm_wall = drain(svc, stream)
    with CompileWatch() as watch:
        results, wall = drain(svc, stream)
    return {
        "svc": svc, "results": results, "wall_s": wall,
        "warm_wall_s": warm_wall, "warm_compiles": warm_watch.count,
        "warm_compile_s": warm_watch.seconds,
        "post_warmup_compiles": watch.count,
        "post_warmup_compile_names": watch.names, "audit": audit,
    }


def check_stream(run: dict, stream: list[dict], *, refined: bool) -> dict:
    from repro.core.refine import relative_residuals
    from repro.serving.faults import SolveError

    errors = sum(isinstance(r, SolveError) for r in run["results"])
    worst_digital = worst_circuit = worst_residual = 0.0
    bad_paths = 0
    for s, r in zip(stream, run["results"]):
        if isinstance(r, SolveError):
            continue
        ref = np.linalg.solve(s["a"], s["b"])
        rel = float(np.max(np.abs(r.x - ref)) / np.max(np.abs(ref)))
        if s["method"] == "cholesky":
            worst_digital = max(worst_digital, rel)
        else:
            worst_circuit = max(worst_circuit, rel)
            if refined and r.info.get("precision_path") not in ("analog", "refined"):
                bad_paths += 1
        res = float(relative_residuals(s["a"][None], s["b"][None], r.x[None])[0])
        worst_residual = max(worst_residual, res)
    st = run["svc"].stats
    out = {
        "tickets": len(stream),
        "solve_errors": errors,
        "fallbacks": st["fallbacks"],
        "post_warmup_compiles": run["post_warmup_compiles"],
        "dc_states_checked": run["audit"].states,
        "dc_worst_rel_dev": run["audit"].worst,
        "digital_worst_rel_dev": worst_digital,
        "analog_worst_rel_err_vs_exact": worst_circuit,
        "worst_rel_residual": worst_residual,
        "wall_s": run["wall_s"],
        "warm_wall_s": run["warm_wall_s"],
        "warm_compiles": run["warm_compiles"],
        "warm_compile_s": run["warm_compile_s"],
        "device_micro_batches": st["device_micro_batches"],
        "host_build_s": st["host_build_s"],
        "device_wait_s": st["device_wait_s"],
        "unpack_s": st["unpack_s"],
    }
    failures = []
    if errors:
        failures.append(f"{errors} SolveErrors")
    if st["fallbacks"]:
        failures.append(f"{st['fallbacks']} digital fallbacks")
    if run["post_warmup_compiles"]:
        failures.append(
            f"post-warmup compiles: {run['post_warmup_compile_names']}")
    if not run["audit"].states or run["audit"].worst > DC_RTOL:
        failures.append(f"DC states deviate {run['audit'].worst:.3e} > {DC_RTOL}")
    if worst_digital > X_RTOL:
        failures.append(f"cholesky deviates {worst_digital:.3e} > {X_RTOL}")
    if worst_circuit > X_RTOL:
        failures.append(f"analog deviates {worst_circuit:.3e} > {X_RTOL}")
    if refined:
        out["precision_paths"] = st["precision_paths"]
        out["refine_iters_total"] = st["refine_iters_total"]
        if worst_residual > REFINE_TOL:
            failures.append(f"residual {worst_residual:.3e} > {REFINE_TOL}")
        if bad_paths:
            failures.append(f"{bad_paths} tickets off the analog/refined paths")
    out["failures"] = failures
    return out


def phase_service() -> list[str]:
    stream = build_stream(SEED, repeat=STREAM_REPEAT)
    failures = []
    for refined in (False, True):
        run = serve(stream, refine=True if refined else None)
        res = check_stream(run, stream, refined=refined)
        report(phase="service", refine=refined, **res)
        failures += [f"service(refine={refined}): {f}" for f in res["failures"]]
    return failures


def phase_settle(allow_cpu: bool) -> list[str]:
    from repro.analysis.runtime import CompileWatch
    from repro.core import engine
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    nets, x = sparse_systems(rng, SETTLE_N, SETTLE_B)
    launches0 = dict(ops.KERNEL_STATS)
    with CompileWatch() as watch:
        t0 = time.perf_counter()
        ell = engine.assemble_batch_ell(nets)
        ell.weights.block_until_ready()
        t_assemble = time.perf_counter() - t0
        t0 = time.perf_counter()
        steps, x_final, res, _dt = engine.euler_settle_batch(
            ell, x, max_steps=SETTLE_MAX_STEPS, check_every=SETTLE_CHECK_EVERY,
        )
        t_sweep = time.perf_counter() - t0
    compiled = ops.KERNEL_STATS["compiled"] - launches0["compiled"]
    interpreted = ops.KERNEL_STATS["interpreted"] - launches0["interpreted"]
    settled = int(np.sum(steps < SETTLE_MAX_STEPS))
    backend = ops.sweep_backend(ell.n_states, ell.ell_width)
    report(
        phase="settle", n=SETTLE_N, batch=SETTLE_B, nz=ell.n_states,
        ell_width=ell.ell_width, backend=backend, settled=settled,
        steps=steps.tolist(), residual_max=float(np.max(res)),
        x_max_dev=float(np.max(np.abs(x_final - x))),
        kernel_launches_compiled=compiled,
        kernel_launches_interpreted=interpreted,
        compiles=watch.count, compile_s=watch.seconds,
        assemble_s=t_assemble, sweep_s=t_sweep,
    )
    failures = []
    if settled != SETTLE_B:
        failures.append(f"settle: {SETTLE_B - settled} systems did not settle")
    if backend != "ell":
        failures.append(f"settle: backend {backend}, expected ell")
    if compiled == 0 and not allow_cpu:
        failures.append("settle: no compiled kernel launch")
    return failures


def phase_streams(chips: int) -> list[str]:
    """The stream phase on ``chips`` device streams against one."""
    stream = build_stream(SEED, repeat=STREAM_REPEAT)
    one = serve(stream, n_devices=1)
    many = serve(stream, n_devices=chips)
    failures = []
    for label, run in (("1", one), (str(chips), many)):
        res = check_stream(run, stream, refined=False)
        report(phase="streams", n_devices=int(label), **res)
        failures += [f"streams({label}): {f}" for f in res["failures"]]
    per_dev = many["svc"].stats["device_micro_batches"]
    max_diff = max(
        float(np.max(np.abs(a.x - b.x))) for a, b in
        zip(one["results"], many["results"])
        if hasattr(a, "x") and hasattr(b, "x")
    )
    report(phase="streams", device_micro_batches=per_dev,
           max_abs_diff_vs_one_device=max_diff)
    if min(per_dev) == 0:
        failures.append(f"streams: a device got no micro-batches {per_dev}")
    if max_diff != 0.0:
        failures.append(f"streams: answers differ from one device by {max_diff:.3e}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-stream service phase")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run without a TPU")
    args = ap.parse_args()

    from bench.harness import enable_compile_cache

    device = device_check(args.allow_cpu, args.chips)
    report(phase="device", compile_cache=enable_compile_cache(), **device)

    from repro.core import engine
    from repro.kernels import ops

    t0 = time.perf_counter()
    if args.chips > 1:
        failures = phase_streams(args.chips)
    else:
        failures = phase_service() + phase_settle(args.allow_cpu)
    counts = {
        "host_dc_resolves": engine.DC_STATS["host_resolves"],
        "kernel_launches_interpreted": ops.KERNEL_STATS["interpreted"],
        "kernel_launches_compiled": ops.KERNEL_STATS["compiled"],
    }
    report(phase="totals", wall_s=time.perf_counter() - t0, **counts)
    if counts["host_dc_resolves"]:
        failures.append(f"{counts['host_dc_resolves']} host DC re-solves")
    if counts["kernel_launches_interpreted"] and not args.allow_cpu:
        failures.append(
            f"{counts['kernel_launches_interpreted']} interpreted kernel launches")
    for f in failures:
        print(f"FAIL {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

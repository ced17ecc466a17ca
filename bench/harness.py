"""One run of one benchmark cell: set-up, a closed loop of rounds, checks.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (its file is given in ``configs``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Everything else is found by name:
the configuration's operators and right-hand-side protocols
(``bench/operators/``, ``bench/rhs/``, see ``bench/generators.py``);
the metrics a cell reports, the ``end_to_end`` and ``per_layer``
entries that list it under ``workloads`` (or list no workloads), each
read by ``bench/metrics/<name>.py``; and the numbers that decide
``correct``, one per key of the configuration's ``limits``, each read
by ``bench/checks/<name>.py``.  Nothing in this file names a cell, a
configuration, an operator, a metric or a check.

The loop drives the public entry points of the solve service.  One
round submits ``round_tickets`` tickets (each system's share of them,
right-hand sides drawn from the seed), calls ``drain()``, and checks
every answer's float64 residual; rounds repeat until ``--seconds`` have
passed.  A ticket's latency runs from its ``submit`` to the return of
its ``drain``.  After the window the delivered answers are compared
with the plain references of ``bench/reference.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import generators, reference

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
REHEARSAL_ROUNDS = 2
TRACE_SECONDS = 20.0


class NoChip(RuntimeError):
    """The accelerator the cell needs is not there."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(name: str, spec_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = json.loads(spec_file.read_text())
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise KeyError(f"no workload {name!r} in {spec_file.name}")
    wl = workloads[name]
    cfg_file = {c["name"]: c["file"] for c in spec["configs"]}[wl["config"]]
    config = json.loads((ROOT / cfg_file).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text())

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=int(wl["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
    )


def rehearsal_config(config: dict) -> dict:
    """The configuration with its rehearsal limits (tests only); the
    systems take their rehearsal sizes in ``generators.load_systems``."""
    return {**config, "limits": {**config["limits"],
                                 **config.get("rehearsal_limits", {})}}


@dataclasses.dataclass
class Ticket:
    system: generators.System
    b: np.ndarray
    latency_s: float
    micro_batch: tuple               # (round, system, slot group): one micro-batch
    x: np.ndarray | None = None      # None: the service returned an error
    residual: float = float("inf")
    settle_steps: int | None = None
    settle_time: float | None = None
    stable: bool | None = None
    path: str | None = None          # the service's precision path
    error: str | None = None


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read from one run."""

    cell: Cell
    config: dict                     # as run (rehearsal limits applied)
    systems: list[generators.System]
    tickets: list[Ticket]            # the tickets the metrics read
    window_tickets: list[Ticket]     # every ticket of the window
    delivered: int
    setup_s: float
    window_s: float
    stats_before: dict
    stats_after: dict
    device_kind: str
    platform: str
    devices: list[int]
    trace: object = None             # bench.trace.Trace in a traced run
    trace_window: tuple[int, int] | None = None


def device_check(chips: int, rehearse: bool):
    """The devices this run uses; :class:`NoChip` if the cell's chips
    are not there (a rehearsal takes whatever JAX has)."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if rehearse:
        return devs[: max(1, min(chips, len(devs)))]
    if platform != "tpu":
        raise NoChip(f"no TPU found: JAX reports {len(devs)} {platform} device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` — one fixed path, since the
    path is part of the cache key.  Every program is cached, however
    short its compile, so a cell's second run compiles nothing."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def default_service(cell: Cell, devs):
    from repro.serving import SolveService

    return SolveService(
        batch_slots=int(cell.traffic["batch_slots"]), devices=list(devs),
        refine=bool(cell.config["refine"]),
    )


def submit_options(cell: Cell, system: generators.System) -> dict:
    return {"method": system.method, "opamp": system.opamp,
            **cell.traffic.get("submit", {})}


def _round(svc, cell, tickets):
    """Submit one round of ``(system, b)``, drain it; returns
    ``(results, latencies)``."""
    import jax

    with jax.profiler.TraceAnnotation("bench.submit"):
        rids, t_sub = [], []
        for system, b in tickets:
            t_sub.append(time.perf_counter())
            rids.append(svc.submit(system.a, b, **submit_options(cell, system)))
    with jax.profiler.TraceAnnotation("bench.drain"):
        out = svc.drain()
    t_done = time.perf_counter()
    return [out[r] for r in rids], [t_done - t for t in t_sub]


def _record(results, latencies, drawn, round_index: int, slots: int) -> list[Ticket]:
    """Record one round's answers: each delivered x with its float64
    residual, settle result and precision path, or the service's error.
    A system's tickets fill its micro-batches in submission order."""
    from repro.serving.faults import SolveError

    tickets = []
    seen: dict[int, int] = {}
    for res, lat, (system, b) in zip(results, latencies, drawn):
        k = seen.get(system.index, 0)
        seen[system.index] = k + 1
        t = Ticket(system=system, b=b, latency_s=lat,
                   micro_batch=(round_index, system.index, k // slots))
        if isinstance(res, SolveError):
            t.error = f"{res.kind}: {res.detail}"
        else:
            t.x = np.array(res.x, dtype=np.float64)
            t.residual = reference.relative_residual(system.a, b, t.x)
            steps = res.info.get("settle_steps")
            t.settle_steps = None if steps is None else int(steps)
            t.settle_time = res.settle_time
            t.stable = bool(res.stable)
            t.path = res.info.get("precision_path")
        tickets.append(t)
    return tickets


def delivered(t: Ticket, settling: bool, residual_limit: float) -> bool:
    """A ticket that meets the accuracy contract (and, in a settle
    cell, carries a finite settle time from a settled sweep)."""
    if t.x is None or not t.residual <= residual_limit:
        return False
    if settling:
        return bool(t.stable) and t.settle_steps is not None and \
            t.settle_time is not None and np.isfinite(t.settle_time)
    return True


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t_process: float | None = None,
        service_factory=default_service) -> dict:
    """One run; returns the result object the command prints last."""
    t0 = time.perf_counter() if t_process is None else t_process
    import jax

    devs = device_check(cell.chips, rehearse)
    if not rehearse:
        enable_compile_cache()
    from repro.analysis.runtime import CompileWatch
    from repro.core import engine

    config = rehearsal_config(cell.config) if rehearse else cell.config
    limits = config["limits"]
    settling = bool(cell.traffic.get("submit", {}).get("compute_settling"))
    systems = generators.load_systems(config, rehearse)
    r = int(cell.traffic["round_tickets"])
    slots = int(cell.traffic["batch_slots"])
    stream = generators.TicketStream(systems, r, seed)
    warm_stream = generators.TicketStream(systems, r, [seed, 9])
    svc = service_factory(cell, devs)

    # warm-up: one round of this cell's shapes, compiled (or read from
    # the persistent cache) before the window
    _round(svc, cell, warm_stream.next_round())
    stats_before = svc.stats
    resolves_before = engine.DC_STATS["host_resolves"]

    tickets: list[Ticket] = []
    round_s: list[float] = []

    def rounds_until(deadline: float) -> float:
        nonlocal tickets
        now = time.perf_counter()
        while now < deadline and not (rehearse and len(round_s) >= REHEARSAL_ROUNDS):
            drawn = stream.next_round()
            results, lat = _round(svc, cell, drawn)
            with jax.profiler.TraceAnnotation("bench.check"):
                tickets += _record(results, lat, drawn, len(round_s), slots)
            round_s.append(time.perf_counter() - now)
            now = time.perf_counter()
        return now

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with CompileWatch() as watch:
        t_start = time.perf_counter()
        t_end = t_start + seconds
        # a traced run traces the first TRACE_SECONDS of its window (the
        # rounds started within them) and runs the rest untraced
        with jax.profiler.TraceAnnotation("bench.window"):
            now = rounds_until(min(t_end, t_start + TRACE_SECONDS) if trace else t_end)
        if trace:
            jax.profiler.stop_trace()
        stats_traced = svc.stats
        traced_tickets, traced_rounds = len(tickets), len(round_s)
        now = rounds_until(t_end)
    window_s = now - t_start
    setup_s = t_start - t0
    trace_data = trace_window = None
    trace_load_s = 0.0
    if trace:
        from bench import trace as tr

        t_load = time.perf_counter()
        trace_data = tr.load(tr.find_xplane(trace_dir))
        trace_load_s = time.perf_counter() - t_load
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_window = tr.window(trace_data)

    peak = [d.memory_stats() for d in devs]
    peak_bytes = (max(s.get("peak_bytes_in_use", 0) for s in peak)
                  if all(peak) else None)
    stats_after = svc.stats
    host_resolves = engine.DC_STATS["host_resolves"] - resolves_before
    del svc
    gc.collect()

    n_delivered = sum(delivered(t, settling, limits["residual"]) for t in tickets)
    # a traced run's metrics read the traced part of the window only
    part = tickets[:traced_tickets] if trace else tickets
    record = RunRecord(
        cell=cell, config=config, systems=systems, tickets=part,
        window_tickets=tickets,
        delivered=sum(delivered(t, settling, limits["residual"]) for t in part),
        setup_s=setup_s, window_s=window_s, stats_before=stats_before,
        stats_after=stats_traced if trace else stats_after,
        device_kind=devs[0].device_kind, platform=devs[0].platform,
        devices=[d.id for d in devs], trace=trace_data, trace_window=trace_window,
    )
    checks = compare(record, seed)
    metrics = read_metrics(record, cell.per_layer if trace else cell.end_to_end)
    failed = sum(t.x is None for t in tickets)
    result = {
        "correct": failed == 0 and bool(tickets) and all(
            c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(tickets),
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak_bytes,
        },
    }
    if trace and record.platform == "tpu":
        from bench import trace as tr

        lo, hi = trace_window
        result["device"]["busy_s"] = float(np.mean(
            [tr.busy_ns(trace_data, d, lo, hi) for d in record.devices])) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": tr.top_ops(trace_data, record.devices, lo, hi),
            "idle_gaps": tr.idle_gaps(trace_data, record.devices[0], lo, hi),
        }
    result["checks"] = checks
    untraced = round_s[traced_rounds:] if trace else round_s
    diagnostics = {
        "rounds": len(round_s), "delivered": int(n_delivered),
        "traced_tickets": traced_tickets if trace else 0,
        # mean round of the traced part and of the rest: what tracing costs
        "round_s_traced": float(np.mean(round_s[:traced_rounds]))
        if trace and traced_rounds else None,
        "round_s_untraced": float(np.mean(untraced)) if untraced else None,
        "window_s": window_s, "trace_load_s": trace_load_s,
        "compiles_in_window": watch.count, "compile_names": watch.names[:8],
        "host_dc_resolves": host_resolves,
        "fallbacks": stats_after["fallbacks"] - stats_before["fallbacks"],
        "errors": [t.error for t in tickets if t.error][:4],
    }
    print("bench diagnostics " + json.dumps(diagnostics, default=str), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return result


def read_metrics(record: RunRecord, entries: list[dict]) -> dict:
    """Each metric's reader from ``bench/metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left out.
    A rehearsal writes no device metric."""
    out = {}
    for entry in entries:
        if record.platform != "tpu" and entry["source"] == "device_trace":
            continue
        reader = importlib.import_module(f"bench.metrics.{entry['name']}")
        value = reader.read(record)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def compare(record: RunRecord, seed: int) -> dict:
    """The numbers that decide ``correct``, each beside its limit: one
    per key of the configuration's ``limits``, read by
    ``bench/checks/<name>.py`` over every ticket of the window.  A check
    that has nothing to read in this cell returns None and is left out.
    """
    checks = {}
    for name, limit in record.config["limits"].items():
        value = importlib.import_module(f"bench.checks.{name}").read(record, seed)
        if value is not None:
            checks[name] = {"value": float(value), "limit": limit}
    return checks

"""Runtime contract gates: compile counting and host-sync attribution.

The static rules (:mod:`repro.analysis.rules`) claim two steady-state
invariants the serving stack's throughput depends on; this module makes
them falsifiable at run time:

* **zero post-warmup compilations** — :class:`CompileWatch` listens to
  the public ``jax.monitoring`` event JAX records around every backend
  compilation (:data:`BACKEND_COMPILE_EVENT`) and keeps each one's
  function name.  :func:`host_callbacks` scans an executable's compiled
  text (``.lower().compile().as_text()``) with the roofline parser
  (:func:`repro.roofline.hlo_parse.host_callback_ops`), so a hot-path
  executable smuggling a host callback (python callback custom-calls,
  infeed/outfeed) is flagged even when the compile count itself is
  legitimate warmup.
* **zero dispatch-phase host syncs** — :class:`SyncWatch` counts host
  materializations of ``jax.Array`` values, attributed to the sync
  label of the innermost :func:`span` that declares one (``dispatch`` /
  ``harvest`` / ``finish`` / ``unpack`` / ``net_build`` /
  ``settle_poll``).  On the CPU
  backend ``ArrayImpl`` exposes the buffer protocol, so there is no
  universal interpreter-level hook — instead the watch patches the
  conversion entry points repo code actually calls (``np.asarray`` /
  ``np.array`` / ``jax.device_get`` and the Python-level ``ArrayImpl``
  methods).  The gate asserts ``dispatch == 0`` *and* that harvest-side
  phases counted nonzero syncs — a dead counter cannot pass.

:func:`span` is the one labelling facility of the solve path.  Each
span opens a ``jax.profiler.TraceAnnotation`` (its events land on the
profiler's ``/host:CPU`` plane, on the device trace's clock), pushes
its sync label if it declares one, and adds its count and elapsed
seconds to the active :class:`SpanTotals` — the one a
:class:`~repro.serving.solve_service.SolveService` installs for the
length of its ``drain()``, so spans opened deep in ``core/`` land on
the service that caused them.  Outside an installed sink a span only
annotates.

:func:`run_service_gate` is the smoke-drain harness CI runs: warm a
:class:`~repro.serving.solve_service.SolveService` on a mixed workload,
re-drain the identical workload under both watches, and require zero
post-warmup compilations, zero dispatch-phase syncs, and no host
callbacks in any hot-path executable.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
from typing import Any, Iterator

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = [
    "CompileWatch", "SpanTotals", "SyncWatch", "host_callbacks", "span",
    "run_service_gate",
]


# ------------------------------------------------------------ compile watch


# the jax.monitoring duration event recorded around each backend compile
# (a persistent-cache retrieval records it too)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Context manager counting XLA compilations while active.

    Registers a ``jax.monitoring`` duration listener for
    :data:`BACKEND_COMPILE_EVENT`: every jit lowering that reaches the
    backend records it once, so ``count`` is the ground truth the
    static recompile rules approximate and a jit cache hit counts zero.
    Re-entrant use is rejected (the listener is process-global).
    """

    _active: "CompileWatch | None" = None

    def __init__(self) -> None:
        self.names: list[str] = []
        self.seconds = 0.0

    @property
    def count(self) -> int:
        return len(self.names)

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.names.append(str(kwargs.get("fun_name", "<unknown>")))
            self.seconds += duration

    def __enter__(self) -> "CompileWatch":
        import jax.monitoring

        if CompileWatch._active is not None:
            raise RuntimeError("CompileWatch is not re-entrant")
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        CompileWatch._active = self
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)
        CompileWatch._active = None


def host_callbacks(jitted, *args, **kwargs) -> list[str]:
    """Host-callback op lines in the executable ``jitted`` compiles for
    these arguments (arrays or ``jax.ShapeDtypeStruct``s)."""
    from repro.roofline.hlo_parse import host_callback_ops

    return host_callback_ops(
        jitted.lower(*args, **kwargs).compile().as_text()
    )


# ------------------------------------------------------------------ spans

# the sync-label stack SyncWatch charges syncs to; index 0 is the
# ambient (unattributed) label.  Only spans that declare a label push.
_SCOPE_STACK: list[str] = ["ambient"]


class SpanTotals:
    """Count and elapsed seconds per span name, for one owner."""

    def __init__(self) -> None:
        self._totals: dict[str, list] = {}     # name -> [count, seconds]

    def add(self, name: str, seconds: float) -> None:
        entry = self._totals.setdefault(name, [0, 0.0])
        entry[0] += 1
        entry[1] += seconds

    def seconds(self, name: str) -> float:
        return self._totals.get(name, (0, 0.0))[1]

    def snapshot(self) -> dict[str, dict[str, Any]]:
        """``{name: {"count": int, "s": float}}``, a copy."""
        return {k: {"count": c, "s": s} for k, (c, s) in self._totals.items()}

    @contextlib.contextmanager
    def installed(self) -> Iterator["SpanTotals"]:
        """Make this the sink of every span opened in the block (in
        this thread or task), nested calls included."""
        token = _SINK.set(self)
        try:
            yield self
        finally:
            _SINK.reset(token)


_SINK: contextvars.ContextVar[SpanTotals | None] = contextvars.ContextVar(
    "repro_span_sink", default=None
)


@contextlib.contextmanager
def span(name: str, *, sync: str | None = None) -> Iterator[None]:
    """Label the block ``name`` on the profiler trace and time it.

    ``sync`` is the label :class:`SyncWatch` charges the block's host
    syncs to; a span without one (a timing span) leaves the enclosing
    label in force, so a sync inside ``core.assemble`` under
    ``serve.dispatch`` still counts as a ``dispatch`` sync.  The count
    and seconds go to the installed :class:`SpanTotals`, if any.  A
    span adds no host sync; with no profiler session its cost is a
    clock pair, an inactive ``TraceMe`` and, with a sync label, a list
    push/pop.  Also usable as a decorator.
    """
    sink = _SINK.get()
    if sync is not None:
        _SCOPE_STACK.append(sync)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name):
            yield
    finally:
        if sink is not None:
            sink.add(name, time.perf_counter() - t0)
        if sync is not None:
            _SCOPE_STACK.pop()


# --------------------------------------------------------------- sync watch


class SyncWatch:
    """Context manager counting host materializations per sync label.

    ``counts`` maps sync label -> number of ``jax.Array`` host
    materializations observed under that label.  Patched entry points:
    ``numpy.asarray`` / ``numpy.array`` (counted only for jax.Array
    operands), ``jax.device_get``, and the Python-level ``ArrayImpl``
    conversion methods (``tolist`` / ``__float__`` / ``__int__`` /
    ``__bool__``).  A reentrancy flag keeps nested conversions (e.g.
    ``device_get`` calling ``np.asarray``) from double counting.
    """

    _active: "SyncWatch | None" = None

    def __init__(self) -> None:
        self.counts: dict[str, int] = {}
        self.calls: list[tuple[str, str]] = []   # (label, entry point)
        self._saved: list[tuple[Any, str, Any]] = []
        self._in_count = False

    def total(self, *labels: str) -> int:
        if not labels:
            return sum(self.counts.values())
        return sum(self.counts.get(l, 0) for l in labels)

    def _record(self, entry: str) -> None:
        scope = _SCOPE_STACK[-1]
        self.counts[scope] = self.counts.get(scope, 0) + 1
        self.calls.append((scope, entry))

    def _patch(self, obj: Any, attr: str, make) -> None:
        orig = getattr(obj, attr)
        self._saved.append((obj, attr, orig))
        setattr(obj, attr, make(orig))

    def __enter__(self) -> "SyncWatch":
        if SyncWatch._active is not None:
            raise RuntimeError("SyncWatch is not re-entrant")
        import jax
        import numpy
        from jax._src import array as _jarray

        watch = self

        def counting_converter(name, orig):
            def wrapped(a, *args, **kwargs):
                if isinstance(a, jax.Array) and not watch._in_count:
                    watch._in_count = True
                    try:
                        watch._record(name)
                    finally:
                        watch._in_count = False
                return orig(a, *args, **kwargs)
            return wrapped

        def counting_method(name, orig):
            def wrapped(self, *args, **kwargs):
                if not watch._in_count:
                    watch._in_count = True
                    try:
                        watch._record(name)
                    finally:
                        watch._in_count = False
                return orig(self, *args, **kwargs)
            return wrapped

        self._patch(numpy, "asarray",
                    lambda orig: counting_converter("np.asarray", orig))
        self._patch(numpy, "array",
                    lambda orig: counting_converter("np.array", orig))
        self._patch(jax, "device_get",
                    lambda orig: counting_converter("jax.device_get", orig))
        for attr in ("tolist", "__float__", "__int__", "__bool__"):
            try:
                self._patch(
                    _jarray.ArrayImpl, attr,
                    lambda orig, a=attr: counting_method(
                        f"ArrayImpl.{a}", orig),
                )
            except (AttributeError, TypeError):
                pass        # method not patchable on this jaxlib
        SyncWatch._active = self
        return self

    def __exit__(self, *exc) -> None:
        for obj, attr, orig in reversed(self._saved):
            setattr(obj, attr, orig)
        self._saved.clear()
        SyncWatch._active = None


# ------------------------------------------------------------- service gate


def _gate_workload(service, rng: np.random.Generator) -> list[int]:
    """A small mixed-n / mixed-method workload; deterministic given rng."""
    rids = []
    for n, method in ((6, "analog_2n"), (10, "analog_2n"), (6, "analog_n"),
                      (12, "cholesky"), (6, "analog_2n"), (10, "cg")):
        m = rng.normal(size=(n, n))
        a = m @ m.T + n * np.eye(n)
        b = rng.normal(size=n)
        rids.append(service.submit(a, b, method=method))
    return rids


def _hot_path_callbacks() -> list[tuple[str, str]]:
    """(executable, op line) for every host callback in the service's
    device programs: the DC solve and the batched digital baselines."""
    import jax
    import jax.numpy as jnp

    from repro.core import baselines, engine

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float64)

    programs = (
        ("dc_solve", engine._dc_solve_vmapped, (spec(2, 128, 128), spec(2, 128)), {}),
        ("cholesky_solve_batch", baselines.cholesky_solve_batch,
         (spec(2, 16, 16), spec(2, 16)), {}),
        ("cg_solve_batch", baselines.cg_solve_batch,
         (spec(2, 16, 16), spec(2, 16)), {"tol": 1e-10, "max_iter": 100}),
    )
    return [
        (name, op)
        for name, fn, args, kwargs in programs
        for op in host_callbacks(fn, *args, **kwargs)
    ]


def run_service_gate(
    *, n_devices: int | None = None, seed: int = 0, verbose: bool = False,
) -> dict[str, Any]:
    """Smoke-drain contract gate over a live :class:`SolveService`.

    Drains one warmup pass (compiles allowed), then re-drains an
    identical workload under :class:`CompileWatch` + :class:`SyncWatch`.
    Returns a report dict with ``ok`` plus the evidence; the contract:

    * ``post_warmup_compiles == 0`` — signatures, patterns and bucket
      shapes are cache-stable across drains;
    * ``dispatch_syncs == 0`` — the dispatch phase never materializes
      a device value (host/device overlap is real);
    * ``harvest_syncs > 0`` — the counter is alive (falsifiability);
    * no host callbacks inside the service's device programs.
    """
    from repro.serving.solve_service import SolveService

    def build():
        return SolveService(
            batch_slots=2, n_devices=n_devices, inflight_per_device=2,
        )

    service = build()

    # warmup drain: all compilation happens here
    with CompileWatch() as warmup_watch:
        rng = np.random.default_rng(seed)
        _gate_workload(service, rng)
        warm = service.drain()
    callbacks = _hot_path_callbacks()

    # measured drain: identical workload through fresh signature/ticket
    # objects — compile-count and sync-attribution must both be silent
    with CompileWatch() as watch, SyncWatch() as sync:
        rng = np.random.default_rng(seed)
        _gate_workload(service, rng)
        out = service.drain()

    errors = [r for r in list(warm.values()) + list(out.values())
              if not hasattr(r, "x")]
    dispatch_syncs = sync.total("dispatch")
    harvest_syncs = sync.total("harvest", "finish", "unpack", "settle_poll")
    report = {
        "ok": (
            watch.count == 0
            and dispatch_syncs == 0
            and harvest_syncs > 0
            and not callbacks
            and not errors
        ),
        "warmup_compiles": warmup_watch.count,
        "post_warmup_compiles": watch.count,
        "post_warmup_compile_names": watch.names,
        "dispatch_syncs": dispatch_syncs,
        "harvest_syncs": harvest_syncs,
        "sync_counts": dict(sync.counts),
        "host_callbacks": callbacks,
        "solve_errors": len(errors),
        "tickets": len(warm) + len(out),
    }
    if verbose:
        report["warmup_compile_names"] = warmup_watch.names
    return report

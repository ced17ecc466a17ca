"""Async continuously-batched, multi-device solve service.

The paper's throughput claim is a *serving* story: a fixed analog array
solves a stream of independent SPD systems at a complexity independent
of matrix size.  This module is the front-end that turns a stream of
heterogeneous requests (different ``n``, different methods, different
settle options) into the homogeneous shared-stamp-pattern micro-batches
the batched engine (:func:`repro.core.solver.solve_batch`) is fast at —
and keeps every device busy while the host builds the next one:

* **submit** — requests are queued, not solved.  Each carries its
  system, the solve method (analog designs or digital baselines), the
  option signature that decides batch compatibility, and its admission
  stamps (``priority`` / ``deadline``) — intake ordering is the same
  :class:`repro.serving.engine.AdmissionQueue` the token-serving engine
  admits decode slots with: priority first, earliest-deadline within a
  class, FIFO on ties.
* **bucket** — admitted requests are grouped by
  ``(n_padded, method, option signature)``.  ``n_padded`` comes from a
  small padding grid, so a mixed-size stream collapses onto a few
  device shapes instead of one jit compile per distinct ``n``.
* **pad** — a request of size ``n`` inside an ``n_pad`` bucket is
  identity-extended: ``A_pad = blockdiag(A, g_pad I)`` with ``g_pad``
  the mean diagonal conductance of ``A`` (keeps the padding in-scale
  and SPD), ``b_pad = g_pad * PAD_SOLUTION_V`` on the pad entries.  The
  pad rows are decoupled from the real system, diagonally dominant
  (fully passive in the 2n design — no extra amps) and, because their
  RHS is nonzero, carry a supply leg to the rail — the padded circuit
  is never floating, so the DC operator stays regular.  The known pad
  solution (``PAD_SOLUTION_V``) is masked back out of every result.
  ``stats()['pad_overhead']`` accounts for the full price: dense work
  scales with ``n_pad^2`` over every dispatched slot, repeat-fills
  included.
* **stream** — micro-batches are data-parallel *across* devices, not
  sharded within one: each fixed-shape ``(batch_slots, n_pad)``
  micro-batch lands whole on one device
  (:func:`repro.distributed.sharding.stream_devices` resolves the
  stream list), assigned round-robin, so devices never exchange a byte
  on the request path.  The v1 service sharded every micro-batch's
  batch axis over the whole mesh (GSPMD collectives + a per-mesh
  compile in the hot loop) and its measured device scaling *inverted*
  — 15.2 → 3.5 → 0.67 req/s at 1 → 2 → 8 host devices in
  BENCH_pr5.json; streaming replaces that with embarrassingly parallel
  placement.
* **overlap** — dispatch is split submit/wait
  (:func:`repro.core.solver.solve_batch_submit`): the host-side phase
  (pad, stack, netlist build, error model, assembly) runs eagerly,
  then the device solve is *dispatched* and the scheduler moves on to
  the next micro-batch's host build while the device computes (JAX
  async dispatch — no threads).  Each stream holds up to
  ``inflight_per_device`` dispatched micro-batches (2 = classic double
  buffering; 1 degrades to the serial build→solve→unpack loop);
  harvest order is dispatch FIFO.  ``stats()`` splits the wall clock
  into ``host_build_s`` / ``device_wait_s`` / ``unpack_s`` (span
  totals, see *Phases and spans* below) — on a saturated stream the
  device wait is the residual the host could not hide.
* **pattern reuse** — each bucket caches one stamp pattern, reused
  across micro-batches and streams.  ``analog_2n`` slot sets are
  normalized per ``(n, design)``, so the first derivation covers every
  later micro-batch; ``analog_n`` slot sets are data-dependent, but a
  union pattern is still sound to cache (a stamped-but-inactive slot
  is an exact no-op: zero conductance, and the per-system
  ``pair_active`` mask keeps its amp dynamics decoupled) — the cached
  union only *grows*, via ``pattern_merge``, when a micro-batch stamps
  a slot the cache lacks.  ``stats()`` reports ``pattern_derivations``
  per bucket: 1 for ``analog_2n`` buckets by construction, and for
  ``analog_n`` it stops climbing once the cached union covers the
  stream's slot population.

Phases and spans
----------------

Every phase of the hot path is a :func:`repro.analysis.runtime.span`:
a ``jax.profiler.TraceAnnotation`` on the profiler's host plane (on
the device trace's clock, so a trace names the host work the device
idles behind), a sync label for ``SyncWatch`` where the phase declares
one, and a count and seconds in this service's own totals, which
``drain()`` installs for its length (spans opened deep in ``core/``
land on the service that caused them; two services never mix).  The
span that is innermost at a moment names what the host was doing:

* ``serve.drain`` — one ``drain()`` (sync label none);
* ``serve.dispatch`` (label ``dispatch``) — one micro-batch's host
  phase: ``serve.pad`` (pad and stack), ``core.build_nets`` (the
  netlists: ``core.transform``, the Sec-IV transform and its
  ``net_build``-labelled sync, then numpy extraction),
  ``core.pattern`` (the cached stamp pattern's cover check or
  derivation), ``core.assemble`` (the DC operator, built on the
  device: the host gathers the stamp values, sends them under a nested
  ``core.transfer`` and dispatches the assembly) and ``core.transfer``
  (the solve's operands, already on the device) before the async solve;
* ``serve.harvest`` (``harvest``) — the block on a micro-batch's DC
  phase;
* ``serve.finish`` (``finish``) — a deferred finish phase:
  ``core.settle`` (the euler settle's reassembly, DC solve and sweep:
  ``core.settle_prep`` once, the host work between the DC point and
  the first chunk — step size, dt fold, float32 cast, upload — then
  ``core.sweep_chunk`` per chunk launch, ``core.settle_poll`` per
  convergence poll) and ``core.refine`` (graded recovery);
* ``serve.unpack`` (``unpack``) — result slicing and acceptance.

``stats["spans"]`` is ``{name: {"count", "s"}}``; the legacy keys
``wall_s`` / ``host_build_s`` / ``device_wait_s`` / ``settle_finish_s``
/ ``unpack_s`` are the totals of ``serve.drain`` / ``serve.dispatch``
/ ``serve.harvest`` / ``serve.finish`` / ``serve.unpack``.
``stats["queue_wait_s"]`` sums, over dispatched tickets, the time from
``submit`` to the start of the first ``serve.dispatch`` that carried
the ticket — the queueing a client's latency cannot split off.

Three counters say what the circuit asked of the hardware:
``stats["neg_cells"]`` and ``stats["cross_branches"]`` sum, over the
dispatched tickets of analog micro-batches, the negative-resistance
cells stamped and the crosspoint branches between the ``x`` and ``-x``
halves (the off-diagonals of ``K_B``; none for an M-matrix), and
``stats["settle_steps_swept"]`` the Euler steps the device ran, per
micro-batch up to its slowest system (pad rows included), over the
unpacked micro-batches of the euler settle.

Failure semantics — the delivery contract
-----------------------------------------

Every submitted ticket yields **exactly one** terminal answer from
``drain()`` — a :class:`~repro.core.solver.SolveResult` or a structured
:class:`~repro.serving.faults.SolveError` — **in bounded time, under
any single-fault model**.  The machinery behind that contract:

* **error taxonomy** — failures are *returned in the ticket's result
  slot*, never raised: ``SolveError(kind, attempts, detail)`` with
  ``kind`` one of ``device_fault`` (the stream's solve raised),
  ``nonfinite`` (the delivered solution carried NaN/Inf),
  ``uncertified`` (settling never certified and the residual
  overflowed, with digital fallback disabled), ``unrefined`` (graded
  recovery stalled with digital fallback disabled — the precision
  contract cannot be met), ``deadline_expired``, ``poison`` (the
  request's own host build raises repeatedly), and ``shed``
  (queue-depth load shedding).
* **bounded retry + poison bisection** — a failing micro-batch of more
  than one ticket is *bisected*: both halves re-dispatch, so a single
  poison request is isolated in ``log2(batch_slots)`` extra dispatches
  while its batch-mates still solve.  A failing singleton charges that
  ticket's retry budget; after ``max_attempts`` the ticket is
  failed-fast with a ``SolveError`` and **never re-queued** — the v1
  behavior of re-queueing *every* ticket whenever a micro-batch raised
  livelocked ``drain()`` on any persistent fault.
* **deadline enforcement & shedding** — ``deadline`` is an absolute
  :func:`time.monotonic` stamp (see :meth:`SolveService.now`): besides
  ordering admission it is now *enforced* — an expired ticket is
  rejected at pop time with ``deadline_expired``, never dispatched.
  With ``max_queue_depth`` set, a drain over depth sheds the
  lowest-admission-rank (lowest-priority) excess with ``shed``.
* **stream quarantine** — a per-device-stream circuit breaker
  (:class:`repro.distributed.sharding.StreamBreaker`):
  ``breaker_threshold`` consecutive device-side failures trip a stream
  open; its in-flight tickets re-queue at original admission rank onto
  the healthy streams (blameless — no retry budget consumed), and
  exponential-backoff half-open probes restore it.  The service
  degrades to fewer streams; with *every* stream quarantined it keeps
  force-probing the soonest-recovering one rather than deadlocking.
* **analog→digital fallback** — a non-finite analog solution (or an
  uncertified one whose residual overflows) re-solves digitally inside
  :func:`repro.core.solver.solve_batch` (``fallback="cholesky"``
  default), recorded per system as ``info["fallback"]`` and counted in
  ``stats["fallbacks"]`` (``stats["fallbacks_injected"]`` when the
  micro-batch's dispatch carried injected corruption — the two are
  split so chaos runs cannot hide numerical regressions).
* **precision paths (graded recovery)** — with ``refine=`` enabled the
  binary fallback becomes verify → refine → fall back: every delivered
  solution carries ``info["residual"]`` (fp64 relative),
  ``info["refine_iters"]`` and ``info["precision_path"]`` — ``analog``
  (raw solve already within the refinement tol), ``refined``
  (mixed-precision iterative refinement converged, see
  :mod:`repro.core.refine`), or ``fallback`` (refinement stalled, a
  digital re-solve delivered).  With ``fallback="none"`` a stalled row
  is instead failed fast as ``unrefined`` — deterministic, never
  retried.  ``stats["precision_paths"]`` /
  ``stats["refine_iters_total"]`` aggregate the contract per stream.
* **fault injection** — the chaos hook: pass a seeded
  :class:`~repro.serving.faults.FaultInjector` as ``fault_injector``
  and the service injects device faults, NaN solutions, host build
  errors and slow solves *at the exact points real ones surface*;
  ``stats["fault_injections"]`` counts them.  ``tests/test_faults.py``
  and ``benchmarks/solve_service.py --faults`` share this mechanism.

``stats`` surfaces the whole story: ``retries``, ``bisections``,
``shed``, ``deadline_expired``, ``quarantines``, ``fallbacks``,
``fault_injections``, per-kind terminal ``errors`` and the breaker
state.  If ``drain()`` is interrupted by an *unexpected* exception
(a bug, ``KeyboardInterrupt``), every popped ticket — terminal answers
included — is re-queued at original admission rank; already-computed
answers re-deliver from the ticket's result slot on the next drain
without recomputation.

Serving iterative workloads
---------------------------

A Newton / SQP client is not a stream of independent one-shots: it
issues a *round* of B linearized systems, blocks on all B solutions,
updates its iterates, and issues the next round — with the same
``(n, method)`` class every round.  :class:`SolveSession` is the
multi-round ticket kind for exactly this shape (create one with
:meth:`SolveService.session`):

* ``solve_round(a, b)`` submits the round's ``(B,)`` systems as
  ordinary tickets into the same bucketed pipelines as one-shot
  traffic and drains, returning the ``(B, n)`` solutions in submission
  order.  It satisfies the ``rounds=`` executor protocol of
  :func:`repro.optim.batched_newton.newton_batch` /
  ``newton_kkt_batch``, so a Newton loop re-platforms onto the service
  by passing ``rounds=service.session(...)``.
* **pattern + jit reuse across rounds** is structural: bucket
  pipelines (stamp pattern, compiled executables, fill statistics)
  live in ``SolveService._pipelines`` and persist across drains, so
  round k > 1 of an iteration-invariant sparsity class is pure cache
  hits — ``pattern_derivations`` stays at 1 for the session's bucket.
* **failure semantics apply per round**: each round's tickets carry
  the session's ``priority`` and a fresh deadline
  (``round_deadline_s``), and ride the full PR-7 machinery — retry
  budgets, bisection, quarantine, fallback.  Exactly-once still holds
  ticket-wise: a mid-round device fault is retried/bisected inside
  the drain and the round completes; only a *terminal* per-ticket
  failure surfaces, as a :class:`SessionRoundError` carrying the
  per-system :class:`SolveError` map (the solutions of the round's
  healthy systems are on the error).  Interleaved one-shot traffic
  drained by a session round is delivered via
  ``session.other_results``.

Single-host caveats (see ROADMAP): netlist building and result
unpacking stay host-side (they are the overlap *budget*, not dead
time).  The settle path is split submit/wait
(:meth:`repro.core.solver.PendingBatchSolve.wait_dc`): a settling
micro-batch releases its stream slot as soon as its DC phase harvests,
and the synchronous transient analysis runs as a deferred *finish*
phase (``stats['settle_finish_s']``) — settling requests still bucket
at exact ``n`` because settle metrics do not un-pad, but they no
longer block their stream's double-buffering.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import numpy as np

from repro.analysis.runtime import SpanTotals, span
from repro.core import engine
from repro.core.operating_point import NonIdealities
from repro.core.refine import as_refine_spec
from repro.core.solver import (
    ANALOG_METHODS,
    DIGITAL_METHODS,
    FALLBACK_METHODS,
    FALLBACK_RESIDUAL_TOL,
    PRECISION_PATHS,
    PendingBatchSolve,
    SolveResult,
    _build_nets,
    solve_batch_submit,
)
from repro.kernels.ell_transient import SWEEP_DTYPES
from repro.core.specs import DEFAULT_PARAMS, OPAMPS, CircuitParams, OpAmpSpec
from repro.serving.engine import AdmissionQueue
from repro.serving.faults import (
    ERROR_KINDS,
    FaultInjected,
    FaultInjector,
    SolveError,
)

# nominal voltage of padded unknowns; in-range for the paper's
# x ~ U[-0.5, 0.5] V protocol, nonzero so pad nodes keep a supply leg
PAD_SOLUTION_V = 0.1

# default padding grid; sizes beyond the grid round up to PAD_QUANTUM
DEFAULT_PAD_SIZES = (8, 16, 32, 48, 64, 96, 128, 192, 256)
PAD_QUANTUM = 64


@dataclasses.dataclass(frozen=True)
class SolveSignature:
    """The option tuple that decides batch compatibility.

    Two requests may share a device batch iff their signatures are
    equal — every field below changes either the stamped circuit, the
    solver semantics, or the settle pipeline.  ``opamp`` is the full
    (frozen, hashable) spec, so custom parts bucket separately from
    registry parts even under a shared name.
    """

    method: str
    opamp: OpAmpSpec
    d_policy: str = "proposed"
    beta: float = 0.5
    alpha: float = 1.0
    compute_settling: bool = False
    settle_method: str = "auto"
    settle_max_steps: int = 200_000
    settle_dt_policy: str = "diag"
    sweep_dtype: str = "float32"
    tol: float = 1e-10
    max_iter: int = 10000
    nonideal: NonIdealities | None = None

    def normalized(self) -> "SolveSignature":
        """Reset every field the dispatched solver ignores to its
        default, so requests differing only in irrelevant options still
        share a bucket (a digital request's opamp, an analog request's
        CG tolerance, settle options without ``compute_settling``...).
        """
        changes: dict[str, Any] = {}
        if self.method in DIGITAL_METHODS:
            # no circuit is stamped and nothing settles
            changes.update(
                opamp=OPAMPS["AD712"], nonideal=None, d_policy="proposed",
                beta=0.5, alpha=1.0, compute_settling=False,
            )
            if self.method == "cholesky":    # direct: no iteration knobs
                changes.update(tol=1e-10, max_iter=10000)
        else:
            changes.update(tol=1e-10, max_iter=10000)
            if self.method == "analog_n":
                # the preliminary builder takes only (a, b, params)
                changes.update(d_policy="proposed", beta=0.5, alpha=1.0)
        if not (self.compute_settling and self.method in ANALOG_METHODS):
            # sweep_dtype only selects the settle sweep kernel, so it is
            # solver-irrelevant (and must not split buckets) without one
            changes.update(
                settle_method="auto", settle_max_steps=200_000,
                settle_dt_policy="diag", sweep_dtype="float32",
            )
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class SolveTicket:
    """One queued request; ``result`` is filled by :meth:`SolveService.drain`
    with the solution — or a structured :class:`SolveError`, never
    nothing: exactly-once delivery is the service contract."""

    rid: int
    a: np.ndarray
    b: np.ndarray
    sig: SolveSignature
    # optional settle warm start (previous solution, (n,)) — a per-ticket
    # payload, NOT part of the bucket signature: cold and warm tickets
    # share micro-batches (a cold row just gets the zero initial state)
    x0: np.ndarray | None = None
    result: SolveResult | SolveError | None = None
    # failed dispatch/harvest count (bounded by max_attempts)
    attempts: int = 0
    # admission stamps (set by AdmissionQueue.push)
    priority: int = 0
    deadline: float | None = None
    seq: int = 0
    # time.perf_counter() at submit, and at the start of the first
    # serve.dispatch span that carried this ticket (the queue wait ends)
    submitted_at: float = 0.0
    dispatched_at: float | None = None

    @property
    def n(self) -> int:
        return self.a.shape[0]


@dataclasses.dataclass
class _BucketPipeline:
    """Cached per-bucket dispatch state."""

    n_pad: int
    sig: SolveSignature
    pattern: engine.StampPattern | None = None
    micro_batches: int = 0
    systems: int = 0
    fill_slots: int = 0
    pattern_derivations: int = 0
    pattern_rebuilds: int = 0


@dataclasses.dataclass
class _InFlight:
    """One dispatched micro-batch awaiting harvest on its stream."""

    pipe: _BucketPipeline
    tickets: list
    pending: PendingBatchSolve
    dev: int
    # the fault kind the chaos injector planted into this dispatch (None
    # for a clean one) — lets delivery accounting attribute corruption-
    # driven recovery to the injector instead of the numerics
    injected: str | None = None


def pad_system(
    a: np.ndarray, b: np.ndarray, n_pad: int, *, rhs: str = "supply"
) -> tuple[np.ndarray, np.ndarray]:
    """Identity-extend ``(A, b)`` to ``n_pad`` unknowns.

    The pad block is ``g_pad I`` with ``g_pad = mean(diag(A))`` —
    decoupled, SPD and in-conductance-scale.  The pad RHS depends on
    the consumer:

    * ``rhs="supply"`` (the analog designs): ``g_pad * PAD_SOLUTION_V``
      — nonzero, so every pad node carries a supply leg to the rail and
      the padded circuit's DC operator is never singular.  Pad solution
      ``PAD_SOLUTION_V``.
    * ``rhs="zero"`` (the digital baselines): zero-extension.  There is
      no circuit to keep connected, and a nonzero pad RHS would inflate
      ``||b||`` and *dilute the iterative solvers' relative-residual
      stopping test* — zero pad entries keep CG/Jacobi iterate
      sequences on the real block identical to the unpadded solve
      (zero initial residual on a decoupled block stays zero).
    """
    n = a.shape[0]
    if n == n_pad:
        return a, b
    if n > n_pad:
        raise ValueError(f"system of size {n} cannot pad to {n_pad}")
    g_pad = float(np.mean(np.diagonal(a)))
    a_pad = np.zeros((n_pad, n_pad), dtype=np.float64)
    a_pad[:n, :n] = a
    a_pad[np.arange(n, n_pad), np.arange(n, n_pad)] = g_pad
    fill = g_pad * PAD_SOLUTION_V if rhs == "supply" else 0.0
    b_pad = np.full(n_pad, fill, dtype=np.float64)
    b_pad[:n] = b
    return a_pad, b_pad


class SolveService:
    """Queue -> bucket -> pad -> per-device streamed async dispatch.

    Parameters
    ----------
    batch_slots:
        Systems per device micro-batch.  Fixed: partial micro-batches
        are filled by repeating the last system (counted in ``stats``),
        so every bucket compiles exactly one ``(batch_slots, n_pad)``
        pipeline per device.
    mesh / n_devices / devices:
        The device streams.  ``devices`` is an explicit list; ``mesh``
        contributes its device order (the v1 constructor signature —
        the mesh is *not* used for GSPMD sharding any more);
        ``n_devices`` takes the first N visible devices.  Default: the
        default device alone.
    inflight_per_device:
        Dispatched-but-unharvested micro-batches each stream may hold.
        2 (default) double-buffers: the host builds micro-batch ``i+1``
        while the device solves ``i``.  1 disables the overlap (serial
        reference mode, used by the benchmark's overlap probe).
    pad_sizes:
        The bucketing grid for ``n``; off-grid sizes round up to the
        next multiple of ``PAD_QUANTUM``.
    max_attempts:
        Retry budget per ticket: failed dispatches/harvests a single
        ticket may see before it is failed-fast with a
        :class:`SolveError` (never re-queued) — the bound that keeps
        ``drain()`` terminating under any persistent fault.
    max_queue_depth:
        Optional load shedding: a drain admitting more than this many
        tickets sheds the lowest-admission-rank excess with
        ``SolveError(kind="shed")``.
    fallback / fallback_residual_tol:
        The analog→digital graceful-degradation policy forwarded to
        :func:`repro.core.solver.solve_batch_submit` (``"cholesky"``
        default, ``"cg"``, ``"none"``).  With ``"none"``, a
        non-finite result retries (it may be transient) and an
        uncertified-with-residual-overflow one fails fast as
        ``uncertified`` (it is deterministic — retrying cannot help).
    refine:
        The graded-recovery policy (``None``/``False`` — off, ``True``
        — the default :class:`repro.core.refine.RefineSpec`, a driver
        name or a full spec), forwarded to
        :func:`repro.core.solver.solve_batch_submit` for every analog
        micro-batch.  Enabled, every delivered solution carries the
        per-ticket precision contract — ``info["residual"]`` (fp64
        relative), ``info["refine_iters"]`` and
        ``info["precision_path"]`` — and a ticket whose refinement
        stalls with ``fallback="none"`` fails fast as ``unrefined``
        (deterministic, like ``uncertified``).
    breaker_threshold / breaker_backoff_s / breaker_backoff_max_s:
        The per-stream circuit breaker: consecutive device-side
        failures before a stream is quarantined, and its
        exponential-backoff half-open probe schedule
        (:class:`repro.distributed.sharding.StreamBreaker`).
    fault_injector:
        Optional seeded :class:`repro.serving.faults.FaultInjector` —
        the chaos hook shared by the fault test suite and the
        degraded-mode benchmark.
    """

    def __init__(
        self,
        *,
        batch_slots: int = 8,
        mesh=None,
        n_devices: int | None = None,
        devices=None,
        inflight_per_device: int = 2,
        pad_sizes: tuple[int, ...] = DEFAULT_PAD_SIZES,
        params: CircuitParams = DEFAULT_PARAMS,
        max_attempts: int = 3,
        max_queue_depth: int | None = None,
        fallback: str = "cholesky",
        fallback_residual_tol: float = FALLBACK_RESIDUAL_TOL,
        refine=None,
        breaker_threshold: int = 3,
        breaker_backoff_s: float = 0.25,
        breaker_backoff_max_s: float = 30.0,
        fault_injector: FaultInjector | None = None,
    ):
        from repro.distributed.sharding import StreamBreaker, stream_devices

        self.devices = stream_devices(
            mesh=mesh, devices=devices, n_devices=n_devices
        )
        if inflight_per_device < 1:
            raise ValueError("inflight_per_device must be >= 1")
        if max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if fallback is None:
            fallback = "none"
        if fallback not in FALLBACK_METHODS:
            raise ValueError(
                f"unknown fallback {fallback!r}: expected one of "
                f"{FALLBACK_METHODS}"
            )
        self.inflight_per_device = int(inflight_per_device)
        self.batch_slots = max(1, int(batch_slots))
        self.pad_sizes = tuple(sorted(pad_sizes))
        self.params = params
        self.max_attempts = int(max_attempts)
        self.max_queue_depth = (
            None if max_queue_depth is None else int(max_queue_depth)
        )
        self.fallback = fallback
        self.fallback_residual_tol = float(fallback_residual_tol)
        self.refine = as_refine_spec(refine)
        self.fault_injector = fault_injector
        self.breaker = StreamBreaker(
            len(self.devices),
            threshold=breaker_threshold,
            backoff_s=breaker_backoff_s,
            backoff_max_s=breaker_backoff_max_s,
        )
        self.queue = AdmissionQueue()
        self._pipelines: dict[tuple, _BucketPipeline] = {}
        self._next_rid = 0
        self._rr = 0             # round-robin stream cursor
        # this service's span totals (installed for each drain) and the
        # summed submit-to-dispatch wait of its dispatched tickets
        self._spans = SpanTotals()
        self._queue_wait_s = 0.0
        self._real_sq = 0.0      # sum n^2 over served systems (stats)
        self._device_batches = [0] * len(self.devices)
        self._counters: dict[str, Any] = {
            "retries": 0,
            "bisections": 0,
            "shed": 0,
            "deadline_expired": 0,
            "fallbacks": 0,
            # fallbacks in micro-batches whose dispatch carried an
            # injected corruption — attributed to the injector, so the
            # genuine "fallbacks" counter stays a clean numerics signal
            "fallbacks_injected": 0,
            "refine_iters_total": 0,
            "precision_paths": {k: 0 for k in PRECISION_PATHS},
            "quarantines": 0,
            "requeued_on_quarantine": 0,
            "errors": {k: 0 for k in ERROR_KINDS},
            "neg_cells": 0,
            "device_assembled": 0,
            "cross_branches": 0,
            "settle_steps_swept": 0,
        }

    @staticmethod
    def now() -> float:
        """The service's deadline clock (:func:`time.monotonic`).

        Deadlines are absolute stamps on this clock:
        ``submit(..., deadline=SolveService.now() + budget_s)``.
        """
        return time.monotonic()

    # ------------------------------------------------------------ intake
    def pad_to(self, n: int) -> int:
        for size in self.pad_sizes:
            if n <= size:
                return size
        return n + (-n) % PAD_QUANTUM

    def _bucket_n(self, ticket: SolveTicket) -> int:
        """The bucket size for one request.

        Settling requests bucket at their *exact* size: settling time
        is a global circuit property, and the 0.1 V pad-node transients
        would otherwise be measured along with the requested system's
        (solutions un-pad cleanly; settle metrics do not).  Everything
        else lands on the padding grid.
        """
        if ticket.sig.compute_settling:
            return ticket.n
        return self.pad_to(ticket.n)

    def submit(
        self,
        a,
        b,
        *,
        method: str = "analog_2n",
        opamp: str | OpAmpSpec = "AD712",
        nonideal: NonIdealities | None = None,
        d_policy: str = "proposed",
        beta: float = 0.5,
        alpha: float = 1.0,
        compute_settling: bool = False,
        settle_method: str = "auto",
        settle_max_steps: int = 200_000,
        settle_dt_policy: str = "diag",
        sweep_dtype: str = "float32",
        tol: float = 1e-10,
        max_iter: int = 10000,
        x0=None,
        priority: int = 0,
        deadline: float | None = None,
    ) -> int:
        """Queue one system; returns the request id.

        Nothing is solved until :meth:`drain` — submission only
        validates shapes, records the batch-compatibility signature,
        and stamps the admission order (``priority`` admits first,
        earliest ``deadline`` within a priority class, FIFO on ties —
        see :func:`repro.serving.engine.admission_key`).

        ``sweep_dtype`` ("float32" | "bfloat16") selects the settle
        sweep kernel precision (signature-relevant only with
        ``compute_settling`` on an analog method).  ``x0`` ((n,)) warm
        starts the settle sweep from a previous solution — a per-ticket
        payload that does not affect bucketing (the
        :class:`SolveSession` warm-start path).
        """
        a = np.asarray(a, dtype=np.float64)
        b = np.asarray(b, dtype=np.float64)
        if a.ndim != 2 or a.shape[0] != a.shape[1] or b.shape != (a.shape[0],):
            raise ValueError(f"expected (n, n) and (n,); got {a.shape}, {b.shape}")
        if sweep_dtype not in SWEEP_DTYPES:
            raise ValueError(
                f"unknown sweep_dtype {sweep_dtype!r}: expected one of "
                f"{SWEEP_DTYPES}"
            )
        if x0 is not None:
            x0 = np.asarray(x0, dtype=np.float64)
            if x0.shape != b.shape or not np.isfinite(x0).all():
                # a malformed warm start must not poison the sweep —
                # reject at submit time, where the caller can see it
                raise ValueError(
                    f"x0 must be a finite ({a.shape[0]},) array"
                )
        if method not in ANALOG_METHODS + DIGITAL_METHODS:
            raise ValueError(
                f"unknown method {method!r}: expected one of "
                f"{ANALOG_METHODS + DIGITAL_METHODS}"
            )
        if isinstance(opamp, str):
            if opamp not in OPAMPS:
                raise ValueError(f"unknown opamp {opamp!r}")
            opamp = OPAMPS[opamp]
        sig = SolveSignature(
            method=method,
            opamp=opamp,
            d_policy=d_policy,
            beta=beta,
            alpha=alpha,
            compute_settling=compute_settling,
            settle_method=settle_method,
            settle_max_steps=settle_max_steps,
            settle_dt_policy=settle_dt_policy,
            sweep_dtype=sweep_dtype,
            tol=tol,
            max_iter=max_iter,
            nonideal=nonideal,
        ).normalized()
        rid = self._next_rid
        self._next_rid += 1
        self.queue.push(
            SolveTicket(rid=rid, a=a, b=b, sig=sig, x0=x0,
                        submitted_at=time.perf_counter()),
            priority=priority, deadline=deadline,
        )
        return rid

    # ---------------------------------------------------------- dispatch
    def _bucket_key(self, ticket: SolveTicket) -> tuple:
        return (self._bucket_n(ticket), ticket.sig)

    def _bucket_pattern(
        self,
        pipe: _BucketPipeline,
        a_pad: np.ndarray,
        b_pad: np.ndarray,
    ) -> tuple[engine.StampPattern | None, list | None]:
        """The bucket's cached stamp pattern, re-derived only on a miss.

        ``analog_2n`` slot sets are normalized per ``(n, design)`` (all
        pair slots + the union of observed ground slots), so after the
        first micro-batch this is a pure cache read
        (``pattern_derivations == 1``).  ``analog_n`` slot sets are
        data-dependent, but caching the union is still sound — a
        stamped-but-inactive slot is an exact no-op (zero conductance;
        the per-system ``pair_active`` mask keeps its amp dynamics
        decoupled) — so those buckets also serve from cache and only
        re-derive + ``pattern_merge`` when a micro-batch stamps a slot
        the cached union lacks.

        The netlists built for the cover check are returned and handed
        to ``solve_batch`` so each micro-batch builds them exactly once.
        """
        sig = pipe.sig
        if sig.method not in ANALOG_METHODS:
            return None, None
        nets = _build_nets(
            a_pad, b_pad, sig.method, d_policy=sig.d_policy,
            beta=sig.beta, alpha=sig.alpha, params=self.params,
        )
        with span("core.pattern"):
            if pipe.pattern is not None and engine.pattern_covers(
                pipe.pattern, nets
            ):
                return pipe.pattern, nets
            union = engine.pattern_union(nets, sig.opamp)
            pipe.pattern_derivations += 1
            if pipe.pattern is None:
                pipe.pattern = union
            else:
                pipe.pattern = engine.pattern_merge(pipe.pattern, union)
                pipe.pattern_rebuilds += 1
        return pipe.pattern, nets

    def _dispatch_micro_batch(
        self, pipe: _BucketPipeline, tickets: list[SolveTicket], dev: int
    ) -> _InFlight:
        """Host phase of one micro-batch + async dispatch to stream ``dev``.

        Returns without blocking on the device — the scheduler builds
        the next micro-batch while this one's solve runs.  An armed
        fault injector draws once per dispatch here: ``build_error``
        raises out of the host phase, the other kinds are planted into
        the returned handle so they surface at harvest exactly where
        real ones would.
        """
        # serve.dispatch's sync label: any jax.Array materialization in
        # here is a dispatch-phase sync — the runtime gate requires zero
        with span("serve.dispatch", sync="dispatch"):
            t_start = time.perf_counter()
            for t in tickets:
                if t.dispatched_at is None:
                    t.dispatched_at = t_start
                    self._queue_wait_s += t_start - t.submitted_at
            fault = (
                None if self.fault_injector is None
                else self.fault_injector.draw(dev=dev)
            )
            if fault is not None:
                self.fault_injector.build_fault(fault)  # raises build_error
            sig = pipe.sig
            n_real = len(tickets)
            fill = self.batch_slots - n_real
            with span("serve.pad"):
                rhs = "zero" if sig.method in DIGITAL_METHODS else "supply"
                padded = [
                    pad_system(t.a, t.b, pipe.n_pad, rhs=rhs) for t in tickets
                ]
                padded += [padded[-1]] * fill    # repeat-fill to fixed shape
                a_stack = np.stack([p[0] for p in padded])
                b_stack = np.stack([p[1] for p in padded])

                settle_x0 = None
                if sig.method in ANALOG_METHODS and any(
                    t.x0 is not None for t in tickets
                ):
                    # warm-start stack: a cold ticket's row is the zero
                    # initial state (identical to no-x0 dispatch); warm
                    # pad entries sit at the known pad solution
                    rows = []
                    for t in tickets:
                        row = np.zeros(pipe.n_pad, dtype=np.float64)
                        if t.x0 is not None:
                            row[: t.n] = t.x0
                            row[t.n:] = PAD_SOLUTION_V
                        rows.append(row)
                    rows += [rows[-1]] * fill
                    settle_x0 = np.stack(rows)

            pattern, nets = self._bucket_pattern(pipe, a_stack, b_stack)
            for net in (nets or [])[:n_real]:
                self._counters["neg_cells"] += net.n_cells
                self._counters["cross_branches"] += net.n_cross_branches
            pending = solve_batch_submit(
                a_stack,
                b_stack,
                method=sig.method,
                opamp=sig.opamp,
                nonideal=sig.nonideal,
                nets=nets,
                d_policy=sig.d_policy,
                beta=sig.beta,
                alpha=sig.alpha,
                compute_settling=sig.compute_settling,
                settle_method=sig.settle_method,
                settle_max_steps=sig.settle_max_steps,
                settle_dt_policy=sig.settle_dt_policy,
                tol=sig.tol,
                max_iter=sig.max_iter,
                fallback=self.fallback,
                fallback_residual_tol=self.fallback_residual_tol,
                refine=self.refine,
                sweep_dtype=sig.sweep_dtype,
                settle_x0=settle_x0,
                pattern=pattern,
                device=self.devices[dev],
            )
        if fault is not None:
            pending = self.fault_injector.arm(pending, fault)
        pipe.micro_batches += 1
        pipe.systems += n_real
        pipe.fill_slots += fill
        self._device_batches[dev] += 1
        return _InFlight(
            pipe=pipe, tickets=tickets, pending=pending, dev=dev,
            injected=fault,
        )

    def _unpack_micro_batch(
        self, pipe, tickets, batch, injected: str | None = None
    ) -> list[tuple[SolveTicket, str, str]]:
        """Materialize per-ticket results from one harvested micro-batch.

        Vectorized: one batched slice (+ ``tolist`` bulk conversion)
        per result field and per ``info`` key, instead of the v1
        per-ticket ``batch[k]`` loop that re-entered the
        ``BatchSolveResult.__getitem__`` normalization once per ticket
        per key.  ``x`` rows are handed out as views into the single
        micro-batch array, trimmed to each ticket's real ``n`` (the pad
        solution is masked out).

        Delivery acceptance runs here: a ticket whose trimmed solution
        carries NaN/Inf is NOT delivered — it is returned as a
        ``("nonfinite", ...)`` failure for the retry machinery (the
        corruption may be transient).  An uncertified settling result
        whose residual overflows with digital fallback disabled is
        returned as ``("uncertified", ...)`` — deterministic, so the
        caller fails it fast; likewise a ``precision_path ==
        "unrefined"`` system (graded recovery stalled with fallback
        disabled) is returned as ``("unrefined", ...)``.  Everything
        else is delivered, with per-system digital fallbacks counted —
        attributed to ``fallbacks_injected`` instead of ``fallbacks``
        when this micro-batch's dispatch carried an ``injected``
        corruption, so chaos runs cannot mask genuine numerical
        regressions — and the precision-path / refine-iteration
        counters updated for every delivered solution.
        """
        n_real = len(tickets)
        xs = np.asarray(batch.x)
        stable = np.asarray(batch.stable)[:n_real].tolist()
        settle = (
            None if batch.settle_time is None
            else np.asarray(batch.settle_time)[:n_real].tolist()
        )
        if batch.info.get("settle_method") == "euler":
            # the sweep runs until the slowest system settles or the
            # budget ends, so the largest step count of the micro-batch
            # is the Euler steps the device ran
            self._counters["settle_steps_swept"] += int(
                np.max(batch.info["settle_steps"])
            )
        cols: dict[str, list] = {}
        shared: dict[str, Any] = {}
        for key, v in batch.info.items():
            if isinstance(v, np.ndarray) and v.ndim >= 1:
                cols[key] = v[:n_real].tolist()
            else:
                # scalar shared by the batch; normalize numpy scalars
                # exactly as BatchSolveResult.__getitem__ would
                shared[key] = batch._info_entry(v, 0)
        bad: list[tuple[SolveTicket, str, str]] = []
        for i, ticket in enumerate(tickets):
            info = {
                k: (cols[k][i] if k in cols else shared[k])
                for k in batch.info
            }
            x = xs[i, : ticket.n]
            if not np.isfinite(x).all():
                bad.append((ticket, "nonfinite", "solution carried NaN/Inf"))
                continue
            if info.get("precision_path") == "unrefined":
                rel = info.get("residual", float("nan"))
                bad.append((
                    ticket, "unrefined",
                    f"refinement stalled at rel residual {rel:.3e} "
                    f"after {info.get('refine_iters', 0)} inner solve(s), "
                    "fallback disabled",
                ))
                continue
            if info.get("settle_certified") is False:
                r = ticket.a @ x - ticket.b
                rel = float(
                    np.linalg.norm(r)
                    / max(np.linalg.norm(ticket.b), np.finfo(np.float64).tiny)
                )
                if rel > self.fallback_residual_tol and not info.get("fallback"):
                    bad.append((
                        ticket, "uncertified",
                        f"settle uncertified, rel residual {rel:.3e}",
                    ))
                    continue
            if info.get("fallback"):
                key = (
                    "fallbacks_injected" if injected == "nonfinite"
                    else "fallbacks"
                )
                self._counters[key] += 1
            path = info.get("precision_path")
            if path is not None:
                self._counters["precision_paths"][path] += 1
                self._counters["refine_iters_total"] += int(
                    info.get("refine_iters", 0)
                )
            info["service_n_padded"] = pipe.n_pad
            info["service_batch_slots"] = self.batch_slots
            ticket.result = SolveResult(
                x=x,
                method=batch.method,
                stable=bool(stable[i]),
                settle_time=None if settle is None else float(settle[i]),
                info=info,
            )
            self._real_sq += float(ticket.n) ** 2
        return bad

    # ------------------------------------------------- failure machinery
    def _fail(self, ticket: SolveTicket, kind: str, detail: str, out) -> None:
        """Terminal: deliver a structured error in the result slot."""
        err = SolveError(kind=kind, attempts=ticket.attempts, detail=detail)
        ticket.result = err
        out[ticket.rid] = err
        self._counters["errors"][kind] += 1

    def _admit_ticket(self, ticket: SolveTicket, out) -> bool:
        """Pop-time gate: re-deliver already-terminal tickets, reject
        expired deadlines (never dispatched).  True = dispatchable."""
        if ticket.result is not None:
            # answered in an interrupted drain: re-deliver, don't redo
            out[ticket.rid] = ticket.result
            return False
        if ticket.deadline is not None and self.now() >= ticket.deadline:
            self._counters["deadline_expired"] += 1
            self._fail(ticket, "deadline_expired",
                       "deadline passed before dispatch", out)
            return False
        return True

    def _group_failed(
        self, pipe, group, exc: Exception, *, device_side: bool, work, out
    ) -> None:
        """One micro-batch raised: bisect groups, charge singletons.

        A group of more than one ticket carries no per-ticket blame —
        it splits in half and both halves re-dispatch (front of the
        work queue, so retries keep their early admission rank).  A
        singleton failure is evidence against that ticket: its retry
        budget is charged, and at ``max_attempts`` it fails fast with
        ``device_fault`` (the stream's solve raised) or ``poison``
        (its own host build raised) — never re-queued again.
        """
        if len(group) > 1:
            self._counters["bisections"] += 1
            mid = (len(group) + 1) // 2
            work.appendleft((pipe, group[mid:]))
            work.appendleft((pipe, group[:mid]))
            return
        ticket = group[0]
        ticket.attempts += 1
        kind = "device_fault" if device_side else "poison"
        if ticket.attempts >= self.max_attempts:
            detail = f"{type(exc).__name__}: {exc}"
            self._fail(ticket, kind, detail[:200], out)
        else:
            self._counters["retries"] += 1
            work.appendleft((pipe, [ticket]))

    def _quarantine(self, dev: int, inflight, per_dev, work) -> None:
        """A stream tripped open: pull its in-flight micro-batches and
        re-queue their tickets (blameless — no retry budget consumed)
        onto the healthy streams, at the front of the work queue."""
        self._counters["quarantines"] += 1
        stuck = [f for f in inflight if f.dev == dev]
        for flight in reversed(stuck):
            inflight.remove(flight)
            per_dev[dev] -= 1
            self._counters["requeued_on_quarantine"] += len(flight.tickets)
            work.appendleft((flight.pipe, flight.tickets))

    def _next_stream(self, per_dev) -> int | None:
        """Round-robin over streams with a free in-flight slot that the
        circuit breaker admits (closed, or due for a half-open probe)."""
        n_dev = len(self.devices)
        for k in range(n_dev):
            dev = (self._rr + k) % n_dev
            if (
                per_dev[dev] < self.inflight_per_device
                and self.breaker.acquire(dev)
            ):
                self._rr = (dev + 1) % n_dev
                return dev
        return None

    def _harvest(
        self, flight: _InFlight, out, per_dev, work, inflight, finishing
    ) -> None:
        """Block on one in-flight micro-batch's *device phase* and
        either deliver it or hand it to the finish queue.

        Only the DC phase (``wait_dc``) occupies the stream: as soon as
        it harvests cleanly the stream slot is released and the breaker
        records the success — a split handle (settle sweep / fallback
        still pending) is appended to ``finishing`` for deferred
        completion, so a settling micro-batch no longer blocks its
        stream's double-buffering.  A device-side exception feeds the
        stream's circuit breaker (tripping it quarantines the stream
        and re-queues its other in-flights) and the group failure
        machinery; a clean single-phase harvest runs delivery
        acceptance immediately (non-finite / uncertified tickets
        re-enter the retry loop individually).
        """
        try:
            with span("serve.harvest", sync="harvest"):
                batch = flight.pending.wait_dc()
        except Exception as exc:
            per_dev[flight.dev] -= 1
            tripped = self.breaker.record_failure(flight.dev)
            self._group_failed(
                flight.pipe, flight.tickets, exc,
                device_side=True, work=work, out=out,
            )
            if tripped:
                self._quarantine(flight.dev, inflight, per_dev, work)
            return
        per_dev[flight.dev] -= 1
        self.breaker.record_success(flight.dev)
        if flight.pending.split:
            finishing.append(flight)
            return
        self._deliver(flight, batch, out, work)

    def _finish_flight(self, flight: _InFlight, out, work) -> None:
        """Complete a deferred finish phase (settle sweep + fallback)
        and deliver.

        The flight's stream was already released and its DC harvest
        recorded as a breaker success — a finish-phase exception is
        charged to the ticket group (bisect / retry / fail-fast as
        ``device_fault``) but never to the stream's breaker: the
        stream did its job, the post-DC analysis failed.
        """
        try:
            with span("serve.finish", sync="finish"):
                batch = flight.pending.wait()
        except Exception as exc:
            self._group_failed(
                flight.pipe, flight.tickets, exc,
                device_side=True, work=work, out=out,
            )
            return
        self._deliver(flight, batch, out, work)

    def _deliver(self, flight: _InFlight, batch, out, work) -> None:
        """Delivery acceptance for one harvested micro-batch: unpack,
        hand out terminal answers, route rejected tickets to retry."""
        with span("serve.unpack", sync="unpack"):
            bad = self._unpack_micro_batch(
                flight.pipe, flight.tickets, batch, injected=flight.injected
            )
        for t in flight.tickets:
            if t.result is not None:
                out[t.rid] = t.result
        retry: list[SolveTicket] = []
        for ticket, kind, detail in bad:
            ticket.attempts += 1
            if (
                kind in ("uncertified", "unrefined")
                or ticket.attempts >= self.max_attempts
            ):
                # uncertified/unrefined are deterministic — retrying
                # cannot help
                self._fail(ticket, kind, detail, out)
            else:
                self._counters["retries"] += 1
                retry.append(ticket)
        if retry:
            work.appendleft((flight.pipe, retry))

    def drain(self) -> dict[int, SolveResult | SolveError]:
        """Answer everything queued; returns ``{rid: result-or-error}``.

        Tickets leave the queue in admission order
        (priority/deadline/FIFO) — shedding the over-depth excess and
        rejecting expired deadlines — and group into buckets; each
        bucket's micro-batches are assigned to breaker-admitted device
        streams round-robin.  A stream holding ``inflight_per_device``
        dispatched micro-batches back-pressures the scheduler: the
        globally-oldest micro-batch is harvested (device wait +
        vectorized unpack) before the next host build starts — with 2
        in-flight slots the host build of micro-batch ``i+1`` overlaps
        the device solve of ``i`` on every stream.  Failures never
        raise out of here: they bisect, retry within each ticket's
        ``max_attempts`` budget, and land as :class:`SolveError`
        results (see the module docstring's failure-semantics
        section), so every admitted ticket is answered exactly once
        and the drain terminates under any persistent fault.  Results
        are handed to the caller and not retained by the service (a
        long-running stream must not accumulate solved systems).

        Only an *unexpected* exception (a scheduler bug,
        ``KeyboardInterrupt``) still propagates; then every popped
        ticket is re-queued at its original admission rank — already
        answered ones re-deliver from their result slot next drain.
        """
        popped = self.queue.pop_all()
        if not popped:
            return {}
        assembled_before = engine.DC_STATS["device_assembled"]
        # every span opened below, in core/ too, lands on this service
        with self._spans.installed(), span("serve.drain"):
            out: dict[int, SolveResult | SolveError] = {}

            queued = popped
            if (
                self.max_queue_depth is not None
                and len(queued) > self.max_queue_depth
            ):
                # load shedding: lowest admission rank (lowest priority /
                # latest deadline / newest) drops first
                queued, shed = (
                    queued[: self.max_queue_depth],
                    queued[self.max_queue_depth:],
                )
                self._counters["shed"] += len(shed)
                for ticket in shed:
                    self._fail(ticket, "shed",
                               f"queue depth over {self.max_queue_depth}", out)

            buckets: dict[tuple, list[SolveTicket]] = {}
            for ticket in queued:
                buckets.setdefault(self._bucket_key(ticket), []).append(ticket)

            # fixed-shape micro-batch groups, bucket-major in admission
            # order of each bucket's head request; retries/bisections
            # re-enter at the FRONT so old work finishes first
            work: collections.deque = collections.deque()
            for key, tickets in buckets.items():
                n_pad, sig = key
                pipe = self._pipelines.setdefault(
                    key, _BucketPipeline(n_pad=n_pad, sig=sig)
                )
                for start in range(0, len(tickets), self.batch_slots):
                    work.append(
                        (pipe, tickets[start:start + self.batch_slots])
                    )

            inflight: list[_InFlight] = []      # dispatch-FIFO harvest order
            finishing: list[_InFlight] = []     # DC done, settle/fallback due
            per_dev = [0] * len(self.devices)
            # deterministic placement per drain: identical request streams
            # hit identical (bucket, device) pairs every drain, so a warmed
            # service never recompiles (jit executables are per device)
            self._rr = 0
            try:
                while work or inflight or finishing:
                    if work:
                        pipe, group = work.popleft()
                        group = [
                            t for t in group if self._admit_ticket(t, out)
                        ]
                        if not group:
                            continue
                        dev = self._next_stream(per_dev)
                        if dev is not None:
                            try:
                                flight = self._dispatch_micro_batch(
                                    pipe, group, dev
                                )
                            except Exception as exc:
                                # host build failure: no device verdict —
                                # hand back a consumed probe slot unjudged
                                self.breaker.release(dev)
                                self._group_failed(
                                    pipe, group, exc,
                                    device_side=False, work=work, out=out,
                                )
                            else:
                                inflight.append(flight)
                                per_dev[dev] += 1
                            continue
                        work.appendleft((pipe, group))
                    if inflight:
                        self._harvest(
                            inflight.pop(0), out, per_dev, work, inflight,
                            finishing,
                        )
                    elif finishing:
                        # streams idle (or blocked): run deferred finish
                        # phases — settle sweeps whose DC harvest already
                        # freed their stream slot
                        self._finish_flight(finishing.pop(0), out, work)
                    elif work:
                        # every stream quarantined with backoff pending:
                        # degrade to probing, never to a deadlock
                        self.breaker.force_probe()
            except BaseException:
                # unexpected interruption: the caller receives nothing, so
                # put EVERY popped ticket back at its original admission
                # rank — answered ones re-deliver from their result slot
                # next drain, nothing is silently discarded
                self.queue.requeue(popped)
                raise
            finally:
                self._counters["device_assembled"] += (
                    engine.DC_STATS["device_assembled"] - assembled_before
                )
        return out

    # ----------------------------------------------------------- sessions
    def session(self, **opts) -> "SolveSession":
        """Open a multi-round ticket kind on this service.

        ``opts`` are :class:`SolveSession` options — the per-round
        submit options (``method`` / ``opamp`` / ``nonideal`` / ...)
        plus ``priority`` and ``round_deadline_s``.  See the module
        docstring's *Serving iterative workloads* section.
        """
        return SolveSession(self, **opts)

    # ------------------------------------------------------------- stats
    @property
    def stats(self) -> dict[str, Any]:
        """Service counters: per-bucket fills, the pad-overhead model,
        and the overlap decomposition.

        ``pad_overhead`` is the dense-work ratio
        ``sum((systems + fill_slots) * n_pad^2) / sum(n^2)``: assembly
        and DC-solve cost scale with the *padded* size, over every
        dispatched slot including the repeat-fills — the full price
        paid for shape-stable pipelines.  ``host_build_s`` /
        ``device_wait_s`` / ``settle_finish_s`` / ``unpack_s``
        decompose ``wall_s``: ``device_wait_s`` is the DC-phase device
        time the overlapped host phases could not hide, and
        ``settle_finish_s`` the deferred finish phases (settle sweep +
        fallback) run after their stream slot was released.
        The four, and ``wall_s``, are the totals of the spans
        ``serve.dispatch`` / ``serve.harvest`` / ``serve.finish`` /
        ``serve.unpack`` and ``serve.drain``.  ``spans`` holds every
        span this service's drains opened, those of ``core/`` included
        (``core.build_nets``, ``core.assemble``, ``core.transfer``,
        ``core.settle_poll`` whose count is the settle polls, ...; see
        the module docstring's *Phases and spans*), as
        ``{name: {"count": int, "s": float}}``.  ``queue_wait_s`` sums
        each dispatched ticket's wait from ``submit`` to the start of
        its first ``serve.dispatch``.  ``neg_cells`` /
        ``cross_branches`` / ``settle_steps_swept`` are the circuit
        counters of the module docstring.  ``device_assembled`` counts
        the analog DC micro-batches whose operator was built on the
        device (:func:`repro.core.engine.assemble_batch_device`) during
        this service's drains: every analog DC dispatch, the inner
        passes of graded refinement included.  ``pattern_derivations``
        counts ``pattern_union`` calls per bucket (1 proves the cache
        served every later micro-batch on every stream).

        The fault-tolerance story rides along: ``retries`` /
        ``bisections`` (non-terminal recovery work), ``shed`` /
        ``deadline_expired`` (admission-time rejections),
        ``quarantines`` / ``requeued_on_quarantine`` + the ``breaker``
        snapshot (stream health), ``device_micro_batches`` (dispatches
        per stream, in ``devices`` order), ``fallbacks`` (per-system
        analog→digital re-solves on clean dispatches — the genuine
        numerics signal) vs ``fallbacks_injected`` (re-solves inside
        micro-batches whose dispatch carried injected corruption,
        attributed to the chaos injector), terminal ``errors`` per
        kind, and ``fault_injections`` when a chaos injector is armed.

        With graded recovery enabled (``refine=``), the precision
        contract rides along too: ``precision_paths`` counts delivered
        solutions per route (``analog`` — the raw solve already met the
        refinement tol; ``refined`` — iterative refinement converged;
        ``fallback`` — refinement stalled and a digital re-solve
        delivered; ``unrefined`` never appears here, it is a terminal
        error kind) and ``refine_iters_total`` the inner analog solves
        consumed — the hardware-quality readout of the stream.
        """
        per_bucket = {}
        pad_sq = 0.0
        total = fills = 0
        for (n_pad, sig), pipe in self._pipelines.items():
            base = key = f"n{n_pad}/{sig.method}"
            suffix = 2
            while key in per_bucket:     # same (n_pad, method), other sig
                key = f"{base}#{suffix}"
                suffix += 1
            per_bucket[key] = {
                "micro_batches": pipe.micro_batches,
                "systems": pipe.systems,
                "fill_slots": pipe.fill_slots,
                "pattern_derivations": pipe.pattern_derivations,
                "pattern_rebuilds": pipe.pattern_rebuilds,
            }
            total += pipe.systems
            fills += pipe.fill_slots
            pad_sq += (pipe.systems + pipe.fill_slots) * float(n_pad) ** 2
        real_sq = self._real_sq
        c = self._counters
        spans = self._spans
        return {
            "requests": total,
            "fill_slots": fills,
            "buckets": per_bucket,
            "pad_overhead": pad_sq / real_sq if real_sq else 1.0,
            "wall_s": spans.seconds("serve.drain"),
            "host_build_s": spans.seconds("serve.dispatch"),
            "device_wait_s": spans.seconds("serve.harvest"),
            "settle_finish_s": spans.seconds("serve.finish"),
            "unpack_s": spans.seconds("serve.unpack"),
            "queue_wait_s": self._queue_wait_s,
            "spans": spans.snapshot(),
            "neg_cells": c["neg_cells"],
            "device_assembled": c["device_assembled"],
            "cross_branches": c["cross_branches"],
            "settle_steps_swept": c["settle_steps_swept"],
            "devices": len(self.devices),
            "device_micro_batches": list(self._device_batches),
            "inflight_per_device": self.inflight_per_device,
            "batch_slots": self.batch_slots,
            "retries": c["retries"],
            "bisections": c["bisections"],
            "shed": c["shed"],
            "deadline_expired": c["deadline_expired"],
            "fallbacks": c["fallbacks"],
            "fallbacks_injected": c["fallbacks_injected"],
            "refine_iters_total": c["refine_iters_total"],
            "precision_paths": dict(c["precision_paths"]),
            "quarantines": c["quarantines"],
            "requeued_on_quarantine": c["requeued_on_quarantine"],
            "errors": dict(c["errors"]),
            "fault_injections": (
                0 if self.fault_injector is None
                else self.fault_injector.stats()["total_injected"]
            ),
            "breaker": self.breaker.stats(),
        }


class SessionRoundError(RuntimeError):
    """One or more tickets of a session round failed *terminally*.

    Raised by :meth:`SolveSession.solve_round` after the round's drain
    completed — every ticket was answered exactly once; the ones that
    exhausted the service's retry/fallback machinery carry a
    :class:`~repro.serving.faults.SolveError` instead of a solution.
    ``errors`` maps the round's batch index to that error; ``x`` holds
    the round's solution array with the healthy systems filled in (the
    failed rows are NaN), so a caller that can tolerate partial rounds
    may recover without resubmitting the whole round.
    """

    def __init__(self, round_index: int, errors: dict, x: np.ndarray):
        kinds = sorted({e.kind for e in errors.values()})
        super().__init__(
            f"session round {round_index}: {len(errors)} ticket(s) failed "
            f"terminally ({', '.join(kinds)})"
        )
        self.round_index = round_index
        self.errors = errors
        self.x = x


class SolveSession:
    """Multi-round ticket kind: one iterative client's stream of solve
    rounds through a :class:`SolveService`.

    A round is a batch of B systems that must *all* resolve before the
    client can form its next round (a Newton/SQP iteration's linearized
    systems — see :mod:`repro.optim.batched_newton`).  Each
    :meth:`solve_round` call submits the round as ordinary tickets
    (shared ``priority``, one fresh absolute deadline from
    ``round_deadline_s``) into the service's bucketed pipelines and
    drains; pattern + jit reuse across rounds is inherited from the
    service's persistent per-bucket pipelines, and the PR-7 failure
    machinery (retry budgets, bisection, quarantine, fallback,
    deadlines) applies per round.  The object satisfies the
    ``rounds=`` executor protocol of
    :func:`repro.optim.batched_newton.newton_batch`:
    ``solve_round(a, b) -> x`` plus the ``solve_rounds`` /
    ``pattern_derivations`` counters.

    Construction options (beyond the service) are the per-round submit
    options: ``method``, ``opamp``, ``nonideal``, ``d_policy``,
    ``beta``, ``alpha``, ``tol``, ``max_iter`` — forwarded verbatim to
    :meth:`SolveService.submit` — plus ``priority`` (admission class of
    every round ticket), ``round_deadline_s`` (per-round latency
    budget, enforced as an absolute deadline stamped at round
    submission), and ``warm_start``.

    ``warm_start=True`` reuses the previous round's solutions as the
    next round's settle warm start (``x0`` per ticket): a Newton
    client's consecutive linearized systems differ by one damped step,
    so the previous DC state already sits near the new fixed point and
    the amplitude-aware chunk schedule
    (:func:`repro.core.spectral.amplitude_settle_steps`) charges only
    the remaining error amplitude.  Rounds must keep the same ``(B,
    n)`` shape to chain (a shape change just cold-starts that round),
    and a round with terminal failures never seeds the next (NaN rows
    must not poison a sweep).  ``settle_steps_by_round`` records the
    per-round mean settle steps (None for rounds without settle-step
    metrics) — the saved-sweep-steps measurement; ``warm_submits``
    counts tickets that actually carried an ``x0``.
    """

    def __init__(
        self,
        service: SolveService,
        *,
        priority: int = 0,
        round_deadline_s: float | None = None,
        warm_start: bool = False,
        **submit_opts,
    ):
        self.service = service
        self.priority = int(priority)
        self.round_deadline_s = (
            None if round_deadline_s is None else float(round_deadline_s)
        )
        self.warm_start = bool(warm_start)
        self.submit_opts = submit_opts
        self.rounds = 0              # rounds completed (or failed terminally)
        self.systems = 0             # tickets submitted across rounds
        self.warm_submits = 0        # tickets submitted with a warm start
        # per-round mean settle steps (None when the round carried no
        # settle-step metrics) — the warm-start savings measurement
        self.settle_steps_by_round: list[float | None] = []
        self._last_x: np.ndarray | None = None
        # interleaved one-shot traffic answered by this session's drains
        self.other_results: dict[int, SolveResult | SolveError] = {}

    # the batched_newton rounds-protocol counters
    @property
    def solve_rounds(self) -> int:
        return self.rounds

    @property
    def pattern_derivations(self) -> int:
        """Stamp patterns derived by the service since it started —
        across *all* its buckets, so with the session as the only
        analog client this is the session's own count (1 per
        iteration-invariant sparsity class proves cross-round reuse).
        """
        return sum(
            p.pattern_derivations for p in self.service._pipelines.values()
        )

    def solve_round(self, a, b) -> np.ndarray:
        """Submit one round of ``(B,)`` systems and block for all B.

        ``a`` is (B, n, n), ``b`` (B, n); returns the (B, n) solutions
        in submission order.  Raises :class:`SessionRoundError` if any
        ticket of the round failed terminally (the drain still answered
        every ticket exactly once — partial solutions ride on the
        error).
        """
        a = np.asarray(a, dtype=np.float64)
        b = np.asarray(b, dtype=np.float64)
        if a.ndim != 3 or b.ndim != 2 or a.shape[:2] != b.shape:
            raise ValueError(
                f"expected (B, n, n) and (B, n); got {a.shape}, {b.shape}"
            )
        deadline = (
            None if self.round_deadline_s is None
            else self.service.now() + self.round_deadline_s
        )
        warm = (
            self.warm_start
            and self._last_x is not None
            and self._last_x.shape == b.shape
        )
        rids = [
            self.service.submit(
                a[k], b[k],
                x0=self._last_x[k] if warm else None,
                priority=self.priority, deadline=deadline,
                **self.submit_opts,
            )
            for k in range(a.shape[0])
        ]
        if warm:
            self.warm_submits += len(rids)
        out = self.service.drain()
        x = np.full_like(b, np.nan)
        errors: dict[int, SolveError] = {}
        steps: list[float] = []
        for k, rid in enumerate(rids):
            res = out.pop(rid)
            if isinstance(res, SolveError):
                errors[k] = res
            else:
                x[k] = res.x
                s = res.info.get("settle_steps")
                if s is not None:
                    steps.append(float(s))
        self.settle_steps_by_round.append(
            float(np.mean(steps)) if steps else None
        )
        # answers for tickets other clients queued on the same service
        self.other_results.update(out)
        index = self.rounds
        self.rounds += 1
        self.systems += len(rids)
        if errors:
            # a partial round never seeds a warm start: NaN rows would
            # poison the next sweep's initial state
            self._last_x = None
            raise SessionRoundError(index, errors, x)
        if self.warm_start:
            self._last_x = x
        return x

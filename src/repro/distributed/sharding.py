"""Logical-axis sharding rules (FSDP x TP x EP x pod-DP).

Model code annotates activations/parameters with *logical* axis names;
the rules map them to mesh axes.  The same model definition therefore
runs on the single-pod (data, model) mesh, the multi-pod
(pod, data, model) mesh, or a single device (rules empty -> no-op).

Parameter placement policy (see DESIGN.md §7):

* ``embed``   (d_model rows of weight matrices)   -> "data"  (= FSDP:
  parameters and optimizer state sharded over the data axis, gathered
  per layer inside the scan by XLA SPMD)
* ``heads`` / ``ff`` / ``vocab`` / ``inner``      -> "model" (= TP)
* ``expert``  -> "model" when the config selects EP, else unsharded
  (the expert's ff dim carries the TP split instead)
* ``batch``   -> ("pod", "data") on the multi-pod mesh (pure DP across
  pods: gradients all-reduce over pod+data)
* sequence/time axes unsharded by default (SP variants opt in via
  ``seq`` -> "model" rules on long-prefill shapes)
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Mapping, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P


LOGICAL_RULES_SINGLE_POD: dict[str, object] = {
    "batch": "data",
    "embed": "data",       # FSDP shard dim
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "inner": "model",      # mamba d_inner
    "expert": None,        # flipped to "model" by EP configs
    "moe_grp": "data",     # hierarchical MoE dispatch groups
    "seq": None,
    "state": None,
}

LOGICAL_RULES_MULTI_POD: dict[str, object] = {
    **LOGICAL_RULES_SINGLE_POD,
    "batch": ("pod", "data"),
}


class _Ctx(threading.local):
    def __init__(self):
        self.rules: Optional[Mapping[str, object]] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_rules(rules: Mapping[str, object]):
    prev = _CTX.rules
    _CTX.rules = rules
    try:
        yield
    finally:
        _CTX.rules = prev


def active_rules() -> Optional[Mapping[str, object]]:
    return _CTX.rules


def logical_spec(
    axes: Sequence[Optional[str]], rules: Optional[Mapping[str, object]] = None
) -> P:
    rules = rules if rules is not None else _CTX.rules
    if rules is None:
        return P()
    return P(*[rules.get(a) if a is not None else None for a in axes])


def logical_constraint(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint by logical axes; no-op without rules."""
    if _CTX.rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(axes))


def boundary_pin(x, axes: Sequence[Optional[str]]):
    """Constraint applied ONLY when the attention layout differs from
    the default batch layout (the yi/internvl/whisper lever).  For
    heads-mode archs the attn layout equals the batch layout and the
    extra pin measurably hurts (8-18% on the memory term), so skip it."""
    rules = _CTX.rules
    if rules is None:
        return x
    if rules.get("attn_batch", rules.get("batch")) == rules.get("batch"):
        return x
    return jax.lax.with_sharding_constraint(x, logical_spec(axes))


def param_specs(logical_tree, rules: Mapping[str, object]):
    """Map a pytree of logical-axis tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_spec(axes, rules),
        logical_tree,
        is_leaf=lambda v: isinstance(v, tuple),
    )


# ---------------------------------------------------------------------------
# Solver-side mesh rules (the solve service's system-batch data parallelism)
# ---------------------------------------------------------------------------
#
# The solver workload has exactly one shardable axis: the *system batch*
# (independent SPD systems streamed through `solve_batch`).  Matrix rows
# and columns stay unsharded — paper-scale operators fit on one device,
# and the per-system LU/Cholesky factorizations do not partition.  The
# rules therefore map the logical "sysbatch" axis to the mesh and pin
# everything else replicated, mirroring how the model side treats
# "batch".
#
# Two placement modes share this mesh:
#
# * `shard_system_batch` splits ONE micro-batch's batch axis over every
#   device (GSPMD NamedSharding).  Each solve then pays a cross-device
#   dispatch + gather on the request path — measured on a CPU with
#   forced host devices as an *inverted* device-scaling curve.  Kept for direct
#   `solve_batch(mesh=...)` callers with big standalone batches.
# * `stream_devices` (the serving v2 path) returns the mesh's device
#   list so the solve service can go data-parallel ACROSS micro-batches
#   instead: each micro-batch lands whole on one device (round-robin),
#   devices never exchange a byte, and JAX async dispatch overlaps one
#   stream's device solve with the next micro-batch's host-side build.

SOLVER_BATCH_AXIS = "sysbatch"

SOLVER_RULES: dict[str, object] = {
    "sysbatch": SOLVER_BATCH_AXIS,   # independent systems -> devices
    "row": None,                     # operator rows stay on-device
    "col": None,
    "state": None,                   # circuit state vectors unsharded
}


def solver_mesh(n_devices: Optional[int] = None, devices=None):
    """1-d solver mesh over the system-batch axis.

    Works on ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    placeholder devices too.  ``n_devices=None`` uses every visible
    device.
    """
    from repro.launch.mesh import _make_mesh

    devs = list(jax.devices() if devices is None else devices)
    if n_devices is not None:
        if n_devices > len(devs):
            raise RuntimeError(
                f"solver mesh wants {n_devices} devices, have {len(devs)}"
            )
        devs = devs[:n_devices]
    return _make_mesh((len(devs),), (SOLVER_BATCH_AXIS,), devs)


def stream_devices(mesh=None, devices=None, n_devices: Optional[int] = None):
    """Ordered device list for per-device solve streams (serving v2).

    Accepts a 1-d solver mesh (its device order), an explicit device
    list, or a device count (the first N visible devices); with none of
    the three, the default device alone.  The solve service assigns
    whole micro-batches to these devices round-robin — per-micro-batch
    data parallelism with no collectives — instead of sharding one
    micro-batch's batch axis via :func:`shard_system_batch`.
    """
    if devices is not None:
        return list(devices)
    if mesh is not None:
        return [d for d in mesh.devices.flat]
    devs = list(jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise RuntimeError(
                f"stream wants {n_devices} devices, have {len(devs)}"
            )
        devs = devs[:n_devices]
    return devs


@dataclasses.dataclass
class _StreamState:
    """Breaker state of one device stream."""

    state: str = "closed"            # closed | open | half_open
    consecutive_failures: int = 0
    backoff_s: float = 0.0           # current open-interval length
    open_until: float = 0.0          # monotonic time the backoff elapses


class StreamBreaker:
    """Per-device-stream circuit breaker for the solve service.

    Each stream (an index into the service's round-robin device list)
    is ``closed`` (serving), ``open`` (quarantined: consecutive
    failures reached ``threshold``; no dispatches until its backoff
    elapses) or ``half_open`` (one probe micro-batch in flight).  A
    successful probe closes the stream and resets its backoff; a
    failed probe re-opens it with the backoff doubled (capped at
    ``backoff_max_s``) — exponential-backoff half-open probing, so a
    flapping device costs a geometrically shrinking share of traffic
    while a recovered one rejoins after a single probe.

    The service owns the policy around the breaker: on a trip it
    re-queues the quarantined stream's in-flight tickets (at original
    admission rank, blameless — no retry budget consumed) onto the
    healthy streams, and when *every* stream is open with work still
    queued it calls :meth:`force_probe` so the service degrades to
    probing instead of deadlocking.
    """

    def __init__(
        self,
        n_streams: int,
        *,
        threshold: int = 3,
        backoff_s: float = 0.25,
        backoff_max_s: float = 30.0,
        clock=time.monotonic,
    ):
        if n_streams < 1:
            raise ValueError("need at least one stream")
        if threshold < 1:
            raise ValueError("threshold must be >= 1")
        self.threshold = int(threshold)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.clock = clock
        self._streams = [_StreamState() for _ in range(n_streams)]
        self.trips = 0               # closed/half_open -> open transitions
        self.probes = 0              # open -> half_open transitions
        self.restores = 0            # half_open -> closed transitions
        # state transitions are read-modify-write on per-stream state
        # reachable from every stream's host thread; acquire/record_*
        # must be atomic or two threads can both win the same probe slot
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._streams)

    def state(self, dev: int) -> str:
        return self._streams[dev].state

    def acquire(self, dev: int) -> bool:
        """May stream ``dev`` take a dispatch right now?

        ``closed`` streams always may.  An ``open`` stream whose
        backoff has elapsed transitions to ``half_open`` and accepts
        exactly this one dispatch as its probe; while the probe is in
        flight further acquires are refused.
        """
        with self._lock:
            s = self._streams[dev]
            if s.state == "closed":
                return True
            if s.state == "open" and self.clock() >= s.open_until:
                s.state = "half_open"
                self.probes += 1
                return True
            return False

    def release(self, dev: int) -> None:
        """Hand back an acquired probe slot without a device verdict.

        Called when a dispatch acquired via :meth:`acquire` never
        reached the device (the *host* build raised): the probe said
        nothing about the stream's health, so a ``half_open`` stream
        returns to ``open`` with its backoff already elapsed — the
        next acquire probes again immediately.
        """
        with self._lock:
            s = self._streams[dev]
            if s.state == "half_open":
                s.state = "open"
                s.open_until = self.clock()

    def record_success(self, dev: int) -> None:
        with self._lock:
            s = self._streams[dev]
            if s.state == "half_open":
                s.state = "closed"
                self.restores += 1
            s.consecutive_failures = 0
            s.backoff_s = 0.0

    def record_failure(self, dev: int) -> bool:
        """Count one device-side failure; returns True when this call
        trips the stream open (caller quarantines its in-flights)."""
        with self._lock:
            s = self._streams[dev]
            s.consecutive_failures += 1
            if s.state == "half_open":
                # failed probe: back off twice as long
                s.state = "open"
                s.backoff_s = min(
                    max(s.backoff_s, self.backoff_s) * 2.0, self.backoff_max_s
                )
                s.open_until = self.clock() + s.backoff_s
                self.trips += 1
                return True
            if s.state == "closed" and s.consecutive_failures >= self.threshold:
                s.state = "open"
                s.backoff_s = self.backoff_s
                s.open_until = self.clock() + s.backoff_s
                self.trips += 1
                return True
            return False

    def force_probe(self) -> int:
        """Expire the soonest-recovering open stream's backoff now.

        Called when every stream is quarantined but work remains: the
        service must keep probing rather than deadlock — "degrade to
        fewer streams", never to zero.  Returns the stream index.
        """
        with self._lock:
            open_streams = [
                i for i, s in enumerate(self._streams) if s.state == "open"
            ]
            if not open_streams:
                raise RuntimeError("force_probe with no open stream")
            dev = min(open_streams, key=lambda i: self._streams[i].open_until)
            self._streams[dev].open_until = self.clock()
            return dev

    def stats(self) -> dict:
        return {
            "states": [s.state for s in self._streams],
            "trips": self.trips,
            "probes": self.probes,
            "restores": self.restores,
        }


def system_batch_sharding(mesh, ndim: int):
    """``NamedSharding`` splitting axis 0 (the system batch) over ``mesh``."""
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, P(SOLVER_BATCH_AXIS, *([None] * (ndim - 1))))


def shard_system_batch(*arrays, mesh):
    """Place each array with its batch axis split over the solver mesh.

    The batch size must divide evenly — the solve service pads every
    micro-batch to a multiple of the device count before dispatch, and
    direct callers get a clear error instead of a GSPMD shape failure.
    """
    n_dev = mesh.devices.size
    out = []
    for x in arrays:
        if x.shape[0] % n_dev:
            raise ValueError(
                f"batch of {x.shape[0]} does not divide over {n_dev} "
                f"devices; pad the batch (the solve service does this "
                f"automatically)"
            )
        out.append(jax.device_put(x, system_batch_sharding(mesh, x.ndim)))
    return tuple(out) if len(out) != 1 else out[0]

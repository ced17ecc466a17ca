"""Batched circuit physics engine — stamp patterns, vectorized assembly,
vmapped DC solves and batched transient settling.

The paper's complexity studies sweep ~1200 SPD/SDD systems through
operating-point and transient analyses.  Per-system Python assembly and
one-at-a-time dense solves dominate that wall-clock, so this module
factors the physics into

* a **stamp pattern** (:class:`StampPattern`) — the static sparsity
  structure of the LTI state-space for a given ``(design, n)``: which
  negative-resistance cell *slots* exist, where each buffer/amp state
  lives, and the scatter indices every stamp writes to.  Patterns are
  cached (:func:`pattern_union` / :func:`pattern_of`) and reused across
  a batch: for the proposed design the pattern depends only on
  ``(n, design)`` because cells live strictly on the ``(i, n+i)`` pairs.
* **batched assembly** — per-system conductance values are scattered
  onto the shared pattern; no per-cell Python loops.  A slot that a
  given system does not populate stamps ``w = 0``: the amp dynamics
  remain (a stable, decoupled subsystem) but inject no current and load
  no node capacitance, so the node physics match the per-system
  assembly exactly.  Two products share one value-gathering pass:

  - :func:`assemble_batch` — the dense ``(B, nz, nz)`` operators
    (vectorized ``np.add.at``), needed by the direct DC solve and the
    exact eig path;
  - :func:`assemble_batch_ell` — the **matrix-free path**: a jitted
    ``jnp`` scatter builds per-row ``(indices, weights)`` ELL arrays
    directly on device (bounded row degree from the pattern: 1 diagonal
    + C cell couplings + branch degree, amp rows <= 4 stamps).  Nothing
    of size ``(B, nz, nz)`` is materialized unless a caller asks
    (:meth:`EllBatchedStateSpace.to_dense`).
* a **batched operating point** (:func:`dc_solve_batch`) — one f32 LU
  factorization per system refined to fp64 on the device (TPUs have no
  f64 LU; see :data:`DC_REFINE_TOL`), with the same tiny-leakage host
  fallback the single path uses for singular supports.
* a **batched transient path** (:func:`transient_batch`) — exact modal
  solution via stacked eigendecomposition for small ``nz`` (the
  reference), and :func:`euler_settle_batch`, a forward-Euler sweep
  driven by the batch-aware Pallas kernels with their fused
  settling-check (max ``|M z + c|``) reduction for large ``nz``
  (``method="auto"`` picks by state count).  The sweep dispatches
  between the dense and the ELL-SpMV kernels by fill ratio and VMEM
  fit (:func:`repro.kernels.ops.sweep_backend`); ``method="spectral"``
  replaces the O(nz^3) eig estimate with the matrix-free spectral
  estimator (:mod:`repro.core.spectral`: power-iteration rate, Krylov
  Ritz modes for the abscissa-aware ``dt_policy="spectral"`` step
  rule, and propagator-filtered deflated subspace iteration for the
  slow mode + restricted numerical-range stability certificate), whose
  predictions also size the euler sweep's chunk schedule.

x64 policy: assembly and the exact paths run float64 end to end (the
circuit spans 1e-12 F against 1e6 rad/s rates); only the Pallas Euler
sweep drops to float32, which the 1 % settling tolerance absorbs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import span
from repro.core.network import Netlist
from repro.core.specs import OpAmpSpec, AD712

# nz above which transient_batch(method="auto") switches from the exact
# eigendecomposition (O(nz^3) per system, but exact settling times) to
# the Pallas forward-Euler sweep.
EIG_STATE_LIMIT = 2048

# bf16 sweeps settle to the *rounded* operator's equilibrium, which sits
# O(kappa * eps_bf16) from the f64 reference — on the paper protocol's
# conditioning (eigenvalues in [10, 1000] uS, kappa <= 1e2) that is up
# to ~12% of the solution scale.  The bf16 settle verdict therefore
# certifies arrival within this per-system band (relative to
# max |x_ref|); recovering fp64 from there is the refinement layer's
# job (repro.core.refine), not the sweep's.
BF16_SETTLE_RTOL = 0.15


# ---------------------------------------------------------------------------
# Stamp patterns
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class StampPattern:
    """Static state-space structure for one ``(design, n)`` family.

    State layout (identical to the historical per-cell assembly order):
    ``[nodes | per pair slot: buf1, buf2, a1_int, (a1_out), a2_int,
    (a2_out) | per ground slot: a_int, (a_out)]``.  Pair slots are
    lexicographically ordered by ``(i, j)``; ground slots by node.  Amps
    are numbered pair slots first (amp1 then amp2 per slot), then ground
    slots — the ordering the offset draws rely on.

    ``eq=False`` + the explicit ``__eq__``/``__hash__`` below make the
    pattern a stable cache key: the dataclass-generated ``__eq__``
    compares ndarray fields with ``==`` (ambiguous truth value) and the
    generated ``__hash__`` raises TypeError, so equal-but-distinct
    patterns used as jit static args or dict keys would either crash or
    retrigger lowering.  Identity is defined by the primary fields only
    — the derived index arrays are a pure function of them.
    """

    design: str
    n_nodes: int
    n_unknowns: int
    pair_i: np.ndarray          # (P,) near node of each pair-cell slot
    pair_j: np.ndarray          # (P,) far node
    gcell_i: np.ndarray         # (G,) node of each ground-cell slot
    states_per_amp: int         # 2 with a second pole, else 1
    buffers: bool

    # derived state indices (filled by the factory)
    buf1_idx: np.ndarray = dataclasses.field(default=None, repr=False)
    buf2_idx: np.ndarray = dataclasses.field(default=None, repr=False)
    a1_int: np.ndarray = dataclasses.field(default=None, repr=False)
    a1_out: np.ndarray = dataclasses.field(default=None, repr=False)
    a2_int: np.ndarray = dataclasses.field(default=None, repr=False)
    a2_out: np.ndarray = dataclasses.field(default=None, repr=False)
    g_int: np.ndarray = dataclasses.field(default=None, repr=False)
    g_out: np.ndarray = dataclasses.field(default=None, repr=False)
    amp_int_index: np.ndarray = dataclasses.field(default=None, repr=False)
    amp_out_index: np.ndarray = dataclasses.field(default=None, repr=False)
    n_states: int = 0

    def _identity(self) -> tuple:
        return (
            self.design, self.n_nodes, self.n_unknowns,
            self.states_per_amp, self.buffers,
        )

    def __eq__(self, other) -> bool:
        if other is self:
            return True
        if not isinstance(other, StampPattern):
            return NotImplemented
        return (
            self._identity() == other._identity()
            and np.array_equal(self.pair_i, other.pair_i)
            and np.array_equal(self.pair_j, other.pair_j)
            and np.array_equal(self.gcell_i, other.gcell_i)
        )

    def __hash__(self) -> int:
        h = getattr(self, "_hash_cache", None)
        if h is None:
            h = hash(self._identity() + (
                self.pair_i.tobytes(), self.pair_j.tobytes(),
                self.gcell_i.tobytes(),
            ))
            object.__setattr__(self, "_hash_cache", h)
        return h

    @property
    def n_pair_slots(self) -> int:
        return int(self.pair_i.shape[0])

    @property
    def n_ground_slots(self) -> int:
        return int(self.gcell_i.shape[0])

    @property
    def n_amp_slots(self) -> int:
        return 2 * self.n_pair_slots + self.n_ground_slots

    def pair_keys(self) -> np.ndarray:
        """Sorted encoding of the pair slots, for slot lookup."""
        return self.pair_i * self.n_nodes + self.pair_j


def _build_pattern(
    design: str,
    n_nodes: int,
    n_unknowns: int,
    pair_i: np.ndarray,
    pair_j: np.ndarray,
    gcell_i: np.ndarray,
    states_per_amp: int,
    buffers: bool,
) -> StampPattern:
    p = pair_i.shape[0]
    g = gcell_i.shape[0]
    spa = states_per_amp
    n_buf = 2 if buffers else 0
    per_pair = n_buf + 2 * spa

    pair_base = n_nodes + np.arange(p, dtype=np.int64) * per_pair
    if buffers:
        buf1 = pair_base
        buf2 = pair_base + 1
    else:
        # ideal buffers: the amp divider reads the far node directly
        buf1 = pair_j.astype(np.int64)
        buf2 = pair_i.astype(np.int64)
    a1_int = pair_base + n_buf
    a1_out = a1_int + 1 if spa == 2 else a1_int
    a2_int = pair_base + n_buf + spa
    a2_out = a2_int + 1 if spa == 2 else a2_int

    g_base = n_nodes + p * per_pair + np.arange(g, dtype=np.int64) * spa
    g_int = g_base
    g_out = g_base + 1 if spa == 2 else g_base
    n_states = n_nodes + p * per_pair + g * spa

    amp_int = np.concatenate(
        [np.stack([a1_int, a2_int], axis=1).reshape(-1), g_int]
    )
    amp_out = np.concatenate(
        [np.stack([a1_out, a2_out], axis=1).reshape(-1), g_out]
    )
    return StampPattern(
        design=design,
        n_nodes=n_nodes,
        n_unknowns=n_unknowns,
        pair_i=pair_i.astype(np.int64),
        pair_j=pair_j.astype(np.int64),
        gcell_i=gcell_i.astype(np.int64),
        states_per_amp=spa,
        buffers=buffers,
        buf1_idx=buf1,
        buf2_idx=buf2,
        a1_int=a1_int,
        a1_out=a1_out,
        a2_int=a2_int,
        a2_out=a2_out,
        g_int=g_int,
        g_out=g_out,
        amp_int_index=amp_int,
        amp_out_index=amp_out,
        n_states=int(n_states),
    )


_PATTERN_CACHE: dict[tuple, StampPattern] = {}
# Proposed-design patterns are normalized per (n, design) and reused
# forever, but preliminary-design patterns are keyed by the exact
# (data-dependent) cell positions — bound the cache so paper-scale
# sweeps of random systems do not grow memory without reuse.
_PATTERN_CACHE_MAX = 512


def _cached_pattern(
    design, n_nodes, n_unknowns, pair_i, pair_j, gcell_i, spa, buffers
) -> StampPattern:
    key = (
        design,
        n_nodes,
        n_unknowns,
        spa,
        buffers,
        pair_i.tobytes(),
        pair_j.tobytes(),
        gcell_i.tobytes(),
    )
    pat = _PATTERN_CACHE.get(key)
    if pat is None:
        pat = _build_pattern(
            design, n_nodes, n_unknowns, pair_i, pair_j, gcell_i, spa, buffers
        )
        while len(_PATTERN_CACHE) >= _PATTERN_CACHE_MAX:
            _PATTERN_CACHE.pop(next(iter(_PATTERN_CACHE)))   # FIFO evict
        _PATTERN_CACHE[key] = pat
    else:
        # LRU refresh: move the hit to the back of the eviction order
        _PATTERN_CACHE.pop(key)
        _PATTERN_CACHE[key] = pat
    return pat


def pattern_of(
    net: Netlist, opamp: OpAmpSpec = AD712, *, buffers: bool = True
) -> StampPattern:
    """Exact pattern of one netlist (its own cells as the slot set)."""
    pair = net.cell_j >= 0
    return _cached_pattern(
        net.design,
        net.n_nodes,
        net.n_unknowns,
        net.cell_i[pair],
        net.cell_j[pair],
        net.cell_i[~pair],
        2 if opamp.p2_hz > 0 else 1,
        buffers,
    )


def pattern_union(
    nets: list[Netlist], opamp: OpAmpSpec = AD712, *, buffers: bool = True
) -> StampPattern:
    """Shared pattern covering every netlist in the batch.

    For the proposed 2n design, cells can only sit on the ``(i, n+i)``
    pairs, so the slot set is normalized to *all* n pairs — the cached
    pattern depends only on ``(n, design)`` and is reused across any
    batch of that family.  For the preliminary design the slot set is
    the union of the batch's actual cell positions.
    """
    first = nets[0]
    for net in nets[1:]:
        if (net.design in ("proposed", "passive")) != (
            first.design in ("proposed", "passive")
        ) or net.n_nodes != first.n_nodes or net.n_unknowns != first.n_unknowns:
            raise ValueError("batch mixes incompatible netlists")

    spa = 2 if opamp.p2_hz > 0 else 1
    n = first.n_unknowns
    if first.design in ("proposed", "passive"):
        idx = np.arange(n, dtype=np.int64)
        pair_i, pair_j = idx, idx + n
        gset = np.unique(
            np.concatenate(
                [net.cell_i[net.cell_j < 0] for net in nets]
            ).astype(np.int64)
        )
        return _cached_pattern(
            "proposed", first.n_nodes, n, pair_i, pair_j, gset, spa, buffers
        )

    keys = np.unique(
        np.concatenate(
            [
                net.cell_i[net.cell_j >= 0] * first.n_nodes
                + net.cell_j[net.cell_j >= 0]
                for net in nets
            ]
        ).astype(np.int64)
    )
    pair_i = keys // first.n_nodes
    pair_j = keys % first.n_nodes
    gset = np.unique(
        np.concatenate([net.cell_i[net.cell_j < 0] for net in nets]).astype(
            np.int64
        )
    )
    return _cached_pattern(
        first.design, first.n_nodes, n, pair_i, pair_j, gset, spa, buffers
    )


def pattern_covers(pat: StampPattern, nets: list[Netlist]) -> bool:
    """Whether every cell of every netlist lands on a slot of ``pat``.

    The solve service uses this to decide if its bucket-cached pattern
    can be reused for a new micro-batch (cheap set membership — no
    assembly, no exceptions as control flow).
    """
    pair_keys = pat.pair_keys()
    for net in nets:
        if net.n_nodes != pat.n_nodes or net.n_unknowns != pat.n_unknowns:
            return False
        pair = net.cell_j >= 0
        keys = net.cell_i[pair] * pat.n_nodes + net.cell_j[pair]
        if not np.all(np.isin(keys, pair_keys)):
            return False
        if not np.all(np.isin(net.cell_i[~pair], pat.gcell_i)):
            return False
    return True


def pattern_merge(a: StampPattern, b: StampPattern) -> StampPattern:
    """Smallest cached pattern covering both ``a`` and ``b``.

    Patterns must belong to the same ``(design, n, buffers)`` family;
    the merged slot set is the union of pair and ground slots.  Used by
    the solve service when a later micro-batch stamps a cell its
    bucket's cached pattern does not carry.
    """
    if (
        a.design != b.design
        or a.n_nodes != b.n_nodes
        or a.n_unknowns != b.n_unknowns
        or a.states_per_amp != b.states_per_amp
        or a.buffers != b.buffers
    ):
        raise ValueError("cannot merge patterns from different families")
    keys = np.union1d(a.pair_keys(), b.pair_keys())
    pair_i = keys // a.n_nodes
    pair_j = keys % a.n_nodes
    gset = np.union1d(a.gcell_i, b.gcell_i)
    return _cached_pattern(
        a.design, a.n_nodes, a.n_unknowns, pair_i, pair_j, gset,
        a.states_per_amp, a.buffers,
    )


# ---------------------------------------------------------------------------
# Batched assembly
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchedStateSpace:
    """``dz/dt = M_b z + c_b`` for a batch of B systems on one pattern."""

    m: np.ndarray                # (B, nz, nz) float64
    c: np.ndarray                # (B, nz)
    pattern: StampPattern
    amp_active: np.ndarray       # (B, n_amp_slots) bool — real amps only
    amp_rail: float
    slew: float

    @property
    def batch(self) -> int:
        return self.m.shape[0]

    @property
    def n_states(self) -> int:
        return self.pattern.n_states

    @property
    def n_nodes(self) -> int:
        return self.pattern.n_nodes

    @property
    def n_unknowns(self) -> int:
        return self.pattern.n_unknowns

    @property
    def amp_int_index(self) -> np.ndarray:
        return self.pattern.amp_int_index

    @property
    def amp_out_index(self) -> np.ndarray:
        return self.pattern.amp_out_index


def _slot_positions(pat: StampPattern, net: Netlist) -> tuple[np.ndarray, np.ndarray]:
    """Map a net's cells onto pattern slots (pair slots, ground slots)."""
    pair = net.cell_j >= 0
    keys = net.cell_i[pair] * pat.n_nodes + net.cell_j[pair]
    sp = np.searchsorted(pat.pair_keys(), keys)
    if sp.size and (
        np.any(sp >= pat.n_pair_slots) or np.any(pat.pair_keys()[sp] != keys)
    ):
        raise ValueError("netlist has a cell outside the pattern's slots")
    gi = net.cell_i[~pair]
    sg = np.searchsorted(pat.gcell_i, gi)
    if sg.size and (
        np.any(sg >= pat.n_ground_slots) or np.any(pat.gcell_i[sg] != gi)
    ):
        raise ValueError("netlist has a ground cell outside the pattern")
    return sp, sg


@dataclasses.dataclass
class _BatchValues:
    """Per-system component values gathered onto a shared pattern's slots.

    The host-side product of the per-net loop, shared by the dense and
    the ELL assembly paths — O(B * components) work and memory, never
    O(B * nz^2).
    """

    pair_w: np.ndarray       # (B, P)
    gcell_w: np.ndarray      # (B, G)
    pair_active: np.ndarray  # (B, P) bool
    g_active: np.ndarray     # (B, G) bool
    amp_active: np.ndarray   # (B, n_amp_slots) bool
    v_os_slots: np.ndarray   # (B, n_amp_slots)
    br_i: np.ndarray         # (B, n_br_max) int64
    br_j: np.ndarray         # (B, n_br_max) int64
    br_g: np.ndarray         # (B, n_br_max)
    n_br: np.ndarray         # (B,) int64 — valid branch count per system
    ground_g: np.ndarray     # (B, n)
    supply_g: np.ndarray     # (B, n)
    s_cur: np.ndarray        # (B, n)
    elem: np.ndarray         # (B, n)


def _gather_batch_values(
    nets: list[Netlist],
    pat: StampPattern,
    v_os: list[np.ndarray | float | None] | None,
) -> _BatchValues:
    b_count = len(nets)
    n = pat.n_nodes
    p_slots, g_slots = pat.n_pair_slots, pat.n_ground_slots

    pair_w = np.zeros((b_count, p_slots), dtype=np.float64)
    gcell_w = np.zeros((b_count, g_slots), dtype=np.float64)
    pair_active = np.zeros((b_count, p_slots), dtype=bool)
    g_active = np.zeros((b_count, g_slots), dtype=bool)
    amp_active = np.zeros((b_count, pat.n_amp_slots), dtype=bool)
    v_os_slots = np.zeros((b_count, pat.n_amp_slots), dtype=np.float64)

    n_br_max = max((net.n_branches for net in nets), default=0)
    br_i = np.zeros((b_count, n_br_max), dtype=np.int64)
    br_j = np.zeros((b_count, n_br_max), dtype=np.int64)
    br_g = np.zeros((b_count, n_br_max), dtype=np.float64)
    n_br = np.zeros(b_count, dtype=np.int64)

    ground_g = np.zeros((b_count, n), dtype=np.float64)
    supply_g = np.zeros((b_count, n), dtype=np.float64)
    s_cur = np.zeros((b_count, n), dtype=np.float64)
    elem = np.zeros((b_count, n), dtype=np.float64)

    for b, net in enumerate(nets):
        sp, sg = _slot_positions(pat, net)
        pair = net.cell_j >= 0
        pair_w[b, sp] = net.cell_w[pair]
        gcell_w[b, sg] = net.cell_w[~pair]
        pair_active[b, sp] = True
        g_active[b, sg] = True
        amp_active[b, 2 * sp] = True
        amp_active[b, 2 * sp + 1] = True
        amp_active[b, 2 * p_slots + sg] = True

        n_amps_b = net.n_amps
        if v_os is not None and v_os[b] is not None and n_amps_b:
            offs = np.broadcast_to(
                np.asarray(v_os[b], dtype=np.float64), (n_amps_b,)
            )
            amp_pos = np.concatenate(
                [np.stack([2 * sp, 2 * sp + 1], axis=1).reshape(-1),
                 2 * p_slots + sg]
            )
            v_os_slots[b, amp_pos] = offs

        nb = net.n_branches
        br_i[b, :nb] = net.branch_i
        br_j[b, :nb] = net.branch_j
        br_g[b, :nb] = net.branch_g
        n_br[b] = nb
        ground_g[b] = net.ground_g
        supply_g[b] = net.supply_g
        s_cur[b] = net.s
        if net.element_count is not None:
            elem[b] = net.element_count

    return _BatchValues(
        pair_w=pair_w,
        gcell_w=gcell_w,
        pair_active=pair_active,
        g_active=g_active,
        amp_active=amp_active,
        v_os_slots=v_os_slots,
        br_i=br_i,
        br_j=br_j,
        br_g=br_g,
        n_br=n_br,
        ground_g=ground_g,
        supply_g=supply_g,
        s_cur=s_cur,
        elem=elem,
    )


def _check_batch_params(nets: list[Netlist]):
    params = nets[0].params
    for net in nets[1:]:
        if net.params != params:
            raise ValueError("batch mixes CircuitParams")
    return params


@span("core.assemble")
def assemble_batch(
    nets: list[Netlist],
    opamp: OpAmpSpec = AD712,
    *,
    v_os: list[np.ndarray | float | None] | None = None,
    buffers: bool = True,
    pattern: StampPattern | None = None,
) -> BatchedStateSpace:
    """Vectorized *dense* state-space assembly for a batch of netlists.

    ``v_os[b]`` is the per-amp input offset of system ``b`` (scalar or
    one value per *actual* amp, in the net's amp order); ``None`` means
    zero offset everywhere.  Materializes the full ``(B, nz, nz)``
    operator — use :func:`assemble_batch_ell` for the matrix-free path.
    """
    b_count = len(nets)
    pat = pattern_union(nets, opamp, buffers=buffers) if pattern is None else pattern
    params = _check_batch_params(nets)

    n = pat.n_nodes
    nz = pat.n_states
    p_slots, g_slots = pat.n_pair_slots, pat.n_ground_slots
    bidx = np.arange(b_count)[:, None]

    vals = _gather_batch_values(nets, pat, v_os)
    pair_w, gcell_w = vals.pair_w, vals.gcell_w
    pair_active, g_active = vals.pair_active, vals.g_active
    amp_active, v_os_slots = vals.amp_active, vals.v_os_slots
    br_i, br_j, br_g = vals.br_i, vals.br_j, vals.br_g
    ground_g, supply_g = vals.ground_g, vals.supply_g
    s_cur, elem = vals.s_cur, vals.elem

    # ---- node capacitance: wiring + switch + active amp/buffer pins ----
    cap = np.full((b_count, n), params.c_node, dtype=np.float64)
    cap += params.c_switch * elem
    pin = 2.0 * opamp.c_in * pair_active.astype(np.float64)
    np.add.at(cap, (bidx, pat.pair_i[None, :]), pin)
    np.add.at(cap, (bidx, pat.pair_j[None, :]), pin)
    np.add.at(
        cap,
        (bidx, pat.gcell_i[None, :]),
        opamp.c_in * g_active.astype(np.float64),
    )
    inv_c = 1.0 / cap

    # ---- passive stamps (branches + ground legs + supplies) ----
    passive = np.zeros((b_count, n, n), dtype=np.float64)
    np.add.at(passive, (bidx, br_i, br_j), -br_g)
    np.add.at(passive, (bidx, br_j, br_i), -br_g)
    diag = np.zeros((b_count, n), dtype=np.float64)
    np.add.at(diag, (bidx, br_i), br_g)
    np.add.at(diag, (bidx, br_j), br_g)
    diag += ground_g + supply_g
    ar = np.arange(n)
    passive[:, ar, ar] += diag

    m = np.zeros((b_count, nz, nz), dtype=np.float64)
    c_vec = np.zeros((b_count, nz), dtype=np.float64)
    m[:, :n, :n] = -passive * inv_c[:, :, None]
    c_vec[:, :n] = s_cur * inv_c

    # ---- amp/buffer dynamics (constant structure, shared by the batch) ----
    w_u = opamp.omega_u
    w_buf = opamp.omega_u
    p2 = 2.0 * np.pi * opamp.p2_hz if opamp.p2_hz > 0 else 0.0
    inv_a0 = 1.0 / opamp.open_loop_gain
    spa = pat.states_per_amp

    if p_slots:
        pi, pj = pat.pair_i, pat.pair_j
        if buffers:
            m[:, pat.buf1_idx, pj] += w_buf
            m[:, pat.buf1_idx, pat.buf1_idx] += -w_buf
            m[:, pat.buf2_idx, pi] += w_buf
            m[:, pat.buf2_idx, pat.buf2_idx] += -w_buf
        for a_int, a_out, vplus, far in (
            (pat.a1_int, pat.a1_out, pi, pat.buf1_idx),
            (pat.a2_int, pat.a2_out, pj, pat.buf2_idx),
        ):
            m[:, a_int, vplus] += w_u
            m[:, a_int, a_out] += -0.5 * w_u
            m[:, a_int, far] += -0.5 * w_u
            m[:, a_int, a_int] += -w_u * inv_a0
            if spa == 2:
                m[:, a_out, a_int] += p2
                m[:, a_out, a_out] += -p2
        # cell currents into both nodes (w = 0 for inactive slots)
        wi = pair_w * inv_c[bidx, pi[None, :]]
        wj = pair_w * inv_c[bidx, pj[None, :]]
        np.add.at(m, (bidx, pi[None, :], pi[None, :]), -wi)
        np.add.at(m, (bidx, pi[None, :], pat.a1_out[None, :]), wi)
        np.add.at(m, (bidx, pj[None, :], pj[None, :]), -wj)
        np.add.at(m, (bidx, pj[None, :], pat.a2_out[None, :]), wj)

    if g_slots:
        gi = pat.gcell_i
        m[:, pat.g_int, gi] += w_u
        m[:, pat.g_int, pat.g_out] += -0.5 * w_u
        m[:, pat.g_int, pat.g_int] += -w_u * inv_a0
        if spa == 2:
            m[:, pat.g_out, pat.g_int] += p2
            m[:, pat.g_out, pat.g_out] += -p2
        wg = gcell_w * inv_c[bidx, gi[None, :]]
        np.add.at(m, (bidx, gi[None, :], gi[None, :]), -wg)
        np.add.at(m, (bidx, gi[None, :], pat.g_out[None, :]), wg)

    if pat.n_amp_slots:
        c_vec[:, pat.amp_int_index] += w_u * v_os_slots

    return BatchedStateSpace(
        m=m,
        c=c_vec,
        pattern=pat,
        amp_active=amp_active,
        amp_rail=opamp.rail_v,
        slew=opamp.slew_v_per_s,
    )


# ---------------------------------------------------------------------------
# Matrix-free ELL assembly (device-resident, jitted scatter)
# ---------------------------------------------------------------------------
#
# The operator's sparsity is bounded by the stamp pattern: every
# buffer/amp row carries at most four stamps, and a node row carries one
# (accumulated) diagonal entry, one amp-output coupling per cell
# terminal, and one off-diagonal per incident branch.  The ELL slot
# layout per node row is therefore
#
#     [0] diagonal | [1 .. C] cell couplings | [1+C ..] branch stamps
#
# with C the pattern's max cell terminals per node (1 for the proposed
# design) and the branch slots assigned by an in-row cumulative count
# (vectorized argsort/searchsorted, vmapped over the batch).  Only the
# branch slots are data-dependent; everything else is static per
# pattern, so the amp-row block is built once host-side and broadcast.


@dataclasses.dataclass
class EllBatchedStateSpace:
    """``dz/dt = M z + c`` with ``M`` in batched ELL (padded sparse-row)
    form: ``(M z)[b, i] = sum_k weights[b, i, k] * z[b, indices[b, i, k]]``.

    Unused slots carry ``(index 0, weight 0)`` — exact no-ops under the
    gathered row reduction.  Device-resident end to end; the dense
    ``(B, nz, nz)`` operator exists only if a caller asks
    (:meth:`to_dense`).
    """

    indices: jnp.ndarray         # (B, nz, K) int32
    weights: jnp.ndarray         # (B, nz, K) float64
    c: jnp.ndarray               # (B, nz) float64
    pattern: StampPattern
    amp_active: np.ndarray       # (B, n_amp_slots) bool — real amps only
    amp_rail: float
    slew: float

    @property
    def batch(self) -> int:
        return self.indices.shape[0]

    @property
    def n_states(self) -> int:
        return self.pattern.n_states

    @property
    def n_nodes(self) -> int:
        return self.pattern.n_nodes

    @property
    def n_unknowns(self) -> int:
        return self.pattern.n_unknowns

    @property
    def amp_int_index(self) -> np.ndarray:
        return self.pattern.amp_int_index

    @property
    def amp_out_index(self) -> np.ndarray:
        return self.pattern.amp_out_index

    @property
    def ell_width(self) -> int:
        return self.indices.shape[2]

    @property
    def fill_ratio(self) -> float:
        """ELL row width over dense row length — the crossover metric."""
        return self.ell_width / max(self.n_states, 1)

    def matvec(self, z: jnp.ndarray) -> jnp.ndarray:
        """Batched ``M z`` (gathered row reduction, operand dtype)."""
        gathered = jnp.take_along_axis(z[:, None, :], self.indices, axis=2)
        return jnp.sum(self.weights * gathered, axis=2)

    def matvec_block(self, z: jnp.ndarray) -> jnp.ndarray:
        """Block matvec ``(B, k, nz) -> (B, k, nz)`` — one gathered row
        reduction over the whole block (the spectral subspace iteration
        runs on this instead of k sequential matvecs); delegates to the
        canonical :func:`repro.core.spectral.ell_block_matvec`."""
        from repro.core.spectral import ell_block_matvec

        return ell_block_matvec(self.indices, self.weights, z)

    def matvec_t(self, z: jnp.ndarray) -> jnp.ndarray:
        """Batched ``M^T z`` (row-wise scatter-add)."""
        b, nz, k = self.indices.shape
        contrib = (self.weights * z[:, :, None]).reshape(b, nz * k)
        cols = self.indices.reshape(b, nz * k)
        bidx = jnp.arange(b)[:, None]
        return jnp.zeros((b, nz), self.weights.dtype).at[bidx, cols].add(contrib)

    def diagonal(self) -> jnp.ndarray:
        """Batched ``diag(M)`` — slots whose column equals their row."""
        rows = jnp.arange(self.n_states, dtype=self.indices.dtype)[None, :, None]
        return jnp.sum(
            jnp.where(self.indices == rows, self.weights, 0.0), axis=2
        )

    def to_dense(self) -> np.ndarray:
        """Materialize ``(B, nz, nz)`` float64 — reference/fallback only."""
        idx = np.asarray(self.indices)
        w = np.asarray(self.weights)
        b, nz, k = idx.shape
        m = np.zeros((b, nz, nz), dtype=np.float64)
        bb = np.broadcast_to(np.arange(b)[:, None, None], idx.shape)
        rr = np.broadcast_to(np.arange(nz)[None, :, None], idx.shape)
        np.add.at(m, (bb, rr, idx), w)
        return m

    def to_dense_bss(self) -> BatchedStateSpace:
        """Dense-path view (the fill-ratio fallback of the sweep)."""
        return BatchedStateSpace(
            m=self.to_dense(),
            c=np.asarray(self.c),
            pattern=self.pattern,
            amp_active=self.amp_active,
            amp_rail=self.amp_rail,
            slew=self.slew,
        )


def _cumcount_np(r: np.ndarray) -> np.ndarray:
    """Per-element count of prior occurrences of the same value."""
    order = np.argsort(r, kind="stable")
    rs = r[order]
    pos = np.arange(r.size) - np.searchsorted(rs, rs, side="left")
    out = np.empty(r.size, dtype=np.int64)
    out[order] = pos
    return out


def _node_cell_layout(pat: StampPattern):
    """Static (row, col, slot) of every cell-output coupling stamp.

    Row = the node a cell terminal touches, col = the driving amp
    output state, slot = the terminal's position among the row's cell
    entries (ELL slots ``1 .. C``).  Order matches the value layout
    ``[pair_w (near) | pair_w (far) | gcell_w]``.
    """
    rows = np.concatenate([pat.pair_i, pat.pair_j, pat.gcell_i])
    cols = np.concatenate([pat.a1_out, pat.a2_out, pat.g_out])
    slot = _cumcount_np(rows)
    c_max = int(slot.max()) + 1 if rows.size else 0
    return rows.astype(np.int64), cols.astype(np.int32), slot, c_max


def _amp_rows_static(
    pat: StampPattern, opamp: OpAmpSpec, buffers: bool, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """The buffer/amp ELL rows — identical for every system in a batch.

    Inactive slots stamp the same constant dynamics as the dense path
    (a stable, decoupled subsystem); only the *node-side* coupling
    weights (cell currents) are per-system.
    """
    n = pat.n_nodes
    nz = pat.n_states
    w_u = opamp.omega_u
    p2 = 2.0 * np.pi * opamp.p2_hz if opamp.p2_hz > 0 else 0.0
    inv_a0 = 1.0 / opamp.open_loop_gain
    spa = pat.states_per_amp

    rows: list[np.ndarray] = []
    cols: list[np.ndarray] = []
    vals: list[np.ndarray] = []

    def stamp(r, c, v):
        r = np.asarray(r, dtype=np.int64)
        rows.append(r)
        cols.append(np.broadcast_to(np.asarray(c, dtype=np.int64), r.shape))
        vals.append(np.broadcast_to(np.asarray(v, dtype=np.float64), r.shape))

    if pat.n_pair_slots:
        pi, pj = pat.pair_i, pat.pair_j
        if buffers:
            stamp(pat.buf1_idx, pj, w_u)
            stamp(pat.buf1_idx, pat.buf1_idx, -w_u)
            stamp(pat.buf2_idx, pi, w_u)
            stamp(pat.buf2_idx, pat.buf2_idx, -w_u)
        for a_int, a_out, vplus, far in (
            (pat.a1_int, pat.a1_out, pi, pat.buf1_idx),
            (pat.a2_int, pat.a2_out, pj, pat.buf2_idx),
        ):
            stamp(a_int, vplus, w_u)
            stamp(a_int, a_out, -0.5 * w_u)
            stamp(a_int, far, -0.5 * w_u)
            stamp(a_int, a_int, -w_u * inv_a0)
            if spa == 2:
                stamp(a_out, a_int, p2)
                stamp(a_out, a_out, -p2)
    if pat.n_ground_slots:
        stamp(pat.g_int, pat.gcell_i, w_u)
        stamp(pat.g_int, pat.g_out, -0.5 * w_u)
        stamp(pat.g_int, pat.g_int, -w_u * inv_a0)
        if spa == 2:
            stamp(pat.g_out, pat.g_int, p2)
            stamp(pat.g_out, pat.g_out, -p2)

    amp_idx = np.zeros((nz - n, k), dtype=np.int32)
    amp_w = np.zeros((nz - n, k), dtype=np.float64)
    if rows:
        r = np.concatenate(rows)
        c = np.concatenate(cols)
        v = np.concatenate(vals)
        slot = _cumcount_np(r)
        amp_idx[r - n, slot] = c.astype(np.int32)
        amp_w[r - n, slot] = v
    return amp_idx, amp_w


# amp rows never exceed four stamps (v+, out, far, self)
_AMP_ROW_WIDTH = 4


def _ell_width(pat: StampPattern, vals: _BatchValues, c_max: int) -> int:
    """Bounded ELL row degree: 1 diag + C cell couplings + max branch
    degree across the batch, floored by the static amp-row width."""
    n = pat.n_nodes
    deg = np.zeros((vals.br_i.shape[0], n), dtype=np.int64)
    valid = np.arange(vals.br_i.shape[1])[None, :] < vals.n_br[:, None]
    bidx = np.arange(vals.br_i.shape[0])[:, None]
    np.add.at(deg, (bidx, vals.br_i), valid.astype(np.int64))
    np.add.at(deg, (bidx, vals.br_j), valid.astype(np.int64))
    max_deg = int(deg.max()) if deg.size else 0
    return max(1 + c_max + max_deg, _AMP_ROW_WIDTH)


@functools.partial(
    jax.jit, static_argnames=("n", "nz", "k", "c_start")
)
def _ell_assemble_jit(
    pair_i, pair_j, gcell_i,
    cell_rows, cell_cols, cell_slot,
    amp_idx, amp_w, amp_int_index,
    br_i, br_j, br_g, n_br,
    pair_w, gcell_w, pair_active, g_active,
    ground_g, supply_g, s_cur, elem, v_os_slots,
    c_node, c_switch, c_in, w_u,
    *, n: int, nz: int, k: int, c_start: int,
):
    """Device-side ELL scatter assembly (see module layout comment)."""
    b_count, nbr = br_i.shape
    bidx = jnp.arange(b_count)[:, None]
    f64 = jnp.float64

    # ---- node capacitance (identical physics to the dense path) ----
    cap = jnp.full((b_count, n), c_node, dtype=f64) + c_switch * elem
    if pair_i.shape[0]:
        pin = 2.0 * c_in * pair_active.astype(f64)
        cap = cap.at[:, pair_i].add(pin)
        cap = cap.at[:, pair_j].add(pin)
    if gcell_i.shape[0]:
        cap = cap.at[:, gcell_i].add(c_in * g_active.astype(f64))
    inv_c = 1.0 / cap

    # ---- accumulated node diagonal ----
    valid = jnp.arange(nbr)[None, :] < n_br[:, None]
    bg = jnp.where(valid, br_g, 0.0)
    diag = -(ground_g + supply_g)
    if nbr:
        diag = diag.at[bidx, br_i].add(-bg)
        diag = diag.at[bidx, br_j].add(-bg)
    if pair_i.shape[0]:
        diag = diag.at[:, pair_i].add(-pair_w)
        diag = diag.at[:, pair_j].add(-pair_w)
    if gcell_i.shape[0]:
        diag = diag.at[:, gcell_i].add(-gcell_w)

    # row nz is a write-off row for padded branch entries
    ell_w = jnp.zeros((b_count, nz + 1, k), dtype=f64)
    ell_i = jnp.zeros((b_count, nz + 1, k), dtype=jnp.int32)

    ell_w = ell_w.at[:, :n, 0].set(diag * inv_c)
    ell_i = ell_i.at[:, :n, 0].set(jnp.arange(n, dtype=jnp.int32)[None, :])

    if cell_rows.shape[0]:
        w_cell = jnp.concatenate([pair_w, pair_w, gcell_w], axis=1)
        w_cell = w_cell * inv_c[:, cell_rows]
        ell_w = ell_w.at[:, cell_rows, 1 + cell_slot].set(w_cell)
        ell_i = ell_i.at[:, cell_rows, 1 + cell_slot].set(
            jnp.broadcast_to(cell_cols[None, :], w_cell.shape)
        )

    if nbr:
        r2 = jnp.concatenate([br_i, br_j], axis=1)
        c2 = jnp.concatenate([br_j, br_i], axis=1)
        # passive off-diag is -g; the operator is -passive/C -> +g/C
        v2 = jnp.concatenate(
            [bg * inv_c[bidx, br_i], bg * inv_c[bidx, br_j]], axis=1
        )
        valid2 = jnp.concatenate([valid, valid], axis=1)
        r2 = jnp.where(valid2, r2, nz)

        def cumcount(r):
            s = r.shape[0]
            order = jnp.argsort(r)                       # stable in jax
            rs = r[order]
            pos = jnp.arange(s) - jnp.searchsorted(rs, rs, side="left")
            return jnp.zeros(s, pos.dtype).at[order].set(pos)

        slot2 = jnp.minimum(c_start + jax.vmap(cumcount)(r2), k - 1)
        ell_w = ell_w.at[bidx, r2, slot2].add(jnp.where(valid2, v2, 0.0))
        ell_i = ell_i.at[bidx, r2, slot2].add(
            jnp.where(valid2, c2, 0).astype(jnp.int32)
        )

    if nz > n:
        ell_w = ell_w.at[:, n:nz, :].set(amp_w[None])
        ell_i = ell_i.at[:, n:nz, :].set(amp_idx[None])

    c_vec = jnp.zeros((b_count, nz), dtype=f64).at[:, :n].set(s_cur * inv_c)
    if amp_int_index.shape[0]:
        c_vec = c_vec.at[:, amp_int_index].add(w_u * v_os_slots)

    return ell_i[:, :nz], ell_w[:, :nz], c_vec


def assemble_batch_ell(
    nets: list[Netlist],
    opamp: OpAmpSpec = AD712,
    *,
    v_os: list[np.ndarray | float | None] | None = None,
    buffers: bool = True,
    pattern: StampPattern | None = None,
) -> EllBatchedStateSpace:
    """Matrix-free state-space assembly: device-resident ELL operators.

    Same physics and arguments as :func:`assemble_batch`, but the
    operator batch is built by a jitted ``jnp`` scatter directly in
    stamp-slot ELL form — host work and memory stay O(B * components)
    and nothing of size ``(B, nz, nz)`` is ever materialized.
    """
    pat = pattern_union(nets, opamp, buffers=buffers) if pattern is None else pattern
    _check_batch_params(nets)
    vals = _gather_batch_values(nets, pat, v_os)

    cell_rows, cell_cols, cell_slot, c_max = _node_cell_layout(pat)
    k = _ell_width(pat, vals, c_max)
    amp_idx, amp_w = _amp_rows_static(pat, opamp, buffers, k)

    indices, weights, c_vec = _ell_assemble_jit(
        jnp.asarray(pat.pair_i), jnp.asarray(pat.pair_j),
        jnp.asarray(pat.gcell_i),
        jnp.asarray(cell_rows), jnp.asarray(cell_cols),
        jnp.asarray(cell_slot),
        jnp.asarray(amp_idx), jnp.asarray(amp_w),
        jnp.asarray(pat.amp_int_index),
        jnp.asarray(vals.br_i), jnp.asarray(vals.br_j),
        jnp.asarray(vals.br_g), jnp.asarray(vals.n_br),
        jnp.asarray(vals.pair_w), jnp.asarray(vals.gcell_w),
        jnp.asarray(vals.pair_active), jnp.asarray(vals.g_active),
        jnp.asarray(vals.ground_g), jnp.asarray(vals.supply_g),
        jnp.asarray(vals.s_cur), jnp.asarray(vals.elem),
        jnp.asarray(vals.v_os_slots),
        nets[0].params.c_node, nets[0].params.c_switch,
        opamp.c_in, opamp.omega_u,
        n=pat.n_nodes, nz=pat.n_states, k=k, c_start=1 + c_max,
    )
    return EllBatchedStateSpace(
        indices=indices,
        weights=weights,
        c=c_vec,
        pattern=pat,
        amp_active=vals.amp_active,
        amp_rail=opamp.rail_v,
        slew=opamp.slew_v_per_s,
    )


# ---------------------------------------------------------------------------
# Vmapped operating point
# ---------------------------------------------------------------------------


# Mixed-precision DC solve.  TPUs implement LU only in float32, so the
# operating point factors the row-equilibrated operator once in f32 and
# recovers fp64 by iterative refinement: the residual ``-c - M z`` is
# formed in f64, the correction solved with the f32 factors, until every
# system's normwise backward error is below DC_REFINE_TOL.  Each pass
# contracts the error by ~kappa * eps_f32 (the service mix's equilibrated
# operators have kappa <= 1e4, i.e. <= 1e-3 per pass), so a handful of
# passes reach f64 accuracy; rows that do not converge within
# DC_REFINE_MAX_ITERS (singular or f32-unfactorable operators) come back
# NaN and are repaired on the host by dc_solve_batch_finalize.
DC_REFINE_TOL = 1e-15
DC_REFINE_MAX_ITERS = 10

# host-side repairs made by dc_solve_batch_finalize: systems whose device
# solve came back non-finite and were re-solved with numpy in f64
DC_STATS = {"host_resolves": 0}


def _dc_solve_refined(m: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    f64 = jnp.float64
    m = m.astype(f64)
    rhs = -c.astype(f64)
    # row equilibration: the circuit rows span ~1e-8..1e8 (1/C scaling
    # against amp rates); unit-max rows keep f32 pivoting meaningful
    scale = jnp.max(jnp.abs(m), axis=2, keepdims=True)
    scale = jnp.where(scale > 0, scale, 1.0)
    m = m / scale
    rhs = rhs / scale[..., 0]
    factors = jax.lax.linalg.lu(m.astype(jnp.float32))[:2]     # (lu, piv)
    m_norm = jnp.max(jnp.sum(jnp.abs(m), axis=2), axis=1)       # (B,)
    rhs_norm = jnp.max(jnp.abs(rhs), axis=1)

    def correction(r):
        dz = jax.vmap(jax.scipy.linalg.lu_solve)(
            factors, r.astype(jnp.float32)
        )
        return dz.astype(f64)

    def backward_error(z):
        r = rhs - jnp.sum(m * z[:, None, :], axis=2)
        denom = m_norm * jnp.max(jnp.abs(z), axis=1) + rhs_norm
        err = jnp.max(jnp.abs(r), axis=1) / jnp.where(denom > 0, denom, 1.0)
        # a non-finite row never satisfies the test (NaN compares False)
        return r, jnp.where(jnp.isfinite(err), err, jnp.inf)

    def body(state):
        z, r, _err, it = state
        z = z + correction(r)
        r, err = backward_error(z)
        return z, r, err, it + 1

    def cond(state):
        _z, _r, err, it = state
        return (it < DC_REFINE_MAX_ITERS) & jnp.any(err > DC_REFINE_TOL)

    z0 = correction(rhs)
    r0, err0 = backward_error(z0)
    z, _r, err, _it = jax.lax.while_loop(cond, body, (z0, r0, err0, 0))
    # unconverged rows go to the host repair path rather than being
    # delivered at f32 accuracy
    return jnp.where((err <= DC_REFINE_TOL * 1e3)[:, None], z, jnp.nan)


_dc_solve_vmapped = jax.jit(_dc_solve_refined)

# per-device stream variant: each micro-batch gets a freshly transferred
# (B, nz) constant vector that nothing reads after the solve, so it is
# donated — XLA writes the (B, nz) state into its allocation.  (The
# (B, nz, nz) operator has no output of its shape to alias.)
_dc_solve_vmapped_donated = jax.jit(_dc_solve_refined, donate_argnums=(1,))

# platforms whose runtime implements input/output buffer aliasing; the
# CPU client ignores donations (with a warning), so fall back there
_DONATION_PLATFORMS = ("gpu", "cuda", "rocm", "tpu")


def _donation_supported(device=None) -> bool:
    plat = device.platform if device is not None else jax.default_backend()
    return plat in _DONATION_PLATFORMS


def dc_solve_batch_submit(
    bss: BatchedStateSpace, *, mesh=None, device=None
) -> jnp.ndarray:
    """Dispatch the batched DC solve; returns the *device* result.

    Under JAX async dispatch the returned array is a future — the host
    thread is free to build the next micro-batch while the device
    factorizes this one (the solve service's overlap model).  Pair with
    :func:`dc_solve_batch_finalize`, which blocks, materializes and
    applies the singular-support fallback; :func:`dc_solve_batch` is
    exactly submit + finalize.

    ``device`` places the whole batch on one device (per-device solve
    streams, donated operand buffers where the platform supports
    aliasing); ``mesh`` instead shards the batch axis over a 1-d solver
    mesh (:func:`repro.distributed.sharding.solver_mesh`).  The two are
    mutually exclusive.
    """
    if device is not None and mesh is not None:
        raise ValueError("pass either device= (stream) or mesh= (shard)")
    with span("core.transfer"):
        if device is not None:
            m = jax.device_put(bss.m, device)
            c = jax.device_put(bss.c, device)
        else:
            m = jnp.asarray(bss.m)
            c = jnp.asarray(bss.c)
    if device is not None:
        if _donation_supported(device):
            return _dc_solve_vmapped_donated(m, c)
        return _dc_solve_vmapped(m, c)
    if mesh is not None:
        from repro.distributed.sharding import shard_system_batch

        m, c = shard_system_batch(m, c, mesh=mesh)
    return _dc_solve_vmapped(m, c)


def dc_solve_batch_finalize(
    z_dev: jnp.ndarray, bss: BatchedStateSpace
) -> np.ndarray:
    """Block on an in-flight DC solve and apply the singular fallback."""
    z = np.asarray(z_dev)
    bad = ~np.all(np.isfinite(z), axis=1)
    if np.any(bad):
        DC_STATS["host_resolves"] += int(np.count_nonzero(bad))
        # JAX device buffers materialize as read-only views; copy
        # before patching the re-solved rows in
        z = np.array(z, dtype=np.float64)
        eye = np.eye(bss.n_states)
        for b in np.nonzero(bad)[0]:
            eps = 1e-12 * np.abs(bss.m[b]).max()
            z[b] = np.linalg.solve(bss.m[b] - eps * eye, -bss.c[b])
    return z


def dc_solve_batch(
    bss: BatchedStateSpace, *, mesh=None, device=None
) -> np.ndarray:
    """Steady states ``z_b = -M_b^{-1} c_b`` for the whole batch.

    Runs the f32-LU + f64-refinement solve on device; systems whose
    operator is singular (degenerate supports, see the single-system
    path) or does not refine to fp64 come back non-finite and are
    re-solved on the host with the tiny relative leakage ``1e-12 |M|``
    to ground (counted in :data:`DC_STATS`).
    See :func:`dc_solve_batch_submit` for the ``mesh`` / ``device``
    placement modes and the async split.
    """
    return dc_solve_batch_finalize(
        dc_solve_batch_submit(bss, mesh=mesh, device=device), bss
    )


# ---------------------------------------------------------------------------
# Settling criterion (shared with repro.core.transient)
# ---------------------------------------------------------------------------


def settling_time(
    dev: np.ndarray,
    times: np.ndarray,
    target: np.ndarray,
    *,
    rtol: float,
    atol: float,
) -> float:
    """Paper's criterion: first instant beyond which every node stays
    within 1% of its operating-point value."""
    tol = np.maximum(rtol * np.abs(target), atol)      # (nodes,)
    ok = np.all(np.abs(dev) <= tol[None, :], axis=1)   # (t,)
    if not ok[-1]:
        return float("inf")
    # last violation -> settle at the next evaluated instant
    bad = np.nonzero(~ok)[0]
    if bad.size == 0:
        return float(times[0])
    last = bad[-1]
    return float(times[min(last + 1, len(times) - 1)])


# ---------------------------------------------------------------------------
# Batched transient analysis
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchTransientResult:
    stable: np.ndarray           # (B,) bool
    settle_time: np.ndarray      # (B,) seconds; inf if never
    x_converged: np.ndarray      # (B, n_unknowns)
    max_re_eig: np.ndarray       # (B,)
    dominant_tau: np.ndarray     # (B,)
    mirror_residual: np.ndarray  # (B,)
    method: str = "eig"
    # spectral path only: converged rightmost Ritz pair with negative
    # restricted numerical abscissa (see repro.core.spectral); None on
    # the eig/euler paths
    certified: np.ndarray | None = None
    # euler path: per-system sweep steps actually taken (== max_steps
    # if never settled); spectral path: the predicted step count; None
    # on the eig/nonlinear paths.  The session warm-start accounting
    # reads this (steps saved = cold prediction - steps taken).
    settle_steps: np.ndarray | None = None

    def __len__(self) -> int:
        return self.stable.shape[0]


def _transient_batch_eig(
    bss: BatchedStateSpace,
    *,
    t_max: float,
    t_min: float,
    n_times: int,
    stability_tol: float,
    settle_rtol: float,
    settle_atol: float,
) -> BatchTransientResult:
    """Exact modal settling for every system (stacked eigendecomposition)."""
    b_count = bss.batch
    nu = bss.n_unknowns
    nn = bss.n_nodes

    lam, vec = np.linalg.eig(bss.m)                    # (B, nz), (B, nz, nz)
    max_re = np.max(lam.real, axis=1)
    rate_scale = np.max(np.abs(lam.real), axis=1)
    rate_scale = np.where(rate_scale == 0.0, 1.0, rate_scale)
    stable = max_re < stability_tol * rate_scale

    neg = lam.real < 0
    decays = np.where(neg, -lam.real, np.inf)
    min_decay = decays.min(axis=1)
    dominant_tau = np.where(min_decay < np.inf, 1.0 / min_decay, np.inf)

    settle = np.full(b_count, np.inf)
    x_conv = np.full((b_count, nu), np.nan)
    mirror = np.full(b_count, np.nan)

    if np.any(stable):
        times = np.logspace(np.log10(t_min), np.log10(t_max), n_times)
        idx = np.nonzero(stable)[0]
        z_star = np.linalg.solve(bss.m[idx], -bss.c[idx][..., None])[..., 0]
        coef = np.linalg.solve(vec[idx], (0.0 - z_star)[..., None])[..., 0]
        for k, b in enumerate(idx):
            rows = vec[b, :nu, :] * coef[k][None, :]   # (nu, modes)
            expo = np.exp(
                np.clip(lam[b][None, :] * times[:, None], -745.0, 60.0)
            )
            dev = np.real(expo @ rows.T)               # (t, nu)
            v_star = np.real(z_star[k, :nn])
            settle[b] = settling_time(
                dev, times, v_star[:nu], rtol=settle_rtol, atol=settle_atol
            )
            x_conv[b] = v_star[:nu]
            mirror[b] = (
                float(np.max(np.abs(v_star[:nu] + v_star[nu: 2 * nu])))
                if nn == 2 * nu
                else 0.0
            )
    return BatchTransientResult(
        stable=stable,
        settle_time=settle,
        x_converged=x_conv,
        max_re_eig=max_re,
        dominant_tau=dominant_tau,
        mirror_residual=mirror,
        method="eig",
    )


def _settle_dt(
    bss: BatchedStateSpace | EllBatchedStateSpace,
    dt_safety: float,
    dt_policy: str,
) -> np.ndarray:
    """Per-system forward-Euler step size.

    ``"diag"`` — the Gershgorin-flavoured ``dt_safety / max_i |M_ii|``
    rule (cheap, conservative for diagonally dominated rows, but blind
    to off-diagonal structure: it assumes near-real dominant modes).
    ``"spectral"`` — the abscissa-aware rule
    (:func:`repro.core.spectral.mode_dt_limit`): the margined modulus
    bound ``2 dt_safety / |lambda|_max`` from power iteration, tightened
    by the per-mode Euler-circle condition ``dt < 2 |Re| / |lambda|^2``
    over the exterior Krylov Ritz modes — so it stays valid for
    underdamped operators (``|Im| >> |Re|``), where both the diag rule
    and a bare modulus rule would integrate divergently.
    """
    if dt_policy == "spectral":
        from repro.core import spectral

        # dt-only configuration: rate + Krylov Ritz modes, no slow-mode
        # extraction and no certificate
        return spectral.spectral_bounds(
            bss, dt_safety=dt_safety, slow_iters=0, lanczos_iters=0
        ).dt
    if dt_policy != "diag":
        raise ValueError(f"unknown dt_policy {dt_policy!r}")
    if isinstance(bss, EllBatchedStateSpace):
        diag = np.abs(np.asarray(bss.diagonal()))
    else:
        diag = np.abs(np.diagonal(bss.m, axis1=1, axis2=2))
    rate = diag.max(axis=1)
    rate = np.where(rate == 0.0, 1.0, rate)
    return dt_safety / rate


def _settle_loop(step_chunk, z, dt, x_ref, *, rtol, atol, check_every,
                 max_steps, tol_floor=None):
    """Shared chunked-sweep convergence loop (dense and ELL backends).

    ``step_chunk(z, n) -> (z', res)`` advances ``n`` steps with the
    dt-folded operator; ``res`` is the fused settling-check reduction
    ``dt * max|M z' + c|``.  The final chunk is clamped so the sweep
    never integrates past ``max_steps`` (the recorded step counts obey
    ``steps <= max_steps``, with ``steps == max_steps`` meaning
    *unsettled within budget* — required now that the chunk length can
    be schedule-sized rather than a divisor of the budget).

    ``tol_floor`` (``(B,)``) widens the per-element band to at least
    that absolute value per system — the bf16 sweeps' equilibrium-shift
    allowance (:data:`BF16_SETTLE_RTOL`).
    """
    b_count, nu = x_ref.shape
    tol = np.maximum(rtol * np.abs(x_ref), atol)            # (B, nu)
    if tol_floor is not None:
        tol = np.maximum(tol, np.asarray(tol_floor)[:, None])
    steps = np.full(b_count, max_steps, dtype=np.int64)
    done = np.zeros(b_count, dtype=bool)
    res = np.zeros(b_count, dtype=np.float64)
    taken = 0
    x_now = None
    while taken < max_steps:
        chunk = min(check_every, max_steps - taken)
        # the host's launch of one chunk: the kernels run async, so this
        # span times the launches, not the device's work.  Both spans of
        # the loop carry the settle_poll sync label: the per-chunk
        # convergence poll IS the sweep's sanctioned host sync, and
        # SyncWatch must not charge it to the dispatch phase of
        # whichever service called us
        with span("core.sweep_chunk", sync="settle_poll"):
            z, r = step_chunk(z, chunk)
        taken += chunk
        with span("core.settle_poll", sync="settle_poll"):
            x_now = np.asarray(z[:, :nu], dtype=np.float64)
            # dt was folded into the operator, so the kernel's reduction
            # is dt * max|M z + c|; undo the fold to report the true
            # residual
            res = np.asarray(r, dtype=np.float64) / dt
            ok = np.all(np.abs(x_now - x_ref) <= tol, axis=1)
            newly = ok & ~done
            steps[newly] = taken
            done |= newly
        if np.all(done):
            break
    if x_now is None:
        # no step budget: the state was never polled
        with span("core.settle_poll", sync="settle_poll"):
            x_now = np.asarray(z[:, :nu], dtype=np.float64)
    # the last poll read the final state
    return steps, x_now, res


def euler_settle_batch(
    bss: BatchedStateSpace | EllBatchedStateSpace,
    x_ref: np.ndarray,
    *,
    rtol: float = 0.01,
    atol: float = 1e-4,
    dt_safety: float = 0.5,
    check_every: int | None = None,
    max_steps: int = 200_000,
    interpret: bool | None = None,
    dt_policy: str = "diag",
    bounds=None,
    x0: np.ndarray | None = None,
    sweep_dtype: str = "float32",
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Forward-Euler settling sweep through the Pallas kernels.

    Integrates the whole batch from ``z = 0`` in float32, ``check_every``
    fused steps per kernel launch, until every unknown of every system
    stays within ``max(rtol |x_ref|, atol)`` of its reference, or
    ``max_steps`` is hit.  The per-system step comes from
    :func:`_settle_dt` (``dt_policy``) and is folded into the operator
    so one kernel serves heterogeneous rates.

    ``x0`` (``(B, n_unknowns)``) warm-starts the sweep: the node block
    of the initial state is seeded with it (mirror nodes get ``-x0`` on
    the 2n design; amp/buffer states start at 0 — the fast modes they
    carry die within a few chunks) instead of the cold ``z = 0``.  A
    good ``x0`` (the previous round of a
    :class:`repro.serving.solve_service.SolveSession`) removes most of
    the slow-mode amplitude, and with spectral ``bounds`` the saved
    steps are *predicted* too, via the amplitude projection below.

    ``sweep_dtype="bfloat16"`` runs the bf16-weight / fp32-accumulate
    sweep kernels (:mod:`repro.kernels.ell_transient`): weight traffic
    halves; the settling band (``rtol`` ~1 %) absorbs the ~3-digit
    weight rounding.  Anything tighter than the band must come from
    digital refinement (:mod:`repro.core.refine`), not the sweep.

    ``bounds`` (a precomputed :class:`repro.core.spectral.SpectralBounds`)
    short-circuits the ``dt_policy="spectral"`` estimate and, when
    ``check_every`` is left ``None``, sizes the sweep chunks from the
    predicted settling step count
    (:func:`repro.kernels.ops.sweep_chunk_schedule`) — long chunks
    amortize kernel launches and host syncs over the predicted horizon
    instead of polling every 50 steps.  When ``bounds`` carries the
    slow-subspace basis, the prediction is amplitude-aware
    (:func:`repro.core.spectral.amplitude_settle_steps`): the initial
    error state (``z0`` embedding of ``x0`` minus the ``x_ref``
    embedding) is projected onto the slow subspace, so warm starts get
    short chunks instead of the blind ``ln(1/rtol)`` horizon.  Without
    a prediction, ``check_every`` defaults to 50.

    A dense :class:`BatchedStateSpace` runs the dense sweep kernels.
    An :class:`EllBatchedStateSpace` runs the matrix-free ELL-SpMV
    sweep — no ``(B, nz, nz)`` materialization anywhere on that path —
    unless its fill ratio says the dense kernel is cheaper
    (:func:`repro.kernels.ops.sweep_backend`), in which case it
    densifies and falls back.

    Returns ``(steps, x_final, residual, dt)``: the per-system settling
    step count (``max_steps`` if it never settled), the recovered
    unknowns, the kernel's fused ``max_i |M z + c|`` settling-check
    reduction from the final chunk, and the per-system step size.
    """
    from repro.kernels.ops import (
        SWEEP_STATE_LIMIT,
        ell_kernel_operands,
        ell_transient_sweep,
        sweep_backend,
        sweep_chunk_schedule,
        transient_sweep,
    )

    b_count = bss.batch
    nu = bss.n_unknowns
    nz = bss.n_states
    nn = bss.n_nodes
    x_ref = np.asarray(x_ref, dtype=np.float64).reshape(b_count, nu)

    if isinstance(bss, EllBatchedStateSpace):
        if sweep_backend(nz, bss.ell_width).startswith("dense"):
            # fill-ratio fallback: the ELL form carries no traffic
            # advantage here, and the dense kernels need no gather
            bss = bss.to_dense_bss()

    def _embed(x_nodes: np.ndarray) -> np.ndarray:
        """Node-block state embedding: ``(B, nu) -> (B, nz)``.

        Mirror nodes get ``-x`` on the 2n design; amp/buffer states 0.
        An estimate (amp outputs are nonzero at DC), good enough for
        warm-start seeds and amplitude projections — the settle loop's
        converged check is what actually terminates the sweep.
        """
        z_full = np.zeros((b_count, nz))
        z_full[:, :nu] = x_nodes
        if nn == 2 * nu:
            z_full[:, nu: 2 * nu] = -x_nodes
        return z_full

    z0_full = None
    if x0 is not None:
        z0_full = _embed(np.asarray(x0, dtype=np.float64).reshape(b_count, nu))

    # bf16 settles converge to the rounded operator's equilibrium: widen
    # the band by the per-system shift allowance (see BF16_SETTLE_RTOL)
    tol_floor = (
        BF16_SETTLE_RTOL * np.max(np.abs(x_ref), axis=1)
        if sweep_dtype == "bfloat16"
        else None
    )

    # the host work between the DC point and the first chunk: the step
    # size, the dt fold and narrowing of the operator, its kernel layout
    # and upload (``core.transfer`` nests inside on the dense path)
    with span("core.settle_prep"):
        if bounds is not None and dt_policy == "spectral":
            # re-apply the caller's safety factor to the (factor-free)
            # stability limit — a precomputed bounds must not pin dt to
            # the dt_safety it happened to be computed with
            dt = dt_safety * np.asarray(bounds.dt_limit)    # (B,)
        else:
            dt = _settle_dt(bss, dt_safety, dt_policy)      # (B,)
        if check_every is None:
            if bounds is not None:
                predicted = bounds.settle_steps
                if getattr(bounds, "slow_basis", None) is not None:
                    from repro.core import spectral

                    z_err = (z0_full if z0_full is not None else 0.0) \
                        - _embed(x_ref)
                    predicted = spectral.amplitude_settle_steps(
                        bounds, z_err, rtol=rtol,
                        x_scale=np.max(np.abs(x_ref), axis=1),
                    )
                check_every = sweep_chunk_schedule(predicted, max_steps)
            else:
                check_every = 50

        if isinstance(bss, EllBatchedStateSpace):
            # hoist the kernel layout out of the chunk loop: dt-folded,
            # slot-major and lane-padded once per sweep
            idx, wt, ct = ell_kernel_operands(
                bss.indices, bss.weights * dt[:, None, None],
                bss.c * dt[:, None], sweep_dtype,
            )
            size = idx.shape[2]
            if z0_full is not None:
                z = jnp.asarray(np.pad(
                    z0_full, ((0, 0), (0, size - nz))).astype(np.float32))
            else:
                z = jnp.zeros((b_count, size), dtype=jnp.float32)

            def step_chunk(zz, n):
                return ell_transient_sweep(
                    idx, wt, zz, ct, n_steps=n, interpret=interpret,
                    padded=True, sweep_dtype=sweep_dtype,
                )
        else:
            mt = (bss.m * dt[:, None, None]).astype(np.float32)
            ct = (bss.c * dt[:, None]).astype(np.float32)
            if sweep_dtype == "bfloat16":
                # bf16 storage semantics on the dense path: round the
                # folded operator through bf16 once, outside the chunk
                # loop (the dense kernels accumulate in f32 regardless)
                mt = np.asarray(
                    jnp.asarray(mt).astype(jnp.bfloat16).astype(jnp.float32)
                )

            # hoist the kernel-shape prep out of the chunk loop:
            # block-pad once (both dense paths) and pre-transpose for
            # the VMEM-resident sweep
            fused = nz <= SWEEP_STATE_LIMIT
            size = nz + (-nz) % 128
            if size != nz:
                mt = np.pad(mt, ((0, 0), (0, size - nz), (0, size - nz)))
                ct = np.pad(ct, ((0, 0), (0, size - nz)))
            if fused:
                mt = mt.transpose(0, 2, 1)

            with span("core.transfer"):
                if z0_full is not None:
                    z = jnp.asarray(np.pad(
                        z0_full, ((0, 0), (0, size - nz))).astype(np.float32))
                else:
                    z = jnp.zeros((b_count, size), dtype=jnp.float32)
                mt_j = jnp.asarray(np.ascontiguousarray(mt))
                ct_j = jnp.asarray(ct)

            def step_chunk(zz, n):
                return transient_sweep(
                    mt_j, zz, ct_j, n_steps=n, interpret=interpret,
                    m_transposed=fused,
                )

    steps, x_final, res = _settle_loop(
        step_chunk, z, dt, x_ref, rtol=rtol, atol=atol,
        check_every=check_every, max_steps=max_steps,
        tol_floor=tol_floor,
    )
    return steps, x_final, res, dt


def transient_batch(
    nets: list[Netlist],
    opamp: OpAmpSpec = AD712,
    *,
    v_os: list[np.ndarray | float | None] | None = None,
    buffers: bool = True,
    t_max: float = 1.0,
    t_min: float = 1e-10,
    n_times: int = 3000,
    stability_tol: float = 1e-6,
    method: str = "auto",
    pattern: StampPattern | None = None,
    interpret: bool | None = None,
    max_steps: int = 200_000,
    check_every: int | None = None,
    x_ref: np.ndarray | None = None,
    dt_policy: str = "diag",
    x0: np.ndarray | None = None,
    sweep_dtype: str = "float32",
    nl_t_end: float = 2e-4,
    nl_n_samples: int = 400,
    nl_safety: float = 0.4,
) -> BatchTransientResult:
    """Batched step-response settling analysis (supplies step at t=0).

    ``method``: ``"eig"`` — exact stacked eigendecomposition (O(nz^3)
    per system; the small-nz reference); ``"euler"`` — Pallas
    forward-Euler sweep (float32, settling time quantized to the
    sweep's check interval); ``"spectral"`` — matrix-free spectral
    estimates only (:mod:`repro.core.spectral`): device-resident on
    the ELL operators, predicts the settling time from the deflated
    rightmost-mode extraction without integrating (within 2x of the
    exact-eig slow mode on the reference set; the result additionally
    carries the ``certified`` stability flags); ``"nonlinear"`` — the
    slew-clipped, rail-clamped RK4 integration
    (:mod:`repro.core.transient_nl`, one vmapped scan over the batch):
    the Fig. 8 instability signature — ``stable`` is False when any
    active amp pins at a rail OR the trajectory never enters the
    settle band around the DC fixed point within ``nl_t_end``
    (``nl_t_end`` / ``nl_n_samples`` / ``nl_safety`` control the
    horizon, the sample grid, and the RK4 stability margin; the other
    time controls belong to the linear paths); ``"auto"`` — eig up to
    ``EIG_STATE_LIMIT`` states, euler beyond.

    On the euler path ``stable`` means *settled within the
    ``max_steps`` budget* — a stiff but asymptotically stable system
    can exceed it (raise ``max_steps``); the eig path reports true
    eigenvalue stability.  ``x_ref`` (the known solutions, ``(B, nu)``)
    lets the euler path settle against the mathematical reference and
    skip the dense DC solve entirely: with it, assembly and sweep run
    matrix-free end to end on the ELL operators.  ``dt_policy``
    ("diag" | "spectral") picks the step-size rule (:func:`_settle_dt`).
    ``x0`` warm-starts the euler sweep from a previous solution and
    ``sweep_dtype`` ("float32" | "bfloat16") selects the sweep kernel
    precision — both forwarded to :func:`euler_settle_batch` (no-ops on
    the other methods).  The euler/spectral results carry
    ``settle_steps`` (taken / predicted per system).

    ``pattern`` is honored by the euler path only; the eig path always
    regroups systems by their exact pattern (required for exact modal
    settling — inactive union-pattern slots pollute the
    eigendecomposition with near-degenerate driven modes).
    """
    params = nets[0].params
    if method == "auto":
        # the eig path runs per exact pattern, so gate on the largest
        # exact state count, not the union pattern's
        probe = max(
            pattern_of(net, opamp, buffers=buffers).n_states for net in nets
        )
        method = "eig" if probe <= EIG_STATE_LIMIT else "euler"
    if method == "eig":
        # The modal path is sensitive to the near-degenerate driven
        # modes that inactive slots add, so group systems by their
        # *exact* pattern: every group reproduces the single-system
        # assembly bit for bit (homogeneous batches — the paper's
        # sweeps — stay one stacked call).
        groups: dict[int, list[int]] = {}
        pats: dict[int, StampPattern] = {}
        for k, net in enumerate(nets):
            pat_k = pattern_of(net, opamp, buffers=buffers)
            gid = id(pat_k)
            groups.setdefault(gid, []).append(k)
            pats[gid] = pat_k
        b_count = len(nets)
        nu = nets[0].n_unknowns
        out = BatchTransientResult(
            stable=np.zeros(b_count, dtype=bool),
            settle_time=np.full(b_count, np.inf),
            x_converged=np.full((b_count, nu), np.nan),
            max_re_eig=np.full(b_count, np.nan),
            dominant_tau=np.full(b_count, np.nan),
            mirror_residual=np.full(b_count, np.nan),
            method="eig",
        )
        for gid, idx in groups.items():
            sub = [nets[k] for k in idx]
            sub_os = None if v_os is None else [v_os[k] for k in idx]
            bss = assemble_batch(
                sub, opamp, v_os=sub_os, buffers=buffers, pattern=pats[gid]
            )
            res = _transient_batch_eig(
                bss,
                t_max=t_max,
                t_min=t_min,
                n_times=n_times,
                stability_tol=stability_tol,
                settle_rtol=params.settle_rtol,
                settle_atol=params.settle_atol,
            )
            ii = np.asarray(idx)
            out.stable[ii] = res.stable
            out.settle_time[ii] = res.settle_time
            out.x_converged[ii] = res.x_converged
            out.max_re_eig[ii] = res.max_re_eig
            out.dominant_tau[ii] = res.dominant_tau
            out.mirror_residual[ii] = res.mirror_residual
        return out
    if method == "nonlinear":
        # slew-clipped, rail-clamped RK4 (one vmapped scan): the
        # instability verdict is physical — an active amp pinned at a
        # rail (Sec. III-C.2) — and settling is measured on the sample
        # grid against the DC fixed point, like the linear paths
        from repro.core import transient_nl

        bss = assemble_batch(
            nets, opamp, v_os=v_os, buffers=buffers, pattern=pattern
        )
        tr = transient_nl.nonlinear_transient_batch(
            nets, opamp,
            t_end=nl_t_end,
            n_samples=nl_n_samples,
            v_os=v_os,
            safety=nl_safety,
            bss=bss,
        )
        b_count = len(nets)
        nu = bss.n_unknowns
        z_star = dc_solve_batch(bss)
        x_star = z_star[:, :nu]
        tol = np.maximum(
            params.settle_rtol * np.abs(x_star)[:, None, :],
            params.settle_atol,
        )
        ok = np.all(np.abs(tr.x - x_star[:, None, :]) <= tol, axis=2)
        # first sample index from which the trajectory stays in-band
        viol = ~ok[:, ::-1]
        last_bad = np.where(
            viol.any(axis=1),
            ok.shape[1] - 1 - np.argmax(viol, axis=1),
            -1,
        )
        settled = ok[:, -1] & ~tr.saturated
        idx = np.clip(last_bad + 1, 0, ok.shape[1] - 1)
        settle_time = np.where(settled, tr.times[idx], np.inf)
        nn = bss.n_nodes
        if nn == 2 * nu:
            mirror = np.max(
                np.abs(z_star[:, :nu] + z_star[:, nu: 2 * nu]), axis=1
            )
        else:
            mirror = np.zeros(b_count)
        return BatchTransientResult(
            stable=settled,
            settle_time=settle_time,
            x_converged=np.where(settled[:, None], tr.x_final, np.nan),
            max_re_eig=np.full(b_count, np.nan),
            dominant_tau=np.full(b_count, np.nan),
            mirror_residual=mirror,
            method="nonlinear",
        )
    if method == "spectral":
        # estimator only: extreme-eigenvalue bounds on the device-
        # resident ELL operators — no dense build, no integration
        from repro.core import spectral

        bss = assemble_batch_ell(
            nets, opamp, v_os=v_os, buffers=buffers, pattern=pattern
        )
        sb = spectral.spectral_bounds(bss, rtol=params.settle_rtol)
        b_count = len(nets)
        nu = bss.n_unknowns
        if x_ref is not None:
            x_conv = np.where(
                sb.stable[:, None],
                np.asarray(x_ref, dtype=np.float64).reshape(b_count, nu),
                np.nan,
            )
        else:
            x_conv = np.full((b_count, nu), np.nan)
        with np.errstate(divide="ignore"):
            tau = np.where(sb.stable, 1.0 / np.maximum(-sb.slow_re, 1e-300),
                           np.inf)
        return BatchTransientResult(
            stable=sb.stable,
            settle_time=sb.settle_time,
            x_converged=x_conv,
            max_re_eig=sb.slow_re,
            dominant_tau=tau,
            mirror_residual=np.full(b_count, np.nan),
            method="spectral",
            certified=sb.certified,
            settle_steps=sb.settle_steps,
        )
    if method != "euler":
        raise ValueError(f"unknown transient method {method!r}")

    # the euler settle of one micro-batch: assembly, DC solve, sweep
    with span("core.settle"):
        if x_ref is not None:
            # matrix-free fast path: ELL assembly, settle against the
            # caller's reference — nothing (B, nz, nz) is ever built
            bss = assemble_batch_ell(
                nets, opamp, v_os=v_os, buffers=buffers, pattern=pattern
            )
            nu = bss.n_unknowns
            x_star = np.asarray(x_ref, dtype=np.float64).reshape(len(nets), nu)
            z_star = None
        else:
            bss = assemble_batch(
                nets, opamp, v_os=v_os, buffers=buffers, pattern=pattern
            )
            # settle against the vmapped DC operating point
            z_star = dc_solve_batch(bss)
            nu = bss.n_unknowns
            x_star = z_star[:, :nu]
        bounds = None
        if dt_policy == "spectral":
            # one full spectral pass: its abscissa-aware dt drives the
            # integration and its predicted settling step count sizes the
            # sweep chunks (kernels launch over the predicted horizon
            # instead of polling every 50 steps)
            from repro.core import spectral

            bounds = spectral.spectral_bounds(bss, rtol=params.settle_rtol)
        steps, x_final, _res, dt = euler_settle_batch(
            bss,
            x_star,
            rtol=params.settle_rtol,
            atol=params.settle_atol,
            max_steps=max_steps,
            check_every=check_every,
            interpret=interpret,
            dt_policy=dt_policy,
            bounds=bounds,
            x0=x0,
            sweep_dtype=sweep_dtype,
        )
    tol = np.maximum(params.settle_rtol * np.abs(x_star), params.settle_atol)
    if sweep_dtype == "bfloat16":
        # same equilibrium-shift allowance the sweep loop applied
        tol = np.maximum(
            tol, BF16_SETTLE_RTOL * np.max(np.abs(x_star), axis=1,
                                           keepdims=True)
        )
    settled = np.all(np.abs(x_final - x_star) <= tol, axis=1)
    settle_time = np.where(settled, steps * dt, np.inf)
    nn = bss.n_nodes
    if nn != 2 * nu:
        mirror = np.zeros(len(nets))
    elif z_star is not None:
        mirror = np.max(np.abs(z_star[:, :nu] + z_star[:, nu: 2 * nu]), axis=1)
    else:
        # matrix-free path: no DC state to read the mirror nodes from
        mirror = np.full(len(nets), np.nan)
    return BatchTransientResult(
        stable=settled,
        settle_time=settle_time,
        x_converged=np.where(settled[:, None], x_final, np.nan),
        max_re_eig=np.full(len(nets), np.nan),
        dominant_tau=np.full(len(nets), np.nan),
        mirror_residual=mirror,
        method="euler",
        settle_steps=steps,
    )

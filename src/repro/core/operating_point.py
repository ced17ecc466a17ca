"""Operating-point (DC) analysis with component non-idealities.

Replaces the paper's LTspice ``.op`` runs: solve the steady state of the
full state-space (finite open-loop gain and input offset included on the
amp rows; digital-pot quantization / tolerance / wiper resistance applied
to the netlist) and compare the recovered unknowns with the mathematical
solution.  This produces the error statistics of Figs. 9a/13a/14a/15a/16a.

Error metric
------------
The paper reports "maximum error" as a percentage; with solutions drawn
from U[-0.5, 0.5] V a per-entry relative error is ill-defined near zero
crossings, so we follow full-scale normalization:

    err_fullscale = max_i |x_hat_i - x_i|  /  max_i |x_i|

(`max_rel_error` — the per-entry metric with an absolute floor — is also
reported for completeness).

Offset model
------------
Datasheet V_os is a *maximum*; SPICE macro models typically realize a
typical-to-zero offset.  ``offset_mode``:

* "none"        — V_os = 0 (macro models without offset),
* "random"      — V_os ~ U(-max, +max) per amp (device variation;
                  default, used for the paper-comparison statistics),
* "alternating" — +/-V_os_max alternating per amp: worst-case
                  *differential* drive of the (i, n+i) cell pairs, an
                  upper bound.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import engine
from repro.core.network import Netlist
from repro.core.specs import OpAmpSpec, AD712
from repro.core.transient import assemble_state_space


@dataclasses.dataclass(frozen=True)
class NonIdealities:
    """Component error model.

    * ``pot_bits``: digital-potentiometer resolution (0 = ideal).
    * ``pot_tol``: relative conductance tolerance, uniform per resistor.
    * ``wiper_ohm``: pot wiper/series resistance (g -> g/(1 + g R_w));
      this is the parasitic the paper's alpha-scaling study (Fig. 16)
      attenuates by scaling conductances down.
    * ``offset_mode``: see module docstring.
    * ``use_finite_gain``: apply the finite open-loop gain.
    * ``seed``: RNG seed for tolerance/offset draws.
    """

    pot_bits: int = 0
    pot_tol: float = 0.0
    wiper_ohm: float = 0.0
    offset_mode: str = "random"
    use_finite_gain: bool = True
    seed: int = 0


IDEAL = NonIdealities(
    pot_bits=0, pot_tol=0.0, wiper_ohm=0.0, offset_mode="none", use_finite_gain=False
)
DEFAULT_NONIDEAL = NonIdealities()
# full hardware model: 10-bit pots with 1% tolerance and 50-ohm wipers
HARDWARE = NonIdealities(pot_bits=10, pot_tol=0.01, wiper_ohm=50.0)


@dataclasses.dataclass
class OperatingPoint:
    x: np.ndarray                 # recovered unknowns
    v: np.ndarray                 # all node voltages
    amp_outputs: np.ndarray       # op-amp output voltages
    amp_saturated: bool           # any |a| beyond the rail -> invalid OP
    max_rel_error: float | None   # per-entry, floored, vs reference
    max_abs_error: float | None   # volts
    err_fullscale: float | None   # max abs error / max |x_ref| (paper metric)


def draw_offsets(
    spec: OpAmpSpec, n_amps: int, mode: str, seed: int
) -> np.ndarray:
    if mode == "none" or n_amps == 0:
        return np.zeros(n_amps)
    if mode == "alternating":
        return spec.v_os * np.where(np.arange(n_amps) % 2 == 0, 1.0, -1.0)
    if mode == "random":
        rng = np.random.default_rng(seed + 7919)
        return rng.uniform(-spec.v_os, spec.v_os, size=n_amps)
    raise ValueError(f"unknown offset_mode {mode!r}")


def apply_nonidealities(net: Netlist, ni: NonIdealities) -> Netlist:
    out = net
    if ni.pot_bits > 0:
        out = out.quantized(ni.pot_bits)
    if ni.pot_tol > 0.0:
        out = out.perturbed(np.random.default_rng(ni.seed), ni.pot_tol)
    if ni.wiper_ohm > 0.0:
        out = out.with_wiper(ni.wiper_ohm)
    return out


def operating_point(
    net: Netlist,
    opamp: OpAmpSpec = AD712,
    *,
    nonideal: NonIdealities = DEFAULT_NONIDEAL,
    x_ref: np.ndarray | None = None,
) -> OperatingPoint:
    """DC solve of the (non-ideal) circuit."""
    net_ni = apply_nonidealities(net, nonideal)
    spec = opamp
    if not nonideal.use_finite_gain:
        spec = dataclasses.replace(spec, open_loop_gain=1e15)
    v_os = draw_offsets(spec, net_ni.n_amps, nonideal.offset_mode, nonideal.seed)
    ss = assemble_state_space(net_ni, spec, v_os=v_os)
    try:
        z = np.linalg.solve(ss.m, -ss.c)
    except np.linalg.LinAlgError:
        # degenerate support: with b_i = 0 on the support node (Eq. 22
        # puts the only ground leg at k_s1 = |b_1|/4) disconnected node
        # pairs float and the DC operator is singular.  Physical
        # circuits always leak; model a tiny leakage to ground on every
        # state (relative 1e-12 — far below the component error floor).
        eps = 1e-12 * np.abs(ss.m).max()
        z = np.linalg.solve(ss.m - eps * np.eye(ss.n_states), -ss.c)
    v = z[: ss.n_nodes]
    a = z[ss.amp_out_index] if ss.amp_out_index.size else np.zeros(0)
    sat = bool(np.any(np.abs(a) > ss.amp_rail)) if a.size else False
    x = net.recovered_solution(v)

    max_rel = max_abs = err_fs = None
    if x_ref is not None:
        x_ref = np.asarray(x_ref, dtype=np.float64)
        err = np.abs(x - x_ref)
        max_abs = float(err.max())
        scale = np.maximum(np.abs(x_ref), 1e-3)
        max_rel = float((err / scale).max())
        err_fs = float(max_abs / max(np.abs(x_ref).max(), 1e-12))
    return OperatingPoint(
        x=x,
        v=v,
        amp_outputs=a,
        amp_saturated=sat,
        max_rel_error=max_rel,
        max_abs_error=max_abs,
        err_fullscale=err_fs,
    )


@dataclasses.dataclass
class BatchOperatingPoint:
    """Batched DC analysis: per-system arrays over a shared stamp pattern."""

    x: np.ndarray                 # (B, n_unknowns)
    v: np.ndarray                 # (B, n_nodes)
    amp_outputs: np.ndarray       # (B, n_amp_slots); inactive slots = 0
    amp_saturated: np.ndarray     # (B,) bool
    max_rel_error: np.ndarray | None    # (B,)
    max_abs_error: np.ndarray | None    # (B,)
    err_fullscale: np.ndarray | None    # (B,)
    # which amp slots system b actually populates (B, n_amp_slots);
    # active slots in slot order == the net's amp order
    amp_active: np.ndarray | None = None

    def __len__(self) -> int:
        return self.x.shape[0]

    def __getitem__(self, b: int) -> OperatingPoint:
        amps = self.amp_outputs[b]
        if self.amp_active is not None:
            amps = amps[self.amp_active[b]]   # single-path n_amps shape
        return OperatingPoint(
            x=self.x[b],
            v=self.v[b],
            amp_outputs=amps,
            amp_saturated=bool(self.amp_saturated[b]),
            max_rel_error=(
                None if self.max_rel_error is None
                else float(self.max_rel_error[b])
            ),
            max_abs_error=(
                None if self.max_abs_error is None
                else float(self.max_abs_error[b])
            ),
            err_fullscale=(
                None if self.err_fullscale is None
                else float(self.err_fullscale[b])
            ),
        )


@dataclasses.dataclass
class PendingBatchOperatingPoint:
    """An in-flight batched DC solve: host metadata + the device future.

    Produced by :func:`operating_point_batch_submit` after the host-side
    work (error model, stamp values) is done and the device assembly
    and the vmapped solve have been *dispatched*; under JAX async
    dispatch the device computes while the caller builds its next
    micro-batch.  :meth:`wait` blocks,
    materializes and unpacks — ``operating_point_batch`` is exactly
    submit + wait, so the two paths cannot drift.  It is idempotent,
    and once it has run the handle holds no device array: the
    ``(B, nz, nz)`` operator lives on the device from submit until the
    harvest and no longer.
    """

    _bss: "engine.BatchedStateSpace | None"
    _z_dev: object
    _x_ref: np.ndarray | None
    _batch: int
    _done: BatchOperatingPoint | None = None

    def wait(self) -> BatchOperatingPoint:
        if self._done is None:
            self._done = self._harvest()
            self._bss = self._z_dev = None
        return self._done

    def _harvest(self) -> BatchOperatingPoint:
        bss = self._bss
        z = engine.dc_solve_batch_finalize(self._z_dev, bss)
        nn = bss.n_nodes
        nu = bss.n_unknowns
        v = z[:, :nn]
        x = v[:, :nu]
        if bss.amp_out_index.size:
            a = z[:, bss.amp_out_index] * bss.amp_active
            sat = np.any(
                (np.abs(z[:, bss.amp_out_index]) > bss.amp_rail)
                & bss.amp_active,
                axis=1,
            )
        else:
            a = np.zeros((self._batch, 0))
            sat = np.zeros(self._batch, dtype=bool)

        max_rel = max_abs = err_fs = None
        if self._x_ref is not None:
            x_ref = np.asarray(self._x_ref, dtype=np.float64).reshape(
                self._batch, nu
            )
            err = np.abs(x - x_ref)
            max_abs = err.max(axis=1)
            scale = np.maximum(np.abs(x_ref), 1e-3)
            max_rel = (err / scale).max(axis=1)
            err_fs = max_abs / np.maximum(np.abs(x_ref).max(axis=1), 1e-12)
        return BatchOperatingPoint(
            x=x,
            v=v,
            amp_outputs=a,
            amp_saturated=sat,
            max_rel_error=max_rel,
            max_abs_error=max_abs,
            err_fullscale=err_fs,
            amp_active=bss.amp_active,
        )


def operating_point_batch_submit(
    nets: list[Netlist],
    opamp: OpAmpSpec = AD712,
    *,
    nonideal: NonIdealities = DEFAULT_NONIDEAL,
    x_ref: np.ndarray | None = None,
    pattern: "engine.StampPattern | None" = None,
    mesh=None,
    device=None,
) -> PendingBatchOperatingPoint:
    """Host phase of the batched DC analysis + async device dispatch.

    Applies the per-system error model, then assembles the batch on the
    shared stamp pattern on the device
    (:func:`repro.core.engine.assemble_batch_device`: the host sends the
    stamp values, the dense operator is built where it is solved) and
    dispatches the batched fp64-refined DC solve — on one ``device``
    (per-device solve streams, see
    :func:`repro.core.engine.dc_solve_batch_submit`), or assembled on
    the default device and sharded over ``mesh`` — and returns without
    blocking.  No ``(B, nz, nz)`` array exists on the host.
    """
    spec = opamp
    if not nonideal.use_finite_gain:
        spec = dataclasses.replace(spec, open_loop_gain=1e15)
    nets_ni = [apply_nonidealities(net, nonideal) for net in nets]
    v_os = [
        draw_offsets(spec, net.n_amps, nonideal.offset_mode, nonideal.seed)
        for net in nets_ni
    ]
    bss = engine.assemble_batch_device(
        nets_ni, spec, v_os=v_os, pattern=pattern, device=device
    )
    z_dev = engine.dc_solve_batch_submit(bss, mesh=mesh, device=device)
    return PendingBatchOperatingPoint(
        _bss=bss, _z_dev=z_dev, _x_ref=x_ref, _batch=len(nets)
    )


def operating_point_batch(
    nets: list[Netlist],
    opamp: OpAmpSpec = AD712,
    *,
    nonideal: NonIdealities = DEFAULT_NONIDEAL,
    x_ref: np.ndarray | None = None,
    pattern: "engine.StampPattern | None" = None,
    mesh=None,
    device=None,
) -> BatchOperatingPoint:
    """Batched DC solve of the (non-ideal) circuits.

    The per-system error model is applied exactly as in the single path
    (quantize -> perturb -> wiper per netlist, per-amp offset draws with
    the same per-system RNG stream), then the whole batch is assembled
    on one shared stamp pattern and solved with the engine's batched
    fp64-refined DC solve.  ``x_ref`` is (B, n) (or None to skip errors).
    ``mesh`` shards the DC solve's batch axis over a 1-d solver mesh
    (:func:`repro.distributed.sharding.solver_mesh`); ``device`` places
    the whole batch on one device instead (the serving streams).  This
    is :func:`operating_point_batch_submit` immediately waited on.
    """
    return operating_point_batch_submit(
        nets,
        opamp,
        nonideal=nonideal,
        x_ref=x_ref,
        pattern=pattern,
        mesh=mesh,
        device=device,
    ).wait()

"""repro.core — Resistive Network Mapping (RNM) analog SPD solver.

This package implements the paper's contribution:

  * the equivalent-resistive-network mapping of an SPD system ``A x = b``
    (Sec. II, Eqs. 5-6),
  * the preliminary n-unknown design (Sec. III, Eqs. 12-13),
  * the proposed 2n-unknown transform (Sec. IV, Eqs. 14-23) with the
    eigen-split stability analysis (Eq. 17) and the proposed D matrix
    (Eq. 22),
  * behavioral op-amp models (Table I) and the circuit transient engine
    (LTI modal solution + nonlinear scan integration) that replaces the
    paper's LTspice runs,
  * operating-point analysis with component non-idealities,
  * the crosspoint-array layout (Sec. IV-A4), power model (Eqs. 28-31)
    and component-count formulas (Table II).

Batched-engine architecture
---------------------------
The physics core is batched end to end (:mod:`repro.core.engine`):

* **Stamp cache** — netlists store structure-of-arrays component stamps
  (``branch_i/j/g``, ``cell_i/j/w``); the static sparsity structure of
  the LTI state-space (cell slots, buffer/amp state layout, scatter
  indices) is a :class:`~repro.core.engine.StampPattern`, cached per
  ``(n, design)`` — for the proposed design cells live only on the
  ``(i, n+i)`` pairs, so one pattern serves every batch of that family.
  Assembly is vectorized ``np.add.at`` scatter-adds into
  ``(B, nz, nz)`` operators; a slot a system does not populate stamps
  ``w = 0`` (amp dynamics stay as a stable decoupled subsystem).
* **vmap vs Pallas path selection** — the operating point is one
  batched f32 LU refined to fp64 on the device; transient settling
  uses the exact stacked eigendecomposition up to
  :data:`~repro.core.engine.EIG_STATE_LIMIT` states and the batch-aware
  Pallas ``transient_step``/``transient_sweep`` forward-Euler kernels
  (fused ``max |M z + c|`` settling-check reduction) beyond.
  ``solve`` is a thin B=1 wrapper over ``solve_batch``.
* **x64 policy** — circuit analyses require float64 (1e-12 F node
  capacitances against 1e6 rad/s amp rates): importing ``repro.core``
  enables JAX x64 mode globally, and assembly/exact paths run float64
  throughout.  Only the Pallas Euler sweep drops to float32, which the
  1 % settling tolerance absorbs.  Model/training code elsewhere in the
  repo always passes explicit dtypes, so it is unaffected.
"""

from jax import config as _config

_config.update("jax_enable_x64", True)

from repro.core.specs import (  # noqa: E402
    AD712,
    LTC2050,
    LTC6268,
    OPAMPS,
    CircuitParams,
    OpAmpSpec,
)
from repro.core.transform import (  # noqa: E402
    Transformed2N,
    assemble_2n,
    column_abs_sums,
    d_matrix_proposed,
    d_matrix_scaled,
    supply_conductance,
    transform_2n,
)
from repro.core.network import (  # noqa: E402
    Netlist,
    build_preliminary,
    build_preliminary_batch,
    build_proposed,
    build_proposed_batch,
)
from repro.core.transient import (  # noqa: E402
    StateSpace,
    TransientResult,
    assemble_state_space,
    lti_transient,
    settling_time,
)
from repro.core.operating_point import (  # noqa: E402
    BatchOperatingPoint,
    NonIdealities,
    OperatingPoint,
    operating_point,
    operating_point_batch,
)
from repro.core.engine import (  # noqa: E402
    BatchTransientResult,
    BatchedStateSpace,
    StampPattern,
    assemble_batch,
    dc_solve_batch,
    euler_settle_batch,
    pattern_of,
    pattern_union,
    transient_batch,
)
from repro.core.solver import (  # noqa: E402
    BatchSolveResult,
    SolveResult,
    solve,
    solve_batch,
)
from repro.core.sdd import is_diagonally_dominant, sdd_margin  # noqa: E402
from repro.core.power import system_power  # noqa: E402
from repro.core.components import component_counts  # noqa: E402
from repro.core.crosspoint import crosspoint_layout  # noqa: E402

__all__ = [
    "AD712",
    "LTC2050",
    "LTC6268",
    "OPAMPS",
    "CircuitParams",
    "OpAmpSpec",
    "Transformed2N",
    "assemble_2n",
    "column_abs_sums",
    "d_matrix_proposed",
    "d_matrix_scaled",
    "supply_conductance",
    "transform_2n",
    "Netlist",
    "build_preliminary",
    "build_preliminary_batch",
    "build_proposed",
    "build_proposed_batch",
    "StateSpace",
    "TransientResult",
    "assemble_state_space",
    "lti_transient",
    "settling_time",
    "NonIdealities",
    "OperatingPoint",
    "BatchOperatingPoint",
    "operating_point",
    "operating_point_batch",
    "BatchTransientResult",
    "BatchedStateSpace",
    "StampPattern",
    "assemble_batch",
    "dc_solve_batch",
    "euler_settle_batch",
    "pattern_of",
    "pattern_union",
    "transient_batch",
    "SolveResult",
    "BatchSolveResult",
    "solve",
    "solve_batch",
    "is_diagonally_dominant",
    "sdd_margin",
    "system_power",
    "component_counts",
    "crosspoint_layout",
]

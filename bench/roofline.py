"""Peak rates and the least work of a kernel, computed from shapes.

The peaks come from ``peaks.json`` beside this file, keyed by the
``device_kind`` JAX reports; a device missing from the table is an
error, never a default.  The byte counts are functions of the operator's
shape alone, so a later change to a kernel changes its time and not
its yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

WEIGHT_BYTES = {"float32": 4, "bfloat16": 2}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError if not listed."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks listed for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


def ell_step_bytes(nz: int, k: int, sweep_dtype: str) -> int:
    """Least HBM traffic of one fused forward-Euler step of one system
    whose ``nz``-state operator has at most ``k`` nonzeros per row:
    each of the ``nz * k`` slots reads a 4-byte index and a weight at
    the sweep dtype, and the step reads the state and the constant
    vector and writes the state, 4 bytes each per state."""
    return nz * k * (4 + WEIGHT_BYTES[sweep_dtype]) + 3 * nz * 4


def sweep_least_seconds(steps: int, systems: int, nz: int, k: int,
                        sweep_dtype: str, hbm_bytes_per_s: float) -> float:
    """Least time of ``steps`` Euler steps over ``systems`` systems at
    the peak HBM bandwidth (the step is bandwidth-bound: 2 flops per
    slot against at least 6 bytes)."""
    return steps * systems * ell_step_bytes(nz, k, sweep_dtype) / hbm_bytes_per_s

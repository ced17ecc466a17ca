"""Symmetric-diagonally-dominant detection — the O(1) passive path.

Eq. 25: the proposed design is *fully passive* (no op-amps, settling at
parasitic-RC speed, independent of n) exactly when

    A_ii >= (K_s)_ii + sum_{j != i} |A_ji|     for all i,

i.e. (A - K_s) is (column) diagonally dominant.  The support node
(i = 0), which Eq. 22's D gives the whole K_s, needs
A_00 >= 2 (K_s)_00 + sum_{j != 0} |A_j0|.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.transform import column_abs_sums, supply_conductance


def sdd_margin(a: jnp.ndarray, b: jnp.ndarray, supply_v: float = 4.0) -> jnp.ndarray:
    """Per-column margin of Eq. 25 (>= 0 everywhere -> passive network).

    margin_i = A_ii - (K_s)_ii - sum_{j != i} |A_ji|, with 2 (K_s)_00 at
    the support node; it equals -2 diag(K_B) of the proposed transform.
    """
    a = jnp.asarray(a)
    k_s = supply_conductance(jnp.asarray(b), supply_v)
    diag = jnp.diagonal(a)
    off = column_abs_sums(a) - jnp.abs(diag)
    return diag - k_s.at[0].multiply(2.0) - off


def is_diagonally_dominant(
    a: jnp.ndarray, b: jnp.ndarray, supply_v: float = 4.0, tol: float = 0.0
) -> jnp.ndarray:
    """True iff the transformed network needs no negative-resistance cell."""
    return jnp.all(sdd_margin(a, b, supply_v) >= -tol)

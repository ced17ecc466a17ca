"""The paper's 2n x 2n SPD transform (Sec. IV, Eqs. 13-23).

Given ``A x = b`` (A symmetric positive-definite), build

    [[K_A, K_B], [K_B, K_A]] {x; -x} = {b - K_s x; -b - K_s (-x)}      (14)

with

    K_A = D + 0.5 (A - |A|) - K_s                                      (15)
    K_B = D - 0.5 (A + |A|)                                            (16)

Every off-diagonal of K_A and K_B is <= 0 (positive resistor); only the
*diagonal* of K_B may be positive, requiring at most n negative-resistance
cells instead of up to (n^2 - n)/2 in the preliminary design.

Eigen-split (Eq. 17):  spec(K_2n) = spec(K_A + K_B)  U  spec(K_A - K_B),
with  K_A - K_B = A - K_s  (Eq. 18)  and  K_A + K_B = 2D - |A| - K_s
(Eq. 19).  PD of the transformed system therefore requires Eq. 20:

    D_ii > 0.5 [ (K_s)_ii + sum_j |A_ji| ].

All functions are pure jnp and jit/vmap compatible.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.specs import CircuitParams, DEFAULT_PARAMS


def column_abs_sums(a: jnp.ndarray) -> jnp.ndarray:
    """sum_j |A_ji| per column i — the paper's only O(n^2) digital cost.

    (Sec. V proposes amortizing it into system assembly or an analog
    MVM-by-ones; ``kernels/spd_transform`` fuses it on TPU.)
    """
    return jnp.sum(jnp.abs(a), axis=0)


def supply_conductance(b: jnp.ndarray, supply_v: float = 4.0) -> jnp.ndarray:
    """Eq. 13: k_si = |b_i| / x_s  (= |0.25 b_i| at 4 V rails)."""
    return jnp.abs(b) / supply_v


def d_matrix_scaled(a: jnp.ndarray, beta: float) -> jnp.ndarray:
    """Eq. 21: D = beta * max_i(sum_j |A_ji|) * I, beta >= 0.5."""
    scale = beta * jnp.max(column_abs_sums(a))
    return scale * jnp.ones(a.shape[0], dtype=a.dtype)


def d_matrix_proposed(a: jnp.ndarray, k_s: jnp.ndarray) -> jnp.ndarray:
    """Eq. 22 — the paper's D.

    D_ii = (K_s)_ii + 0.5 sum_j |A_ji|          for i = 1 (first node)
    D_ii = 0.5 (K_s)_ii + 0.5 sum_j |A_ji|      otherwise

    Column sums of (K_A + K_B) then vanish except column 1 (= k_s1 > 0):
    only nodes 1 and n+1 carry a ground leg, exactly one "support".
    """
    colsum = column_abs_sums(a)
    d = 0.5 * k_s + 0.5 * colsum
    # first node gets the full K_s term -> acts as the single support
    return d.at[0].add(0.5 * k_s[0])


class Transformed2N(NamedTuple):
    """Result of the proposed 2n transform."""

    k_a: jnp.ndarray        # (n, n)  Eq. 15
    k_b: jnp.ndarray        # (n, n)  Eq. 16
    d: jnp.ndarray          # (n,)    diagonal of D
    k_s: jnp.ndarray        # (n,)    supply conductances, Eq. 13
    b_sign: jnp.ndarray     # (n,)    sign of b (selects +/- rail; 0 = NC)
    supply_v: float

    @property
    def n(self) -> int:
        return self.k_a.shape[0]

    def assembled(self) -> jnp.ndarray:
        """The circuit's DC operator  M = [[K_A + K_s, K_B], [K_B, K_A + K_s]].

        Moving the supply term of Eq. 14 to the left-hand side gives
        M {x; -x} = {b; -b};   (K_A + K_s) - K_B = A  recovers the
        original system.
        """
        k_ak = self.k_a + jnp.diag(self.k_s)
        top = jnp.concatenate([k_ak, self.k_b], axis=1)
        bot = jnp.concatenate([self.k_b, k_ak], axis=1)
        return jnp.concatenate([top, bot], axis=0)

    def rhs(self) -> jnp.ndarray:
        """{b; -b} = {K_s x_s; -K_s x_s}."""
        b = self.k_s * self.b_sign * self.supply_v
        return jnp.concatenate([b, -b])

    def negative_cell_conductances(self) -> jnp.ndarray:
        """diag(K_B) — positive entries need a negative-resistance cell.

        Eq. 26: K_Bii = -(1/2)(A_ii - K_sii - sum_{j!=i} |A_ji|) is the
        per-column deviation of (A - K_s) from diagonal dominance; at the
        support node Eq. 22's D adds the other half of K_s00.
        """
        return jnp.diagonal(self.k_b)

    def max_conductance(self) -> jnp.ndarray:
        """Max branch conductance of the transformed network.

        Branches are the off-diagonals of K_A/K_B plus |diag(K_B)|; the
        complexity studies (Figs. 12-14) show this — not n — controls
        settling time.
        """
        n = self.k_a.shape[0]
        off_a = jnp.abs(self.k_a - jnp.diag(jnp.diagonal(self.k_a)))
        off_b = jnp.abs(self.k_b - jnp.diag(jnp.diagonal(self.k_b)))
        return jnp.maximum(
            jnp.maximum(off_a.max(), off_b.max()),
            jnp.abs(jnp.diagonal(self.k_b)).max(),
        )


def transform_2n(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    d_policy: str = "proposed",
    beta: float = 0.5,
    params: CircuitParams = DEFAULT_PARAMS,
) -> Transformed2N:
    """Transform ``A x = b`` into the proposed 2n-unknown system.

    d_policy:
      * "proposed" — Eq. 22 (the paper's final design)
      * "scaled"   — Eq. 21 with scaling factor ``beta`` (Fig. 10 study)
      * "gremban"  — D = diag(A), K_s = 0 (the support-tree transform the
        paper compares against; does not preserve PD in general)
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    abs_a = jnp.abs(a)

    if d_policy == "gremban":
        k_s = jnp.zeros_like(b)
        d = jnp.diagonal(a)
    else:
        k_s = supply_conductance(b, params.supply_v)
        if d_policy == "proposed":
            d = d_matrix_proposed(a, k_s)
        elif d_policy == "scaled":
            d = d_matrix_scaled(a, beta)
        else:
            raise ValueError(f"unknown d_policy: {d_policy!r}")

    k_a = jnp.diag(d) + 0.5 * (a - abs_a) - jnp.diag(k_s)   # Eq. 15
    k_b = jnp.diag(d) - 0.5 * (a + abs_a)                   # Eq. 16
    return Transformed2N(
        k_a=k_a,
        k_b=k_b,
        d=d,
        k_s=k_s,
        b_sign=jnp.sign(b),
        supply_v=params.supply_v,
    )


def assemble_2n(k_a: jnp.ndarray, k_b: jnp.ndarray) -> jnp.ndarray:
    """[[K_A, K_B], [K_B, K_A]] (Eq. 14 left-hand block matrix)."""
    top = jnp.concatenate([k_a, k_b], axis=1)
    bot = jnp.concatenate([k_b, k_a], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def eigen_split(tr: Transformed2N) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 17-19: the transformed spectrum splits into

    spec(K_A - K_B) = spec(A - K_s)   and
    spec(K_A + K_B) = spec(2D - |A| - K_s).

    Returns eigenvalues of both blocks (of the *circuit* operator M,
    i.e. including the supply conductance K_s on the diagonal, so the
    first block's spectrum is exactly spec(A)).
    """
    k_ak = tr.k_a + jnp.diag(tr.k_s)
    lam_minus = jnp.linalg.eigvalsh(k_ak - tr.k_b)   # = spec(A)
    lam_plus = jnp.linalg.eigvalsh(k_ak + tr.k_b)    # = spec(2D - |A|)
    return lam_minus, lam_plus


def stability_condition(a: jnp.ndarray, k_s: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """Eq. 20 margin per node: D_ii - 0.5[(K_s)_ii + sum_j |A_ji|].

    >= 0 (with equality allowed when another column provides support)
    keeps (K_A + K_B) diagonally dominant hence PSD.
    """
    return d - 0.5 * (k_s + column_abs_sums(a))


def scale_system(
    tr: Transformed2N, alpha: float
) -> Transformed2N:
    """Eq. 27: scale every conductance by alpha (solution unchanged)."""
    return Transformed2N(
        k_a=tr.k_a * alpha,
        k_b=tr.k_b * alpha,
        d=tr.d * alpha,
        k_s=tr.k_s * alpha,
        b_sign=tr.b_sign,
        supply_v=tr.supply_v,
    )

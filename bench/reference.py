"""Plain float64 references for the benchmark's correctness comparison.

Nothing here imports the system under test: the references are written
from the semantics the configuration states, in straightforward numpy
and scipy, so that a fault in the program cannot hide in them.

* :func:`solve`, :func:`solve_many` — the delivered answer ``x`` of
  ``A x = b``, by a float64 LU solve on the host.
* :func:`circuit` — the paper's proposed 2n circuit (Sec. IV transform,
  Sec. II-B negative-resistance cells, behavioral op-amps and buffers)
  as the linear state-space ``dz/dt = M z + c``, one system at a time.
* :func:`settle_steps` — the forward-Euler settle sweep of that circuit
  in float64 with the configuration's step rule and settle band: the
  step count at which every unknown first lies within the band of the
  circuit's DC operating point, polled every ``check_every`` steps.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import scipy.linalg
import scipy.sparse as sp
import scipy.sparse.linalg as spla


def solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``x = A^-1 b`` in float64 (LU with partial pivoting)."""
    return scipy.linalg.solve(a, b, assume_a="gen")


def solve_many(a: np.ndarray, bs: np.ndarray) -> np.ndarray:
    """``x_k = A^-1 b_k`` for each row ``b_k`` of ``bs``, one LU of A."""
    lu = scipy.linalg.lu_factor(a)
    return scipy.linalg.lu_solve(lu, bs.T).T


def relative_residual(a: np.ndarray, b: np.ndarray, x: np.ndarray) -> float:
    """``||A x - b||_2 / ||b||_2`` in float64."""
    return float(np.linalg.norm(a @ x - b) / np.linalg.norm(b))


@dataclasses.dataclass(frozen=True)
class Circuit:
    """The hardware a configuration states (paper Table I, Sec. III-A)."""

    supply_v: float          # supply rails |x_s| [V]
    c_node: float            # parasitic node capacitance [F]
    c_switch: float          # analog-switch capacitance per element [F]
    gbw_hz: float            # op-amp gain-bandwidth [Hz]
    open_loop_gain: float    # op-amp DC gain [V/V]
    p2_hz: float             # op-amp second pole [Hz]
    c_in: float              # op-amp input capacitance per pin [F]
    settle_rtol: float       # settle band, relative to the DC state
    settle_atol: float       # settle band floor [V]
    dt_safety: float         # Euler step = dt_safety / max |M_ii|
    check_every: int         # steps between settle polls
    max_steps: int           # sweep budget


def circuit(a: np.ndarray, b: np.ndarray, hw: Circuit):
    """State-space ``(M, c)`` of the proposed 2n circuit for ``A x = b``.

    State layout: the 2n node voltages, then per unknown ``p`` the pair
    slot on nodes ``(p, n + p)`` — two buffers and two two-pole op-amps
    (integrator + output) — then one op-amp per ground cell.  Every pair
    slot carries its amps whether or not a cell is stamped there; an
    unstamped slot has zero cell conductance and loads no node.
    Returns ``(M, c)`` as a CSR matrix and a dense vector.
    """
    n = b.shape[0]
    nn = 2 * n
    # Sec. IV transform (Eqs. 13, 15, 16, 22)
    ks = np.abs(b) / hw.supply_v
    d = 0.5 * ks + 0.5 * np.abs(a).sum(axis=0)
    d[0] += 0.5 * ks[0]
    ka = np.diag(d) + 0.5 * (a - np.abs(a)) - np.diag(ks)
    kb = np.diag(d) - 0.5 * (a + np.abs(a))
    kak = ka + np.diag(ks)
    m_dc = np.block([[kak, kb], [kb, kak]])
    supply_g = np.concatenate([ks, ks])
    sign = np.sign(b)
    supply_v = hw.supply_v * np.concatenate([sign, -sign])

    # components: negative off-diagonals are resistors, positive ones
    # (only on the (p, n + p) pairs) negative-resistance cells; row sums
    # less the supply leg are ground legs, or ground cells where negative
    tol = max(np.abs(m_dc).max(), 1.0) * 1e-14
    iu, ju = np.triu_indices(nn, k=1)
    vals = m_dc[iu, ju]
    br = vals < -tol
    bi, bj, bg = iu[br], ju[br], -vals[br]
    cell = vals > tol
    if np.any(ju[cell] != iu[cell] + n):
        raise ValueError("a cell lies off the (p, n + p) pairs")
    pair_w = np.zeros(n)
    pair_w[iu[cell]] = vals[cell]
    gamma = m_dc.sum(axis=1) - supply_g
    gcell = np.nonzero(gamma < -tol)[0]
    gcell_w = -gamma[gcell]
    ground_g = np.where(gamma > tol, gamma, 0.0)

    # node capacitance: wiring, one switch per element circuit on the
    # node (cells and supply switches), op-amp input pins of live cells
    active = pair_w > 0
    elem = np.zeros(nn)
    elem[:n] += active
    elem[n:] += active
    elem[gcell] += 1.0
    elem += supply_g > 0
    cap = hw.c_node + hw.c_switch * elem
    cap[:n] += 2.0 * hw.c_in * active
    cap[n:] += 2.0 * hw.c_in * active
    cap[gcell] += hw.c_in

    n_g = gcell.shape[0]
    nz = nn + 6 * n + 2 * n_g
    rows: list[np.ndarray] = []
    cols: list[np.ndarray] = []
    data: list[np.ndarray] = []

    def stamp(r, c, v):
        r, c = np.broadcast_arrays(np.asarray(r), np.asarray(c))
        rows.append(r.ravel())
        cols.append(c.ravel())
        data.append(np.broadcast_to(np.asarray(v, dtype=np.float64), r.shape).ravel())

    # passive network (branches, ground legs, supplies), KCL / C
    node_diag = ground_g + supply_g
    np.add.at(node_diag, bi, bg)
    np.add.at(node_diag, bj, bg)
    stamp(np.arange(nn), np.arange(nn), -node_diag / cap)
    stamp(bi, bj, bg / cap[bi])
    stamp(bj, bi, bg / cap[bj])

    w_u = 2.0 * np.pi * hw.gbw_hz
    w_buf = w_u
    p2 = 2.0 * np.pi * hw.p2_hz
    inv_a0 = 1.0 / hw.open_loop_gain
    p = np.arange(n)
    near, far = p, p + n
    base = nn + 6 * p
    buf1, buf2 = base, base + 1
    a1_int, a1_out, a2_int, a2_out = base + 2, base + 3, base + 4, base + 5
    # buffers follow the far node of each amp's cell terminal
    stamp(buf1, far, w_buf)
    stamp(buf1, buf1, -w_buf)
    stamp(buf2, near, w_buf)
    stamp(buf2, buf2, -w_buf)
    # gain-2 stage: da_int/dt = w_u (v+ - (a_out + v_far_buffered)/2 - a_int/A0)
    for a_int, a_out, vplus, fb in ((a1_int, a1_out, near, buf1),
                                    (a2_int, a2_out, far, buf2)):
        stamp(a_int, vplus, w_u)
        stamp(a_int, a_out, -0.5 * w_u)
        stamp(a_int, fb, -0.5 * w_u)
        stamp(a_int, a_int, -w_u * inv_a0)
        stamp(a_out, a_int, p2)
        stamp(a_out, a_out, -p2)
    # cell current w (a_out - v) into each terminal node
    stamp(near, near, -pair_w / cap[near])
    stamp(near, a1_out, pair_w / cap[near])
    stamp(far, far, -pair_w / cap[far])
    stamp(far, a2_out, pair_w / cap[far])
    if n_g:
        g_int = nn + 6 * n + 2 * np.arange(n_g)
        g_out = g_int + 1
        stamp(g_int, gcell, w_u)
        stamp(g_int, g_out, -0.5 * w_u)
        stamp(g_int, g_int, -w_u * inv_a0)
        stamp(g_out, g_int, p2)
        stamp(g_out, g_out, -p2)
        stamp(gcell, gcell, -gcell_w / cap[gcell])
        stamp(gcell, g_out, gcell_w / cap[gcell])

    m = sp.csr_matrix(
        (np.concatenate(data), (np.concatenate(rows), np.concatenate(cols))),
        shape=(nz, nz),
    )
    m.sum_duplicates()
    m.eliminate_zeros()
    c = np.zeros(nz)
    c[:nn] = supply_g * supply_v / cap
    return m, c


def settle_steps(a_list, b_list, hw: Circuit) -> np.ndarray:
    """Forward-Euler settle steps of each system's circuit, float64.

    Integrates ``z <- z + dt (M z + c)`` from ``z = 0`` with
    ``dt = dt_safety / max_i |M_ii|`` and polls every ``check_every``
    steps whether every unknown lies within
    ``max(settle_rtol |x*|, settle_atol)`` of the DC operating point
    ``x*`` (the first n node voltages of ``-M^-1 c``).  Returns the step
    count of the first poll that passes, or ``max_steps``.
    """
    mats, consts, refs, tols = [], [], [], []
    for a, b in zip(a_list, b_list):
        m, c = circuit(np.asarray(a, np.float64), np.asarray(b, np.float64), hw)
        n = b.shape[0]
        z_star = spla.spsolve(m.tocsc(), -c)
        x_ref = z_star[:n]
        dt = hw.dt_safety / np.abs(m.diagonal()).max()
        mats.append(m * dt)
        consts.append(c * dt)
        refs.append(x_ref)
        tols.append(np.maximum(hw.settle_rtol * np.abs(x_ref), hw.settle_atol))
    n = refs[0].shape[0]
    big = sp.block_diag(mats, format="csr")
    c_all = np.concatenate(consts)
    sizes = [mm.shape[0] for mm in mats]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    node_idx = (offsets[:, None] + np.arange(n)[None, :])
    x_ref = np.stack(refs)
    tol = np.stack(tols)
    count = len(mats)
    steps = np.full(count, hw.max_steps, dtype=np.int64)
    done = np.zeros(count, dtype=bool)
    z = np.zeros(big.shape[0])
    taken = 0
    while taken < hw.max_steps and not done.all():
        chunk = min(hw.check_every, hw.max_steps - taken)
        for _ in range(chunk):
            z = z + (big @ z + c_all)
        taken += chunk
        ok = np.all(np.abs(z[node_idx] - x_ref) <= tol, axis=1)
        newly = ok & ~done
        steps[newly] = taken
        done |= newly
    return steps

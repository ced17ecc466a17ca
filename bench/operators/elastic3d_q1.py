"""PETSc KSP tutorial ex56's operator: 3-D linear elasticity on the unit
cube, ``ne`` trilinear (Q1) hexahedra per side, isotropic material
(Young's modulus ``E``, Poisson ratio ``nu``), element stiffness by 2x2x2
Gauss quadrature.  Nodes are ordered ``(ix * ny + iy) * nz + iz`` on the
``(ne + 1)^3`` grid, three unknowns (x, y, z displacement) per node,
interleaved.  As in ex56, an element whose centre lies within 0.25 of
the cube's centre is ``soft_alpha`` times as stiff (ex56's ``-alpha``,
1e-3 there by default; 1 makes the material homogeneous).
``dirichlet = "y0"`` holds every node of the y = 0 face: its rows and
columns are removed.  Scaled by ``conductance_scale`` siemens.

Unlike the stencil operators, the result is SPD but not an M-matrix:
about a third of its off-diagonals are positive and no row is
diagonally dominant.
"""

import itertools

import numpy as np

# the eight corners of the reference cube [-1, 1]^3, in the grid's
# (x, y, z) lexicographic order, and the 2x2x2 Gauss points
CORNERS = np.array(list(itertools.product((-1.0, 1.0), repeat=3)))
GAUSS = CORNERS / np.sqrt(3.0)


def material(e: float, nu: float) -> np.ndarray:
    """Isotropic elasticity in Voigt order (xx, yy, zz, yz, xz, xy)."""
    lam = e * nu / ((1.0 + nu) * (1.0 - 2.0 * nu))
    mu = e / (2.0 * (1.0 + nu))
    d = np.zeros((6, 6))
    d[:3, :3] = lam
    d[np.arange(3), np.arange(3)] += 2.0 * mu
    d[np.arange(3, 6), np.arange(3, 6)] = mu
    return d


def element_stiffness(h: float, e: float, nu: float) -> np.ndarray:
    """(24, 24) stiffness of one cube of side ``h``, unknowns ordered
    corner-major, displacement component fastest."""
    d = material(e, nu)
    ke = np.zeros((24, 24))
    for g in GAUSS:
        # d N_a / d xi_k at the Gauss point, (8, 3); the cube's Jacobian
        # is (h / 2) I, so physical gradients are (2 / h) times these
        grad = np.empty((8, 3))
        for k in range(3):
            others = [m for m in range(3) if m != k]
            grad[:, k] = CORNERS[:, k] / 8.0 * np.prod(
                1.0 + CORNERS[:, others] * g[others], axis=1)
        grad *= 2.0 / h
        bmat = np.zeros((6, 24))
        for a in range(8):
            gx, gy, gz = grad[a]
            c = 3 * a
            bmat[0, c] = gx
            bmat[1, c + 1] = gy
            bmat[2, c + 2] = gz
            bmat[3, c + 1], bmat[3, c + 2] = gz, gy
            bmat[4, c], bmat[4, c + 2] = gz, gx
            bmat[5, c], bmat[5, c + 1] = gy, gx
        ke += bmat.T @ d @ bmat * (h / 2.0) ** 3      # unit Gauss weights
    return 0.5 * (ke + ke.T)         # exactly symmetric, as assembled


def stiffness(ne: int, e: float, nu: float,
              soft_alpha: float = 1.0) -> tuple[np.ndarray, np.ndarray]:
    """The unconstrained stiffness of the ``ne^3`` mesh and its node
    coordinates ``(n_nodes, 3)``."""
    nn = ne + 1
    h = 1.0 / ne
    grid = np.arange(nn ** 3).reshape(nn, nn, nn)
    corner = [grid[dx: dx + ne, dy: dy + ne, dz: dz + ne].ravel()
              for dx, dy, dz in CORNERS.astype(int).clip(0)]
    nodes = np.stack(corner, axis=1)                        # (ne^3, 8)
    dofs = (3 * nodes[:, :, None] + np.arange(3)).reshape(-1, 24)
    centres = (np.stack(np.meshgrid(*[np.arange(ne)] * 3, indexing="ij"),
                        axis=-1).reshape(-1, 3) + 0.5) * h
    soft = np.linalg.norm(centres - 0.5, axis=1) < 0.25
    alpha = np.where(soft, soft_alpha, 1.0)                 # (ne^3,)
    k = np.zeros((3 * nn ** 3, 3 * nn ** 3))
    np.add.at(k, (dofs[:, :, None], dofs[:, None, :]),
              alpha[:, None, None] * element_stiffness(h, e, nu)[None])
    coords = np.stack(np.meshgrid(*[np.arange(nn) * h] * 3, indexing="ij"),
                      axis=-1).reshape(-1, 3)
    return k, coords


def free_dofs(ne: int, dirichlet: str) -> np.ndarray:
    """The unknowns left after the Dirichlet face's nodes are held."""
    if dirichlet != "y0":
        raise ValueError(f"unknown dirichlet face {dirichlet!r}")
    nn = ne + 1
    iy = np.arange(nn ** 3) // nn % nn
    return (3 * np.nonzero(iy > 0)[0][:, None] + np.arange(3)).ravel()


def build(spec: dict) -> np.ndarray:
    ne = int(spec["ne"])
    k, _ = stiffness(ne, float(spec["E"]), float(spec["nu"]),
                     float(spec["soft_alpha"]))
    keep = free_dofs(ne, spec["dirichlet"])
    return k[np.ix_(keep, keep)] * spec["conductance_scale"]

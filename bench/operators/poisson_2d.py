"""The 2-D model problem: the 5-point Laplacian on an nx-by-ny interior
grid with Dirichlet boundaries (diagonal 4, each of the up to four
neighbours -1), unknowns ordered ``idx(i, j) = i * ny + j``, scaled by
``conductance_scale`` siemens.  The same matrix as
``pyamg.gallery.poisson((nx, ny))`` and the assembly of PETSc's KSP
tutorial ex2."""

import numpy as np


def build(spec: dict) -> np.ndarray:
    nx, ny = int(spec["nx"]), int(spec["ny"])
    n = nx * ny
    i = np.repeat(np.arange(nx), ny)
    j = np.tile(np.arange(ny), nx)
    k = i * ny + j
    east = i < nx - 1
    north = j < ny - 1
    src = np.concatenate([k[east], k[north]])
    dst = np.concatenate([k[east] + ny, k[north] + 1])
    a = np.zeros((n, n))
    a[np.concatenate([src, dst]), np.concatenate([dst, src])] = -1.0
    a[np.arange(n), np.arange(n)] = 4.0
    return a * spec["conductance_scale"]

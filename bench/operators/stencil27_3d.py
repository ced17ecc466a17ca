"""HPCG's operator: the 27-point stencil on an nx-by-ny-by-nz grid,
``diagonal`` on the diagonal and ``off_diagonal`` for each of the up to
26 neighbours present, ``idx = (ix * ny + iy) * nz + iz``, scaled by
``conductance_scale`` siemens."""

import numpy as np


def build(spec: dict) -> np.ndarray:
    nx, ny, nz = int(spec["nx"]), int(spec["ny"]), int(spec["nz"])
    n = nx * ny * nz
    grid = np.arange(n).reshape(nx, ny, nz)
    a = np.zeros((n, n))
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                if dx == dy == dz == 0:
                    continue
                sx = slice(max(0, -dx), nx - max(0, dx))
                sy = slice(max(0, -dy), ny - max(0, dy))
                sz = slice(max(0, -dz), nz - max(0, dz))
                tx = slice(max(0, dx), nx - max(0, -dx))
                ty = slice(max(0, dy), ny - max(0, -dy))
                tz = slice(max(0, dz), nz - max(0, -dz))
                a[grid[sx, sy, sz].ravel(), grid[tx, ty, tz].ravel()] = \
                    spec["off_diagonal"]
    a[np.arange(n), np.arange(n)] = spec["diagonal"]
    return a * spec["conductance_scale"]

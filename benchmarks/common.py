"""Shared benchmark harness utilities.

Every fig*/table* module exposes ``run(full: bool) -> list[dict]`` and
prints CSV rows ``name,metric,value``; ``benchmarks.run`` orchestrates.
Default sizes are reduced for CPU wall-time; ``--full`` reproduces the
paper-scale sweeps.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import numpy as np

US = 1e-6

# the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed path inside the checkout (listed in .gitignore) — the
# path is part of the cache key, so it must never move between runs
_REPO_CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself and
    no other directory is configured); otherwise the cache lives in
    ``<repo>/.jax_cache``.  Called by the benchmark and smoke entry
    points only — never on library import, never by tests.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def emit(rows: list[dict], stream_print=print) -> None:
    for r in rows:
        name = r.pop("name")
        for k, v in r.items():
            if isinstance(v, float):
                stream_print(f"{name},{k},{v:.6g}")
            else:
                stream_print(f"{name},{k},{v}")


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def stats(xs) -> dict:
    xs = np.asarray([x for x in xs if np.isfinite(x)], dtype=np.float64)
    if xs.size == 0:
        return {"median": float("nan"), "p90": float("nan"),
                "max": float("nan"), "n": 0}
    return {
        "median": float(np.median(xs)),
        "p90": float(np.percentile(xs, 90)),
        "max": float(xs.max()),
        "n": int(xs.size),
    }


def gen_systems(seed: int, n: int, count: int, density: float = 1.0):
    """Paper protocol systems: eigenvalues in [10, 1000] uS,
    x ~ U[-0.5, 0.5], b = A x."""
    from repro.data.spd import random_spd, random_rhs_from_solution

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        a = random_spd(rng, n, density=density)
        x, b = random_rhs_from_solution(rng, a)
        out.append((a, x, b))
    return out

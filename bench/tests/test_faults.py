"""With the timed path broken underneath, ``correct`` comes out false.

One test per fault the cells can have: an answer altered where the
service produces it; a step that returns its state unchanged (the DC
solve, and the settle sweep); half of a micro-batch left out, its
answers copied from the rest.  No cell runs on more than one chip, so
there is no exchange between chips to leave out.
"""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SETTLE = [c for c in CELLS if harness.load_cell(c).traffic["submit"].get("compute_settling")]


def rehearse(name):
    return harness.run(harness.load_cell(name), seed=4242, seconds=1.0,
                       trace=False, rehearse=True)


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered(name, monkeypatch):
    from repro.serving import solve_service

    original = solve_service.SolveResult

    def altered(**kw):
        kw["x"] = kw["x"] * (1.0 + 1e-6)
        return original(**kw)

    monkeypatch.setattr(solve_service, "SolveResult", altered)
    assert not rehearse(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_dc_solve_returns_state_unchanged(name, monkeypatch):
    from repro.core import engine

    def unchanged(m, c):
        return jnp.zeros_like(c)

    monkeypatch.setattr(engine, "_dc_solve_vmapped", unchanged)
    monkeypatch.setattr(engine, "_dc_solve_vmapped_donated", unchanged)
    assert not rehearse(name)["correct"]


@pytest.mark.parametrize("name", SETTLE)
def test_sweep_returns_state_unchanged(name, monkeypatch):
    from repro.kernels import ops

    def unchanged(m, z, c, **kw):
        return z, jnp.zeros(z.shape[0], dtype=jnp.float32)

    monkeypatch.setattr(ops, "transient_sweep", unchanged)
    monkeypatch.setattr(ops, "ell_transient_sweep",
                        lambda idx, w, z, c, **kw: unchanged(None, z, c))
    assert not rehearse(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_half_the_batch_left_out(name, monkeypatch):
    from repro.serving.solve_service import SolveService

    original = SolveService._unpack_micro_batch

    def half(self, pipe, tickets, batch, injected=None):
        x = np.array(batch.x)
        keep = max(1, x.shape[0] // 2)
        x[keep:] = x[:keep][np.arange(x.shape[0] - keep) % keep]
        batch.x = x
        return original(self, pipe, tickets, batch, injected)

    monkeypatch.setattr(SolveService, "_unpack_micro_batch", half)
    assert not rehearse(name)["correct"]

"""The plain references agree with the program where both are sound.

These tests read the program only to check the references: the circuit
state-space against the engine's dense assembly, and the float64 settle
steps against the engine's float32 Euler sweep, at small sizes on the
CPU.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import generators, reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def load(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    (system,) = generators.load_systems(cfg, rehearse=True)
    bs = np.stack([b for _, b in generators.TicketStream([system], 3, 20260).next_round()])
    return cfg, system.a, bs


def program_state_space(a, b):
    from repro.core import engine
    from repro.core.solver import _build_nets
    from repro.core.specs import DEFAULT_PARAMS, OPAMPS

    nets = _build_nets(a[None], b[None], "analog_2n", d_policy="proposed",
                       beta=0.5, alpha=1.0, params=DEFAULT_PARAMS)
    pat = engine.pattern_union(nets, OPAMPS["AD712"])
    bss = engine.assemble_batch(nets, OPAMPS["AD712"], pattern=pat)
    return nets, pat, bss


@pytest.mark.parametrize("name", sorted(p.stem for p in CONFIGS.glob("*.json")))
def test_circuit_matches_engine_assembly(name):
    cfg, a, bs = load(name)
    hw = reference.Circuit(**cfg["circuit"])
    for b in bs:
        m, c = reference.circuit(a, b, hw)
        _, _, bss = program_state_space(a, b)
        assert m.shape == bss.m.shape[1:]
        scale = np.abs(bss.m[0]).max(axis=1, keepdims=True)
        np.testing.assert_allclose(m.toarray() / scale, bss.m[0] / scale,
                                   rtol=0, atol=1e-12)
        np.testing.assert_allclose(c, bss.c[0], rtol=1e-12, atol=0)


def test_settle_steps_match_engine_sweep():
    from repro.core import engine
    from repro.core.specs import OPAMPS

    cfg, a, bs = load("poisson5_16x16")
    hw = reference.Circuit(**cfg["circuit"])
    ref = reference.settle_steps([a] * len(bs), list(bs), hw)
    program = []
    for b in bs:
        nets, pat, _ = program_state_space(a, b)
        tr = engine.transient_batch(nets, OPAMPS["AD712"], method="euler",
                                    pattern=pat)
        program.append(int(tr.settle_steps[0]))
    assert program == ref.tolist()
    assert np.all(ref < hw.max_steps)


def test_reference_solve_and_residual():
    _, a, bs = load("hpcg27_8cube")
    x = reference.solve(a, bs[0])
    assert reference.relative_residual(a, bs[0], x) < 1e-14
    np.testing.assert_allclose(reference.solve_many(a, bs)[0], x, rtol=1e-12)

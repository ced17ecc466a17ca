"""PETSc ex56's elasticity operator and load, and the service against the
plain references on it, on the CPU.

The operator checks are independent of the generator's own arithmetic:
a sound Q1 assembly maps every rigid-body motion to zero force.  The
service check runs the cell's rehearsal size through ``SolveService``
(euler settle, float32 sweep) and compares with ``bench.reference``.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import generators, reference
from bench.operators import elastic3d_q1
from bench.rhs import nodal_load

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "elastic3d_q1_4cube.json").read_text())
(SYSTEM,) = CONFIG["systems"]


def operator(ne):
    return generators.operator({**SYSTEM["operator"], "ne": ne})


def rigid_body_modes(coords):
    """Three translations and three rotations, as (6, 3 * n_nodes)."""
    x, y, z = coords.T
    zero, one = np.zeros_like(x), np.ones_like(x)
    fields = [(one, zero, zero), (zero, one, zero), (zero, zero, one),
              (-y, x, zero), (zero, -z, y), (z, zero, -x)]
    return np.stack([np.stack(f, axis=1).ravel() for f in fields])


@pytest.mark.parametrize("ne", [1, 2, 3])
def test_stiffness_is_symmetric_and_rigid_body_free(ne):
    k, coords = elastic3d_q1.stiffness(ne, SYSTEM["operator"]["E"],
                                       SYSTEM["operator"]["nu"])
    scale = np.abs(k).max()
    assert np.abs(k - k.T).max() <= 1e-14 * scale
    modes = rigid_body_modes(coords)
    force = k @ modes.T
    assert np.abs(force).max() <= 1e-12 * scale * np.abs(modes).max()
    # and nothing else is free: the six modes span the null space
    assert np.sum(np.linalg.eigvalsh(k) < 1e-10 * scale) == 6


def test_soft_inclusion_scales_the_central_elements():
    """ex56's inclusion: at ne = 4 the eight elements around the cube's
    centre (and only they) carry ``soft_alpha``, so the centre node's
    own block scales by it and a corner node's does not change."""
    e, nu = SYSTEM["operator"]["E"], SYSTEM["operator"]["nu"]
    hard, _ = elastic3d_q1.stiffness(4, e, nu, 1.0)
    soft, _ = elastic3d_q1.stiffness(4, e, nu, 1e-3)
    centre = 3 * ((2 * 5 + 2) * 5 + 2) + np.arange(3)
    np.testing.assert_allclose(soft[np.ix_(centre, centre)],
                               1e-3 * hard[np.ix_(centre, centre)], rtol=1e-12,
                               atol=1e-15 * np.abs(hard).max())
    np.testing.assert_array_equal(soft[:3, :3], hard[:3, :3])
    # the soft mesh keeps the six rigid-body modes and no other
    scale = np.abs(soft).max()
    assert np.sum(np.linalg.eigvalsh(soft) < 1e-10 * scale) == 6


@pytest.mark.parametrize("ne", [2, 3, 4])
def test_reduced_operator_is_spd_and_not_an_m_matrix(ne):
    a = operator(ne)
    n = 3 * (ne + 1) ** 2 * ne
    assert a.shape == (n, n)
    np.testing.assert_array_equal(a, a.T)
    assert np.linalg.eigvalsh(a)[0] > 0
    off = a - np.diag(np.diag(a))
    assert (np.diag(a) / np.abs(off).sum(axis=1)).min() < 0.5
    assert (off > 0).any()
    if ne == SYSTEM["operator"]["ne"]:
        # the configuration's scale puts the largest diagonal at 400 uS
        assert np.diag(a).max() == pytest.approx(4e-4)


def test_nodal_load_draws_the_same_sizes_on_every_seed():
    a = operator(2)
    spec = SYSTEM["rhs"]
    draws = [nodal_load.draw(np.random.default_rng(seed), a, spec, 5)
             for seed in (1, 2, 2**31 + 7)]
    assert {d.shape for d in draws} == {(5, a.shape[0])}
    load = np.tile(spec["load"], a.shape[0] // 3)
    for d in draws:
        # one force per node, the same on every node, a positive amplitude
        assert np.all(d[:, :1] > 0)
        np.testing.assert_allclose(d / d[:, :1], np.broadcast_to(
            load / load[0], d.shape), rtol=1e-15)
        x = np.linalg.solve(a, d.T).T
        peak = np.abs(x).max(axis=1)
        assert np.all(peak <= spec["x_max"] * (1 + 1e-12))
        assert np.all(peak >= spec["lo"] * spec["x_max"] * (1 - 1e-12))


def test_service_matches_the_references_at_rehearsal_size():
    from repro.serving import SolveService

    (system,) = generators.load_systems(CONFIG, rehearse=True)
    bs = generators.TicketStream([system], 4, 2**31 + 99).next_round()
    bs = np.stack([b for _, b in bs])
    svc = SolveService(batch_slots=4, refine=True)
    rids = [svc.submit(system.a, b, method=system.method, opamp=system.opamp,
                       compute_settling=True, settle_method="euler",
                       sweep_dtype="float32") for b in bs]
    out = svc.drain()
    xs = np.stack([np.asarray(out[r].x, dtype=np.float64) for r in rids])
    want = reference.solve_many(system.a, bs)
    for x, b, w in zip(xs, bs, want):
        assert reference.relative_residual(system.a, b, x) <= 1e-10
        assert np.abs(x - w).max() <= 1e-9 * np.abs(w).max()
    hw = reference.Circuit(**CONFIG["circuit"])
    ref_steps = reference.settle_steps([system.a] * len(bs), list(bs), hw)
    steps = np.array([out[r].info["settle_steps"] for r in rids])
    assert np.all(np.abs(steps - ref_steps) <= hw.check_every)
    assert all(out[r].stable for r in rids)

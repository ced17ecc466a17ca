"""The service's circuit counters: ``neg_cells`` and ``cross_branches``
say which half of the 2n network a workload stamps, and
``settle_steps_swept`` how many Euler steps the device ran; the
``core.settle_prep`` span times the host work before the first chunk.

An M-matrix (the 2-D Poisson operator) stamps no crosspoint branch
between the ``x`` and ``-x`` halves; the 3-D elasticity operator of
PETSc's ex56 is SPD but not an M-matrix and stamps them, so a change
that assumed an M-matrix fails here."""

import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import transform as T
from repro.serving.solve_service import SolveService

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SLOTS = 2


def _operator(kind):
    from bench.operators import elastic3d_q1, poisson_2d

    if kind == "poisson":
        return poisson_2d.build({"nx": 3, "ny": 3, "conductance_scale": 1e-4})
    return elastic3d_q1.build({"ne": 1, "E": 1.0, "nu": 0.25, "soft_alpha": 1.0,
                               "dirichlet": "y0", "conductance_scale": 9e-4})


@pytest.fixture(scope="module", params=["poisson", "elastic"])
def settled(request):
    """Three tickets (two micro-batches) of one operator through the
    euler settle; returns the operator, the right-hand sides and the
    service's stats."""
    a = _operator(request.param)
    rng = np.random.default_rng(11)
    bs = rng.uniform(0.5, 1.0, size=(3, a.shape[0])) @ a
    svc = SolveService(batch_slots=SLOTS)
    rids = [svc.submit(a, b, compute_settling=True, settle_method="euler")
            for b in bs]
    out = svc.drain()
    assert all(r.stable for r in out.values())
    steps = [out[rid].info["settle_steps"] for rid in rids]
    return request.param, a, bs, {**svc.stats, "ticket_steps": steps}


def test_cross_branches_only_off_the_m_matrix_class(settled):
    kind, a, bs, st = settled
    if kind == "poisson":
        assert st["cross_branches"] == 0
    else:
        assert st["cross_branches"] > 0


def test_neg_cells_are_the_positive_kb_diagonals(settled):
    _, a, bs, st = settled
    want = sum(int(np.sum(np.diagonal(np.asarray(T.transform_2n(a, b).k_b)) > 0))
               for b in bs)
    assert want > 0
    assert st["neg_cells"] == want


def test_settle_steps_swept_is_chunks_times_chunk_length(settled):
    _, _, _, st = settled
    spans = st["spans"]
    assert spans["core.sweep_chunk"]["count"] >= 2
    assert st["settle_steps_swept"] == 50 * spans["core.sweep_chunk"]["count"]


def test_settle_prep_once_per_settled_micro_batch(settled):
    _, _, _, st = settled
    spans = st["spans"]
    assert spans["serve.dispatch"]["count"] == 2
    assert spans["core.settle"]["count"] == 2
    assert spans["core.settle_prep"]["count"] == 2
    # the upload nests inside the prep
    assert spans["core.settle_prep"]["s"] > 0


def test_dc_only_drain_sweeps_nothing():
    a = _operator("elastic")
    svc = SolveService(batch_slots=SLOTS)
    svc.submit(a, a @ np.linspace(0.5, 1.0, a.shape[0]))
    svc.drain()
    st = svc.stats
    assert st["settle_steps_swept"] == 0
    assert "core.settle_prep" not in st["spans"]
    assert st["cross_branches"] > 0 and st["neg_cells"] > 0


def test_settle_steps_swept_is_each_micro_batch_slowest_ticket(settled):
    """The sweep of a micro-batch runs until its slowest system settles,
    so the steps swept are the sum, over micro-batches, of the largest
    settle step of each."""
    _, _, _, st = settled
    steps = st["ticket_steps"]
    want = sum(max(steps[k: k + SLOTS]) for k in range(0, len(steps), SLOTS))
    assert st["settle_steps_swept"] == want

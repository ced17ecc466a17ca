"""Batched engine: batch/single parity, Pallas sweep kernels, stamp cache."""

import numpy as np
import pytest

from repro.core import engine
from repro.core.network import build_preliminary, build_proposed
from repro.core.operating_point import DEFAULT_NONIDEAL, operating_point
from repro.core.solver import solve, solve_batch
from repro.core.transient import lti_transient
from repro.data.spd import random_sdd, random_spd, random_rhs_from_solution


def _batch(seed, n, count, *, with_non_pd=False, with_sdd=False):
    """Stacked paper-protocol systems, optionally salted with edge cases."""
    rng = np.random.default_rng(seed)
    a_list, x_list, b_list = [], [], []
    for _ in range(count):
        a = random_spd(rng, n)
        x, b = random_rhs_from_solution(rng, a)
        a_list.append(a), x_list.append(x), b_list.append(b)
    if with_non_pd:
        # Fig. 8 protocol: flipping the sign destabilizes the circuit
        a_list[1], b_list[1] = -a_list[1], -b_list[1]
        x_list[1] = np.linalg.solve(a_list[1], b_list[1])
    if with_sdd:
        a_sdd = random_sdd(rng, n)
        x_sdd, b_sdd = random_rhs_from_solution(rng, a_sdd)
        a_list[2], x_list[2], b_list[2] = a_sdd, x_sdd, b_sdd
    return np.stack(a_list), np.stack(x_list), np.stack(b_list)


@pytest.mark.parametrize("method", ["analog_2n", "analog_n"])
def test_solve_batch_matches_solve(method):
    """Acceptance: a 64-system n=20 batch matches per-system solve to
    1e-8 on x (and on stability/settle_time), non-PD system included."""
    count = 64 if method == "analog_2n" else 16   # analog_n is O(n^2) states
    a, x, b = _batch(7, 20, count, with_non_pd=True, with_sdd=True)
    batch = solve_batch(
        a, b, method=method, x_ref=x, compute_settling=True,
        settle_method="eig",
    )
    assert len(batch) == count
    for k in range(count):
        single = solve(
            a[k], b[k], method=method, x_ref=x[k], compute_settling=True
        )
        np.testing.assert_allclose(
            batch.x[k], single.x, rtol=0.0, atol=1e-8
        )
        assert bool(batch.stable[k]) == single.stable
        st_b, st_s = float(batch.settle_time[k]), float(single.settle_time)
        if np.isfinite(st_s):
            np.testing.assert_allclose(st_b, st_s, rtol=1e-6)
        else:
            assert not np.isfinite(st_b)
        np.testing.assert_allclose(
            batch.info["err_fullscale"][k],
            single.info["err_fullscale"],
            rtol=1e-6, atol=1e-12,
        )


def test_solve_batch_flags_non_pd_unstable():
    a, x, b = _batch(11, 10, 4, with_non_pd=True)
    batch = solve_batch(a, b, method="analog_2n", compute_settling=True)
    assert not batch.stable[1]
    assert batch.settle_time[1] == np.inf
    assert np.all(batch.stable[[0, 2, 3]])
    assert np.all(np.isfinite(batch.settle_time[[0, 2, 3]]))


def test_operating_point_batch_nonideal_parity():
    """The hardware error model (quantization/offsets) draws per system
    exactly as the single path does."""
    from repro.core.operating_point import operating_point_batch

    a, x, b = _batch(13, 12, 6)
    nets = [build_proposed(a[k], b[k]) for k in range(6)]
    op_b = operating_point_batch(
        nets, nonideal=DEFAULT_NONIDEAL, x_ref=x
    )
    for k in range(6):
        op_s = operating_point(nets[k], nonideal=DEFAULT_NONIDEAL, x_ref=x[k])
        np.testing.assert_allclose(op_b.x[k], op_s.x, rtol=0.0, atol=1e-9)
        assert bool(op_b.amp_saturated[k]) == op_s.amp_saturated
        np.testing.assert_allclose(
            float(op_b.err_fullscale[k]), op_s.err_fullscale, rtol=1e-6
        )


@pytest.mark.parametrize("builder", [build_proposed, build_preliminary])
def test_dc_solve_refines_f32_lu_to_f64(builder):
    """The device DC solve (f32 LU + f64 refinement) matches host f64
    ``np.linalg.solve(M, -c)`` far inside the service's 1e-9 parity,
    with no system left for the host repair path."""
    a, x, b = _batch(23, 16, 6, with_sdd=True)
    bss = engine.assemble_batch([builder(a[k], b[k]) for k in range(6)])
    resolves = engine.DC_STATS["host_resolves"]
    z = engine.dc_solve_batch(bss)
    ref = np.linalg.solve(bss.m, -bss.c[..., None])[..., 0]
    rel = np.max(np.abs(z - ref), axis=1) / np.max(np.abs(ref), axis=1)
    assert np.max(rel) <= 1e-12
    assert engine.DC_STATS["host_resolves"] == resolves


def test_dc_solve_counts_host_resolves_of_singular_systems():
    """A singular operator cannot refine: its row comes back non-finite
    from the device, is re-solved with the tiny leakage on the host and
    counted; its batch-mates keep their fp64 device result."""
    import dataclasses

    a, x, b = _batch(29, 8, 3)
    bss = engine.assemble_batch([build_proposed(a[k], b[k]) for k in range(3)])
    m = bss.m.copy()
    m[1, :, 0] = 0.0                     # state 0 of system 1 floats
    singular = dataclasses.replace(bss, m=m)
    resolves = engine.DC_STATS["host_resolves"]
    z = engine.dc_solve_batch(singular)
    assert engine.DC_STATS["host_resolves"] == resolves + 1
    assert np.all(np.isfinite(z))
    ref = np.linalg.solve(bss.m[[0, 2]], -bss.c[[0, 2], :, None])[..., 0]
    rel = np.max(np.abs(z[[0, 2]] - ref), axis=1) / np.max(np.abs(ref), axis=1)
    assert np.max(rel) <= 1e-12


def _dc_nets(seed, n, count, *, builder=build_proposed, density=1.0):
    rng = np.random.default_rng(seed)
    nets = []
    for _ in range(count):
        a = random_spd(rng, n, density=density)
        _x, b = random_rhs_from_solution(rng, a)
        nets.append(builder(a, b))
    return nets


def _rel_rows(z, ref):
    return np.max(np.abs(z - ref), axis=1) / np.max(np.abs(ref), axis=1)


@pytest.mark.parametrize(
    "builder, v_os, buffers",
    [
        (build_proposed, False, True),
        (build_preliminary, False, True),
        (build_proposed, True, True),
        (build_preliminary, True, True),
        (build_proposed, False, False),
    ],
    ids=["proposed", "preliminary", "proposed-v_os", "preliminary-v_os",
         "proposed-no-buffers"],
)
def test_device_assembly_matches_host_assembly(builder, v_os, buffers):
    """The DC operator built on the device is the host dense operator to
    f64 round-off, and the DC point solved from it matches the host-
    assembled solve; operating_point_batch (which takes the device path)
    matches the host-assembled dc_solve_batch to 1e-12 relative."""
    import jax

    from repro.core.operating_point import (
        NonIdealities, apply_nonidealities, draw_offsets,
        operating_point_batch,
    )
    from repro.core.specs import AD712

    nets = _dc_nets(43, 12, 4, builder=builder)
    rng = np.random.default_rng(5)
    offs = (
        [rng.uniform(-1e-3, 1e-3, size=net.n_amps) for net in nets]
        if v_os else None
    )
    host = engine.assemble_batch(nets, v_os=offs, buffers=buffers)
    dev = engine.assemble_batch_device(nets, v_os=offs, buffers=buffers)
    assert isinstance(dev.m, jax.Array) and isinstance(dev.c, jax.Array)
    scale = np.abs(host.m).max()
    np.testing.assert_allclose(np.asarray(dev.m), host.m, rtol=0.0,
                               atol=1e-12 * scale)
    np.testing.assert_allclose(np.asarray(dev.c), host.c, rtol=1e-12)
    assert np.array_equal(dev.amp_active, host.amp_active)
    assert _rel_rows(engine.dc_solve_batch(dev),
                     engine.dc_solve_batch(host)).max() <= 1e-12

    if buffers:
        # the operating-point path: its error model, then the device
        # assembly, against the same error model assembled on the host
        ni = DEFAULT_NONIDEAL if v_os else NonIdealities(offset_mode="none")
        op = operating_point_batch(nets, nonideal=ni)
        nets_ni = [apply_nonidealities(net, ni) for net in nets]
        draws = [draw_offsets(AD712, net.n_amps, ni.offset_mode, ni.seed)
                 for net in nets_ni]
        z = engine.dc_solve_batch(
            engine.assemble_batch(nets_ni, AD712, v_os=draws)
        )
        assert _rel_rows(op.x, z[:, : op.x.shape[1]]).max() <= 1e-12


def test_device_assembly_sums_duplicate_stamps():
    """Two branches between one node pair stamp one operator entry: the
    device densify sums them as the host scatter-add does."""
    import dataclasses

    nets = [
        dataclasses.replace(
            net,
            branch_i=np.concatenate([net.branch_i, net.branch_i[:5]]),
            branch_j=np.concatenate([net.branch_j, net.branch_j[:5]]),
            branch_g=np.concatenate([net.branch_g, net.branch_g[:5]]),
        )
        for net in _dc_nets(53, 10, 3)
    ]
    host = engine.assemble_batch(nets)
    dev = engine.assemble_batch_device(nets)
    np.testing.assert_allclose(np.asarray(dev.m), host.m, rtol=0.0,
                               atol=1e-12 * np.abs(host.m).max())


def test_operating_point_batch_never_materializes_dense(monkeypatch):
    """Shape spy: the DC operating-point path builds no (B, nz, nz)
    host array and never calls the host dense assembly; one
    device_assembled count per micro-batch."""
    from repro.core.operating_point import (
        IDEAL, operating_point_batch_submit,
    )

    nets = _dc_nets(47, 12, 3)
    pat = engine.pattern_union(nets)
    nz = pat.n_states
    forbidden = []

    def spy(fn):
        def wrapped(shape, *a, **kw):
            s = tuple(shape) if isinstance(shape, (tuple, list)) else (shape,)
            if len(s) == 3 and s[1] >= nz and s[2] >= nz:
                forbidden.append(s)
            return fn(shape, *a, **kw)
        return wrapped

    for name in ("zeros", "empty", "full"):
        monkeypatch.setattr(np, name, spy(getattr(np, name)))
    monkeypatch.setattr(
        engine, "assemble_batch",
        lambda *a, **kw: (_ for _ in ()).throw(
            AssertionError("host dense assembly on the DC path")),
    )

    before = dict(engine.DC_STATS)
    ops = [
        operating_point_batch_submit(nets, nonideal=IDEAL, pattern=pat)
        for _ in range(2)
    ]
    xs = [op.wait().x for op in ops]
    assert forbidden == []
    assert engine.DC_STATS["device_assembled"] == before["device_assembled"] + 2
    assert engine.DC_STATS["host_resolves"] == before["host_resolves"]
    np.testing.assert_array_equal(xs[0], xs[1])
    assert np.all(np.isfinite(xs[0]))


def _largest_row_width(nets, pat):
    """The ELL width before its rounding: 1 + C + the largest row degree."""
    vals = engine._gather_batch_values(nets, pat, None)
    c_max = engine._node_cell_layout(pat)[3]
    return engine._branch_slots(
        pat.n_states, vals.br_i, vals.br_j, vals.n_br, c_max
    )[1]


def test_device_assembly_reuses_executables_and_repairs_on_host():
    """A second micro-batch with another operator of the same n (fewer
    branches and a smaller largest row degree, in the same power-of-two
    and multiple-of-8 classes) compiles nothing; a singular system in a
    device-assembled batch is re-solved on the host and counted, and its
    batch-mates keep their device result."""
    import dataclasses

    from repro.analysis.runtime import CompileWatch
    from repro.core.operating_point import IDEAL, operating_point_batch

    nets1 = _dc_nets(3, 12, 4, density=0.9)
    nets2 = _dc_nets(3, 12, 4, density=0.4)
    n_br = [max(net.n_branches for net in nets) for nets in (nets1, nets2)]
    assert n_br[0] != n_br[1]
    pat = engine.pattern_union(nets1 + nets2)
    widths = [_largest_row_width(nets, pat) for nets in (nets1, nets2)]
    assert widths[0] != widths[1]
    operating_point_batch(nets1, nonideal=IDEAL, pattern=pat)   # warm
    with CompileWatch() as watch:
        op2 = operating_point_batch(nets2, nonideal=IDEAL, pattern=pat)
    assert watch.count == 0, watch.names
    assert np.all(np.isfinite(op2.x))

    dev = engine.assemble_batch_device(nets1, pattern=pat)
    singular = dataclasses.replace(dev, m=dev.m.at[1, :, 0].set(0.0))
    resolves = engine.DC_STATS["host_resolves"]
    z = engine.dc_solve_batch(singular)
    assert engine.DC_STATS["host_resolves"] == resolves + 1
    assert np.all(np.isfinite(z))
    host = engine.assemble_batch(nets1, pattern=pat)
    keep = [0, 2, 3]
    ref = np.linalg.solve(host.m[keep], -host.c[keep, :, None])[..., 0]
    assert _rel_rows(z[keep], ref).max() <= 1e-12


def test_settle_drain_frees_each_dc_operator_at_its_harvest(monkeypatch):
    """A settle drain of three micro-batches: the deferred finishes run
    once every DC phase is harvested, and by then no (B, nz, nz) device
    operator is live (each handle lets its operator go at the harvest)."""
    import jax

    from repro.serving.solve_service import SolveService

    rng = np.random.default_rng(19)
    a = random_spd(rng, 8)
    live = []
    sweep = engine.transient_batch

    def spy(nets, *args, **kw):
        live.append([
            x.shape for x in jax.live_arrays()
            if x.ndim == 3 and x.shape[1] == x.shape[2] > 8
        ])
        return sweep(nets, *args, **kw)

    monkeypatch.setattr(engine, "transient_batch", spy)
    svc = SolveService(batch_slots=2)
    for b in rng.uniform(0.5, 1.0, size=(5, 8)) @ a:
        svc.submit(a, b, compute_settling=True, settle_method="euler")
    out = svc.drain()
    assert len(out) == 5 and all(r.stable for r in out.values())
    assert svc.stats["device_assembled"] == 3
    assert live == [[], [], []]


def test_pattern_cache_reused_across_batches():
    """Proposed-design patterns depend only on (n, design)."""
    a1, x1, b1 = _batch(17, 8, 3)
    a2, x2, b2 = _batch(19, 8, 5)
    nets1 = [build_proposed(a1[k], b1[k]) for k in range(3)]
    nets2 = [build_proposed(a2[k], b2[k]) for k in range(5)]
    p1 = engine.pattern_union(nets1)
    p2 = engine.pattern_union(nets2)
    assert p1 is p2          # cache hit: same object
    assert p1.n_pair_slots == 8


def test_mixed_cell_population_under_union_pattern():
    """A batch mixing fully-passive (SDD) and cell-bearing systems uses
    the same pattern; inactive slots must not perturb the physics."""
    a, x, b = _batch(23, 10, 4, with_sdd=True)
    nets = [build_proposed(a[k], b[k]) for k in range(4)]
    assert any(net.is_passive for net in nets)
    assert any(not net.is_passive for net in nets)
    tr = engine.transient_batch(nets, method="eig")
    for k in range(4):
        single = lti_transient(nets[k])
        np.testing.assert_allclose(
            tr.x_converged[k], single.x_converged, rtol=0.0, atol=1e-8
        )
        np.testing.assert_allclose(
            tr.settle_time[k], single.settle_time, rtol=1e-6
        )


def test_euler_sweep_settles_to_reference():
    """The Pallas forward-Euler path (interpret mode on CPU) drives the
    batch to the mathematical solution."""
    a, x, b = _batch(29, 16, 4)
    nets = [build_proposed(a[k], b[k]) for k in range(4)]
    bss = engine.assemble_batch(nets)
    steps, x_final, res, dt = engine.euler_settle_batch(
        bss, x, max_steps=40_000, interpret=True
    )
    assert np.all(steps < 40_000)
    np.testing.assert_allclose(x_final, x, rtol=0.02, atol=1e-3)
    assert np.all(res >= 0.0)
    assert np.all(dt > 0.0)


def test_transient_batch_euler_method():
    """method='euler' end-to-end (assemble -> vmapped OP -> Pallas sweep)."""
    a, x, b = _batch(31, 12, 3)
    nets = [build_proposed(a[k], b[k]) for k in range(3)]
    tr = engine.transient_batch(nets, method="euler", interpret=True)
    assert tr.method == "euler"
    assert np.all(tr.stable)
    assert np.all(np.isfinite(tr.settle_time))
    np.testing.assert_allclose(tr.x_converged, x, rtol=0.02, atol=1e-3)


def test_batched_kernels_non_multiple_n():
    """Regression: all transient kernels auto-pad non-block-multiple n."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.ops import (
        transient_step, transient_step_batched, transient_sweep,
    )

    rng = np.random.default_rng(5)
    bsz, n = 3, 137          # 137 is far from any block multiple
    m = jnp.asarray(rng.standard_normal((bsz, n, n)) * 0.05, jnp.float32)
    z = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((bsz, n)), jnp.float32)

    out, res = transient_step_batched(m, z, c, 1e-2, interpret=True)
    want, wres = ref.transient_step_batched_ref(m, z, c, 1e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res), np.asarray(wres),
                               rtol=2e-5, atol=2e-5)

    # unequal block dims: padding must reach a multiple of lcm(bm, bk)
    out_u, res_u = transient_step_batched(
        m, z, c, 1e-2, block=(64, 128), interpret=True
    )
    np.testing.assert_allclose(np.asarray(out_u), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    out2, res2 = transient_sweep(m, z, c, n_steps=5, dt=1e-2, interpret=True)
    want2, wres2 = ref.transient_sweep_ref(m, z, c, n_steps=5, dt=1e-2)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(res2), np.asarray(wres2),
                               rtol=2e-5, atol=2e-5)

    # single-system wrapper on odd shapes (the legacy hard-assert path)
    out3 = transient_step(m[0], z[0], c[0], 1e-2, interpret=True)
    want3 = ref.transient_step_ref(m[0], z[0][:, None], c[0][:, None], 1e-2)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(want3)[:, 0],
                               rtol=2e-5, atol=2e-5)


def test_preliminary_union_pattern():
    """Preliminary-design batches share the union of cell positions."""
    a, x, b = _batch(37, 8, 3)
    nets = [build_preliminary(a[k], b[k]) for k in range(3)]
    pat = engine.pattern_union(nets)
    for net in nets:
        assert np.sum(net.cell_j >= 0) <= pat.n_pair_slots
    tr = engine.transient_batch(nets, method="eig")
    for k in range(3):
        single = lti_transient(nets[k])
        np.testing.assert_allclose(
            tr.settle_time[k], single.settle_time, rtol=1e-6
        )

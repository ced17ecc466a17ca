"""The paper's figures and tables (Figs. 8-16, Tables I-II), one
function each.

Each function mirrors the paper's experimental protocol and returns
rows ``{"name": ..., metric: value, ...}``; ``main`` prints them as
``name,metric,value`` CSV.  Default sizes are CPU-reduced; ``--full``
widens them to the paper's sweeps (slow); ``--smoke`` runs the quick
subset (Figs. 8 and 10, Tables I and II, the D-policy comparison).

    PYTHONPATH=src python examples/paper_figs.py [--full] [--smoke]
        [--only fig12,fig13]

The sweeps run on the batched engine (:mod:`repro.core.engine`): each
size/parameter class builds its netlists host-side, then errors come
from one ``operating_point_batch`` (fp64-refined DC solve) and settling
times from one ``transient_batch`` (stacked-eig modal path) per class,
instead of per-system Python loops.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import engine
from repro.core.network import build_preliminary, build_proposed
from repro.core.operating_point import NonIdealities, operating_point_batch
from repro.core.specs import AD712, OPAMPS
from repro.core.transient_nl import nonlinear_transient_batch

US = 1e-6
SMOKE = ("fig8", "fig10", "table1", "table2", "dpolicy")


def gen_systems(seed: int, n: int, count: int, density: float = 1.0):
    """Paper protocol systems: eigenvalues in [10, 1000] uS,
    x ~ U[-0.5, 0.5], b = A x."""
    from repro.data.spd import random_rhs_from_solution, random_spd

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        a = random_spd(rng, n, density=density)
        x, b = random_rhs_from_solution(rng, a)
        out.append((a, x, b))
    return out


def stats(xs) -> dict:
    """Median, 90th percentile, max and count of the finite entries."""
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


MACRO = NonIdealities(offset_mode="none")          # SPICE-macro-equivalent
TABLE1 = NonIdealities(offset_mode="random")       # datasheet-max offsets


def _batch_metrics(nets, xs, *, nonideal, opamp=AD712):
    """(err_fullscale[], settle_us[]) for a class of netlists, computed
    as one batched operating point + one batched settling call."""
    op = operating_point_batch(
        nets, opamp, nonideal=nonideal, x_ref=np.stack(xs)
    )
    # the figures report the paper's exact (modal) settling times
    tr = engine.transient_batch(nets, opamp, method="eig")
    return op.err_fullscale, tr.settle_time * 1e6


def fig8_stability(full: bool = False) -> list[dict]:
    """5x5 PD vs negative-definite: stability + amp saturation.

    Both designs run through the batched machinery: one stacked-eig
    ``transient_batch`` for the LTI verdict and one vmapped nonlinear
    RK4 batch for the rail-saturation signature (Sec. III-C.2)."""
    (a, x, b), = gen_systems(8, 5, 1)
    nets = [build_proposed(a, b), build_proposed(-a, -b)]
    lti = engine.transient_batch(nets, method="eig")
    nl = nonlinear_transient_batch(nets, t_end=2e-4)
    rows = []
    for k, tag in enumerate(("pd", "nd")):
        err = (np.abs(nl.x_final[k] - x).max() / np.abs(x).max()
               if tag == "pd" else float("nan"))
        rows.append({
            "name": f"fig8_{tag}",
            "lti_stable": int(lti.stable[k]),
            "amp_saturated": int(nl.saturated[k]),
            "err_fullscale": float(err),
        })
    return rows


def fig9_preliminary(full: bool = False) -> list[dict]:
    """Preliminary n-design: error + settling across sizes."""
    sizes = (5, 10, 20, 30) if not full else (5, 10, 20, 40, 60, 100)
    count = 6 if not full else 20
    rows = []
    for n in sizes:
        systems = gen_systems(900 + n, n, count)
        nets = [build_preliminary(a, b) for a, _x, b in systems]
        errs, settles = _batch_metrics(
            nets, [x for _a, x, _b in systems], nonideal=MACRO
        )
        s = stats(settles)
        e = stats(errs)
        rows.append({
            "name": f"fig9_n{n}",
            "settle_med_us": s["median"], "settle_p90_us": s["p90"],
            "err_med_pct": e["median"] * 100, "err_max_pct": e["max"] * 100,
            "count": s["n"],
        })
    return rows


def fig10_beta(full: bool = False) -> list[dict]:
    """D-matrix scaling beta: smaller beta -> faster + more accurate.

    All (system, beta) variants share one proposed-design pattern, so
    the whole figure is a single batched OP + settling call.
    """
    betas = (0.5, 0.75, 1.0, 2.0, 4.0)
    systems = gen_systems(10, 16, 2)
    nets, xs, names = [], [], []
    for a, x, b in systems:
        for beta in betas:
            nets.append(build_proposed(a, b, d_policy="scaled", beta=beta))
            xs.append(x)
            names.append(f"fig10_beta{beta}")
    errs, settles = _batch_metrics(nets, xs, nonideal=MACRO)
    return [
        {"name": name, "settle_us": float(t), "err_pct": float(e) * 100}
        for name, t, e in zip(names, settles, errs)
    ]


def fig12_complexity(full: bool = False) -> list[dict]:
    """Proposed design across sizes (unconstrained conductance):
    settling grows with max conductance, not n per se."""
    sizes = (5, 10, 20, 50, 100) if not full else (5, 10, 20, 50, 100, 200, 300)
    count = 6 if not full else 20
    rows = []
    for n in sizes:
        systems = gen_systems(1200 + n, n, count)
        nets = [build_proposed(a, b) for a, _x, b in systems]
        tr = engine.transient_batch(nets, method="eig")
        settles = tr.settle_time * 1e6
        gmax = [net.max_conductance() / US for net in nets]
        s = stats(settles)
        rows.append({
            "name": f"fig12_n{n}",
            "settle_med_us": s["median"], "settle_p90_us": s["p90"],
            "gmax_med_uS": float(np.median(gmax)),
            "count": s["n"],
        })
    return rows


def _fixed_conductance(name, sizes, density, g_target, count):
    from repro.data.spd import random_spd_fixed_conductance

    rng = np.random.default_rng(13)
    rows = []
    for n in sizes:
        nets, xs = [], []
        for _ in range(count):
            out = random_spd_fixed_conductance(
                rng, n, g_target=g_target, density=density)
            if out is None:
                continue
            a, x, b = out
            nets.append(build_proposed(a, b))
            xs.append(x)
        if not nets:
            rows.append({"name": f"{name}_n{n}", "found": 0})
            continue
        errs, settles = _batch_metrics(nets, xs, nonideal=MACRO)
        s = stats(settles)
        e = stats(errs)
        rows.append({
            "name": f"{name}_n{n}",
            "found": len(nets),
            "settle_med_us": s["median"],
            "err_med_pct": e["median"] * 100,
        })
    return rows


def fig13_fixed_conductance(full: bool = False) -> list[dict]:
    """Fixed 800 uS max conductance, density 1: settling independent of n."""
    sizes = (30, 50, 80) if not full else (20, 30, 50, 80, 100, 150)
    return _fixed_conductance("fig13", sizes, 1.0, 800 * US,
                              4 if not full else 15)


def fig14_density05(full: bool = False) -> list[dict]:
    """Fixed 550 uS, density 0.5: size-independence over a wider range."""
    sizes = (30, 60, 120) if not full else (20, 50, 100, 200, 500)
    return _fixed_conductance("fig14", sizes, 0.5, 550 * US,
                              4 if not full else 15)


def fig15_opamps(full: bool = False) -> list[dict]:
    """Op-amp trade-off: LTC2050 accuracy, LTC6268 speed (Table I)."""
    count = 4 if not full else 12
    n = 20
    systems = gen_systems(15, n, count)
    nets = [build_proposed(a, b) for a, _x, b in systems]
    xs = [x for _a, x, _b in systems]
    rows = []
    for amp_name, spec in OPAMPS.items():
        errs, settles = _batch_metrics(nets, xs, nonideal=TABLE1, opamp=spec)
        e, s = stats(errs), stats(settles)
        rows.append({
            "name": f"fig15_{amp_name}",
            "err_p90_pct": e["p90"] * 100,
            "settle_p90_us": s["p90"],
        })
    return rows


def fig16_alpha(full: bool = False) -> list[dict]:
    """System scaling alpha: smaller conductances shrink the wiper-
    parasitic error (and power), Eq. 27."""
    alphas = (0.01, 0.1, 1.0, 10.0)
    wiper = NonIdealities(offset_mode="none", wiper_ohm=50.0)
    systems = gen_systems(16, 12, 2)
    nets, xs, names = [], [], []
    for a, x, b in systems:
        for alpha in alphas:
            nets.append(build_proposed(a, b, alpha=alpha))
            xs.append(x)
            names.append(f"fig16_alpha{alpha}")
    errs, settles = _batch_metrics(nets, xs, nonideal=wiper)
    return [
        {"name": name, "err_pct": float(e) * 100, "settle_us": float(t)}
        for name, t, e in zip(names, settles, errs)
    ]


def table1_specs(full: bool = False) -> list[dict]:
    return [{
        "name": f"table1_{s.name}",
        "gbw_mhz": s.gbw_hz / 1e6,
        "slew_v_per_us": s.slew_v_per_s / 1e6,
        "vos_uv": s.v_os * 1e6,
    } for s in OPAMPS.values()]


def table2_components(full: bool = False) -> list[dict]:
    from repro.core.components import (
        component_counts, component_reduction, netlist_counts)

    rows = []
    for n in (10, 100):
        pre = component_counts("preliminary", n)
        pro = component_counts("proposed", n)
        rows.append({
            "name": f"table2_n{n}",
            "pre_opamps": pre["opamps"], "pro_opamps": pro["opamps"],
            "pre_pots": pre["variable_resistors"],
            "pro_pots": pro["variable_resistors"],
            "reduction_pct": component_reduction(n) * 100,
        })
    # measured counts on a concrete system
    (a, x, b), = gen_systems(2, 20, 1)
    meas = netlist_counts(build_proposed(a, b))
    rows.append({"name": "table2_measured_n20", **meas})
    return rows


def d_policy_comparison(full: bool = False) -> list[dict]:
    """Sec. IV-A: the paper's D (Eq. 22) vs Gremban's support-tree
    transform (D = diag(A), K_s = 0).  The paper's point: Gremban's
    choice does not keep the transformed system PD on general SPD
    inputs; Eq. 22 always does."""
    from repro.core.transform import transform_2n

    count = 20 if not full else 100
    rows = []
    for policy in ("proposed", "gremban"):
        pd_ok = 0
        for a, x, b in gen_systems(41, 16, count):
            tr = transform_2n(a, b, d_policy=policy)
            m = np.asarray(tr.assembled())
            ev_min = float(np.linalg.eigvalsh((m + m.T) / 2)[0])
            scale = float(np.abs(m).max())
            if ev_min > -1e-9 * scale:
                pd_ok += 1
        rows.append({
            "name": f"dpolicy_{policy}",
            "pd_preserved_pct": 100.0 * pd_ok / count,
            "count": count,
        })
    return rows


ALL = {
    "fig8": fig8_stability,
    "fig9": fig9_preliminary,
    "fig10": fig10_beta,
    "fig12": fig12_complexity,
    "fig13": fig13_fixed_conductance,
    "fig14": fig14_density05,
    "fig15": fig15_opamps,
    "fig16": fig16_alpha,
    "table1": table1_specs,
    "table2": table2_components,
    "dpolicy": d_policy_comparison,
}


def main(argv=None) -> dict[str, list[dict]]:
    ap = argparse.ArgumentParser(
        description="The paper's figures and tables as CSV rows.")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="quick subset: " + ",".join(SMOKE))
    ap.add_argument("--only", default="",
                    help="comma-separated subset, e.g. fig12,fig13")
    args = ap.parse_args(argv)
    only = set(filter(None, args.only.split(","))) or (
        set(SMOKE) if args.smoke else set(ALL))
    unknown = only - set(ALL)
    if unknown:
        ap.error(f"unknown figures {sorted(unknown)}; known: {list(ALL)}")

    out = {}
    print("name,metric,value")
    for key, fn in ALL.items():
        if key not in only:
            continue
        out[key] = fn(full=args.full)
        for row in out[key]:
            for metric, value in row.items():
                if metric == "name":
                    continue
                if isinstance(value, float):
                    value = f"{value:.6g}"
                print(f"{row['name']},{metric},{value}")
    return out


if __name__ == "__main__":
    main()

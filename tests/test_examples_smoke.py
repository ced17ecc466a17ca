"""CI smoke runs of the end-to-end example drivers.

Each example exposes ``main(argv)`` with a ``--smoke`` configuration
sized for seconds-scale CI; these tests pin the example entry points to
the library APIs (renames/regressions in either break here first) and
assert the workload actually exercised the analog engine — the
train_lm probe checks the refresh accounting (one batched solve per
refresh on one cached pattern), which a silently-skipping block filter
would zero out; the paper_figs probe checks the Fig. 8 stability
verdicts and the Sec. IV-A D-policy comparison.
"""

import importlib
import importlib.util
import pathlib

EXAMPLES = pathlib.Path(__file__).resolve().parents[1] / "examples"


def _load(name):
    spec = importlib.util.spec_from_file_location(name, EXAMPLES / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fem_poisson_example_smoke(capsys):
    _load("fem_poisson").main(["--smoke"])
    text = capsys.readouterr().out
    assert "ERROR" not in text
    assert "zero op-amps at every size" in text


def test_train_lm_example_smoke():
    out = _load("train_lm").main(["--smoke"])
    hist = out["history"]
    assert hist and all(h["loss"] == h["loss"] for h in hist)  # finite
    an = importlib.import_module("repro.optim.analog_newton")
    rs = an.REFRESH_STATS
    # steps=4, refresh_every=2 -> 2 refreshes, each ONE batched solve
    # on the one cached pattern, and blocks actually qualified
    assert rs.refreshes == 2
    assert rs.solve_batch_calls == rs.refreshes
    assert rs.systems_solved > 0
    assert rs.pattern_derivations == 1
    an.reset_refresh_stats()


def test_paper_figs_example_smoke(capsys):
    out = _load("paper_figs").main(["--smoke"])
    assert set(out) == {"fig8", "fig10", "table1", "table2", "dpolicy"}
    assert capsys.readouterr().out.startswith("name,metric,value\n")
    pd, nd = out["fig8"]
    # Fig. 8: the PD system settles to x; its negation is unstable and
    # drives the op-amps into the rails
    assert pd["lti_stable"] == 1 and pd["amp_saturated"] == 0
    assert pd["err_fullscale"] < 1e-2
    assert nd["lti_stable"] == 0 and nd["amp_saturated"] == 1
    # Fig. 10: every beta variant settled, error grows with beta
    assert all(0 < r["settle_us"] < float("inf") for r in out["fig10"])
    per_beta = [r["err_pct"] for r in out["fig10"][:5]]
    assert per_beta == sorted(per_beta)
    # Sec. IV-A: Eq. 22 keeps every transformed system PD, Gremban not
    pct = {r["name"]: r["pd_preserved_pct"] for r in out["dpolicy"]}
    assert pct["dpolicy_proposed"] == 100.0
    assert pct["dpolicy_gremban"] < 100.0

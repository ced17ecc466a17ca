"""Unit suite for the static-analysis engine, rules, and runtime gates.

Every rule gets a true-positive and a true-negative fixture snippet
(written under a path that puts it in the rule's module scope), plus a
suppression-honoring case; the framework pieces (suppression parsing,
baseline diffing, reporters) and the runtime watches (CompileWatch /
SyncWatch) are exercised directly.  The StampPattern / SolveSignature
cache-key stability contract is regression-tested with the compile
counter: equal-but-distinct keys must not retrigger lowering.
"""

import json
import textwrap

import numpy as np
import pytest

from repro.analysis import (
    ALL_RULES,
    Analyzer,
    CompileWatch,
    Finding,
    SyncWatch,
    diff_baseline,
    host_callbacks,
    human_report,
    is_suppressed,
    json_report,
    load_baseline,
    parse_suppressions,
    span,
    write_baseline,
)
from repro.analysis.runtime import _SCOPE_STACK


def run_on(tmp_path, rel_path, source, rules=ALL_RULES, config=None):
    """Analyze one fixture snippet at a repo-relative-like path."""
    f = tmp_path / rel_path
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    return Analyzer(rules, config).run([f], root=tmp_path)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------- suppressions


def test_suppression_parsing_forms():
    src = (
        "x = 1  # repro: ignore\n"
        "y = 2  # repro: ignore[rule-a, rule-b]\n"
        "# repro: ignore[rule-c]\n"
        "z = 3\n"
        "w = 4\n"
    )
    sup = parse_suppressions(src)
    assert sup[1] == frozenset({"*"})
    assert sup[2] == frozenset({"rule-a", "rule-b"})
    # a comment-only line covers itself and the next line
    assert sup[3] == frozenset({"rule-c"})
    assert sup[4] == frozenset({"rule-c"})
    assert 5 not in sup


def test_is_suppressed_matches_rule_and_wildcard():
    f = Finding(rule="r", path="p", line=3, col=0,
                severity="error", message="m")
    assert is_suppressed(f, {3: frozenset({"r"})})
    assert is_suppressed(f, {3: frozenset({"*"})})
    assert not is_suppressed(f, {3: frozenset({"other"})})
    assert not is_suppressed(f, {4: frozenset({"r"})})


# -------------------------------------------------------- host-sync-in-hot-path

HOT_LOOP_BAD = """
    import numpy as np

    class S:
        def drain(self):
            for flight in self.inflight:
                x = np.asarray(flight.result)
                v = flight.res.item()
                t = float(flight.elapsed)
"""

HOT_LOOP_OK = """
    import numpy as np

    class S:
        def drain(self):
            for flight in self.inflight:
                self.pending.append(flight)

        def _unpack(self):
            # not a hot function: materialization is fine here
            return np.asarray(self.batch.x)
"""


def test_host_sync_flags_sync_calls_in_hot_loop(tmp_path):
    found = run_on(tmp_path, "serving/loop.py", HOT_LOOP_BAD)
    assert rules_of(found) == ["host-sync-in-hot-path"]
    assert len(found) == 3          # asarray + .item() + float()


def test_host_sync_ignores_cold_paths_and_other_modules(tmp_path):
    assert run_on(tmp_path, "serving/loop.py", HOT_LOOP_OK) == []
    # same bad code outside serving/ is out of scope
    assert run_on(tmp_path, "core/loop.py", HOT_LOOP_BAD) == []


def test_host_sync_suppression_honored(tmp_path):
    src = """
    import numpy as np

    class S:
        def drain(self):
            for f in self.inflight:
                x = np.asarray(f.r)  # repro: ignore[host-sync-in-hot-path]
    """
    assert run_on(tmp_path, "serving/loop.py", src) == []


# ------------------------------------------------------------ recompile-hazard

JIT_IN_BODY = """
    import jax

    def solve(m):
        f = jax.jit(lambda x: x @ x)
        return f(m)
"""

JIT_AT_MODULE = """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("block",))
    def kernel(x, block=128):
        return x

    _solver = jax.jit(lambda m: m)

    class Engine:
        def __init__(self):
            self._step = jax.jit(lambda c: c)
"""

UNHASHABLE_STATIC = """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("shape",))
    def pad(x, shape=[1, 2]):
        return x
"""

TRACED_BRANCH = """
    import jax

    @jax.jit
    def step(x):
        if float(x[0]) > 0:
            return x
        return -x
"""


def test_recompile_flags_jit_in_function_body(tmp_path):
    found = run_on(tmp_path, "kernels/k.py", JIT_IN_BODY)
    assert rules_of(found) == ["recompile-hazard"]


def test_recompile_allows_module_scope_decorators_and_init(tmp_path):
    # the decorator's own partial(jax.jit, ...) call must NOT count as
    # a call "inside" the function it decorates
    assert run_on(tmp_path, "kernels/k.py", JIT_AT_MODULE) == []


def test_recompile_flags_unhashable_static_default(tmp_path):
    found = run_on(tmp_path, "kernels/k.py", UNHASHABLE_STATIC)
    assert rules_of(found) == ["recompile-hazard"]
    assert "unhashable" in found[0].message


def test_recompile_flags_traced_value_branch(tmp_path):
    found = run_on(tmp_path, "kernels/k.py", TRACED_BRANCH)
    assert rules_of(found) == ["recompile-hazard"]
    assert "branch test" in found[0].message


# -------------------------------------------------------------- dtype-contract

BF16_ESCAPE = """
    import jax.numpy as jnp

    def prepare(m):
        return jnp.asarray(m).astype("bfloat16")
"""

BF16_IN_BOUNDARY = """
    import jax.numpy as jnp

    def euler_settle_batch(m):
        return jnp.asarray(m).astype("bfloat16")
"""

X64_NARROWING = """
    import numpy as np

    def refine(r):
        return np.zeros(3, dtype=np.float32) + r.astype("float32")
"""


def test_dtype_flags_bf16_escape_outside_kernels(tmp_path):
    found = run_on(tmp_path, "serving/svc.py", BF16_ESCAPE)
    assert rules_of(found) == ["dtype-contract"]


def test_dtype_allows_bf16_inside_boundary(tmp_path):
    # the kernels/ module and the declared boundary functions are the
    # sanctioned low-precision zone
    assert run_on(tmp_path, "kernels/sweep.py", BF16_ESCAPE) == []
    assert run_on(tmp_path, "core/engine.py", BF16_IN_BOUNDARY) == []


def test_dtype_flags_narrowing_in_x64_modules_only(tmp_path):
    found = run_on(tmp_path, "core/refine.py", X64_NARROWING)
    assert rules_of(found) == ["dtype-contract"]
    assert len(found) == 2          # dtype= construction + astype
    # the same narrowing outside the strict-x64 module set is fine
    assert run_on(tmp_path, "serving/svc.py", X64_NARROWING) == []


# ---------------------------------------------------------- donation-after-use

DONATE_THEN_READ = """
    import jax

    _f = jax.jit(lambda m, c: m + c, donate_argnums=(0,))

    def solve(m, c):
        y = _f(m, c)
        return y + m.sum()
"""

DONATE_IN_RETURN = """
    import jax

    _f = jax.jit(lambda m, c: m + c, donate_argnums=(0, 1))

    def solve(m, c, use_donation):
        if use_donation:
            return _f(m, c)
        # this branch only runs when the donating call did not
        return m @ c
"""

DONATE_THEN_REBIND = """
    import jax

    _f = jax.jit(lambda m: m * 2, donate_argnums=(0,))

    def solve(m):
        y = _f(m)
        m = y + 1
        return m
"""


def test_donation_flags_read_after_donating_call(tmp_path):
    found = run_on(tmp_path, "core/s.py", DONATE_THEN_READ)
    assert rules_of(found) == ["donation-after-use"]
    assert "'m'" in found[0].message


def test_donation_allows_return_position_and_rebinding(tmp_path):
    assert run_on(tmp_path, "core/s.py", DONATE_IN_RETURN) == []
    assert run_on(tmp_path, "core/s.py", DONATE_THEN_REBIND) == []


# -------------------------------------------------------- unlocked-shared-state

UNLOCKED = """
    class AdmissionQueue:
        def __init__(self):
            self._items = []

        def push(self, item):
            self._items.append(item)
"""

LOCKED = """
    import threading

    class AdmissionQueue:
        def __init__(self):
            self._items = []
            self._lock = threading.Lock()

        def push(self, item):
            with self._lock:
                self._items.append(item)

        def __len__(self):
            return len(self._items)
"""


def test_unlocked_flags_mutation_outside_lock(tmp_path):
    found = run_on(tmp_path, "serving/q.py", UNLOCKED)
    assert rules_of(found) == ["unlocked-shared-state"]


def test_unlocked_accepts_lock_and_exempts_init(tmp_path):
    assert run_on(tmp_path, "serving/q.py", LOCKED) == []
    # classes outside the configured shared-state set are not checked
    other = UNLOCKED.replace("AdmissionQueue", "LocalScratch")
    assert run_on(tmp_path, "serving/q.py", other) == []


# --------------------------------------------------- blocking-call-in-stream-loop

BLOCKING = """
    class S:
        def step(self):
            import time
            time.sleep(0.1)
"""

BLOCKING_SUPPRESSED = """
    import time

    class S:
        def step(self):
            # injected-slow chaos fault: the stall is the point
            time.sleep(0.1)  # repro: ignore[blocking-call-in-stream-loop]
"""


def test_blocking_flags_import_and_sleep_in_stream_code(tmp_path):
    found = run_on(tmp_path, "serving/e.py", BLOCKING)
    assert rules_of(found) == ["blocking-call-in-stream-loop"]
    assert len(found) == 2          # the import and the sleep


def test_blocking_suppression_and_cold_functions(tmp_path):
    assert run_on(tmp_path, "serving/e.py", BLOCKING_SUPPRESSED) == []
    cold = BLOCKING.replace("def step", "def build_report")
    assert run_on(tmp_path, "serving/e.py", cold) == []


# ------------------------------------------------------------- swallowed-error

SWALLOWED = """
    def deliver(t):
        try:
            t.send()
        except Exception:
            pass

    def harvest(t):
        try:
            t.wait()
        except:
            return None
"""

HANDLED = """
    def deliver(t, out):
        try:
            t.send()
        except Exception as exc:
            out[t.rid] = make_error(exc)

    def narrow(t):
        try:
            t.wait()
        except TimeoutError:
            pass
"""


def test_swallowed_flags_bare_and_pass_body_handlers(tmp_path):
    found = run_on(tmp_path, "serving/d.py", SWALLOWED)
    assert rules_of(found) == ["swallowed-error"]
    assert len(found) == 2


def test_swallowed_accepts_structured_delivery_and_narrow_types(tmp_path):
    assert run_on(tmp_path, "serving/d.py", HANDLED) == []


# ----------------------------------------------------------- analyzer plumbing


def test_analyzer_config_disables_and_reoptions_rules(tmp_path):
    config = {"swallowed-error": {"enabled": False}}
    assert run_on(tmp_path, "serving/d.py", SWALLOWED,
                  config=config) == []
    # option override: a different hot-function set
    config = {"host-sync-in-hot-path": {"hot_functions": ("other",)}}
    assert run_on(tmp_path, "serving/loop.py", HOT_LOOP_BAD,
                  config=config) == []


def test_analyzer_reports_parse_errors_as_findings(tmp_path):
    found = run_on(tmp_path, "serving/broken.py", "def f(:\n")
    assert rules_of(found) == ["parse-error"]


def test_repo_source_tree_is_clean_against_committed_baseline():
    """The tree must analyze clean — the same check CI enforces."""
    from pathlib import Path

    from repro.analysis.__main__ import DEFAULT_BASELINE

    root = Path(__file__).resolve().parents[1]
    findings = Analyzer(ALL_RULES).run([root / "src"], root=root)
    new, _stale = diff_baseline(findings, load_baseline(DEFAULT_BASELINE))
    assert new == [], human_report(new)


# ----------------------------------------------------------- baseline diffing


def F(rule="r", path="p.py", line=1, message="m"):
    return Finding(rule=rule, path=path, line=line, col=0,
                   severity="error", message=message)


def test_diff_baseline_absorbs_counts_and_reports_overflow():
    entries = [{"rule": "r", "path": "p.py", "message": "m", "count": 2}]
    new, stale = diff_baseline([F(line=1), F(line=9), F(line=30)], entries)
    assert len(new) == 1 and stale == []        # third one overflows
    new, stale = diff_baseline([F(line=5)], entries)
    assert new == []
    assert stale == [{"rule": "r", "path": "p.py", "message": "m",
                      "count": 1}]


def test_baseline_roundtrip_preserves_why(tmp_path):
    path = tmp_path / "baseline.json"
    write_baseline([F(), F(line=2)], path)
    entries = load_baseline(path)
    assert entries[0]["count"] == 2
    assert entries[0]["why"] == "TODO: justify"
    entries[0]["why"] = "legacy exception"
    write_baseline([F()], path, previous=entries)
    assert load_baseline(path)[0]["why"] == "legacy exception"


def test_baseline_version_mismatch_raises(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError):
        load_baseline(path)


def test_reporters():
    out = human_report([F()])
    assert "p.py:1:1" in out and "1 finding(s): 1 error" in out
    assert human_report([]) == "clean: no findings"
    data = json.loads(json_report([F(), F()]))
    assert data["total"] == 2 and data["counts"] == {"r": 2}


# -------------------------------------------------------------- runtime gates


def test_compile_watch_counts_fresh_lowering_only():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.arange(4.0)
    with CompileWatch() as w1:
        f(x).block_until_ready()
    assert w1.count == 1
    assert host_callbacks(f, x) == []
    with CompileWatch() as w2:
        f(x).block_until_ready()        # cache hit: no new lowering
    assert w2.count == 0


def test_compile_watch_rejects_reentry():
    with CompileWatch():
        with pytest.raises(RuntimeError):
            CompileWatch().__enter__()


def test_sync_watch_attributes_syncs_to_scope():
    import jax.numpy as jnp

    y = jnp.arange(3.0)
    with SyncWatch() as watch:
        np.asarray(y)                       # ambient
        with span("serve.harvest", sync="harvest"):
            xs = np.asarray(y)
            float(xs[0])                    # numpy operand: not counted
        np.asarray(np.arange(3.0))          # numpy operand: not counted
    assert watch.counts == {"ambient": 1, "harvest": 1}
    assert watch.total() == 2
    assert watch.total("harvest") == 1
    # patches restored, scope stack balanced
    assert _SCOPE_STACK == ["ambient"]
    with span("serve.x", sync="x"):
        assert _SCOPE_STACK[-1] == "x"
    assert _SCOPE_STACK == ["ambient"]


# ----------------------------------------------------- cache-key stability


def _patterns():
    from repro.core.engine import _build_pattern

    mk = lambda g: _build_pattern(
        "proposed", 12, 6, np.arange(6), np.arange(6) + 6,
        np.arange(g), 2, True,
    )
    return mk(2), mk(2), mk(3)


def test_stamp_pattern_hash_eq_contract():
    p1, p2, p3 = _patterns()
    assert p1 == p2 and p1 is not p2
    assert hash(p1) == hash(p2)
    assert p1 != p3 and p1 != "not a pattern"
    assert len({p1, p2, p3}) == 2


def test_equal_patterns_share_one_jit_cache_entry():
    """Equal-but-distinct StampPatterns as static args must not
    retrigger lowering — the regression the generated dataclass
    ``__hash__`` (TypeError) made impossible to even express."""
    import functools

    import jax

    p1, p2, p3 = _patterns()

    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, pat):
        return x * pat.n_states

    x = np.arange(3.0)
    with CompileWatch() as warm:
        f(x, p1).block_until_ready()
    assert warm.count == 1
    with CompileWatch() as again:
        f(x, p2).block_until_ready()    # equal pattern: cache hit
    assert again.count == 0
    with CompileWatch() as differ:
        f(x, p3).block_until_ready()    # different pattern: recompile
    assert differ.count == 1


def test_solve_signature_cache_key_stability():
    from repro.core.operating_point import NonIdealities
    from repro.core.specs import OPAMPS
    from repro.serving.solve_service import SolveSignature

    mk = lambda: SolveSignature(
        method="analog_2n", opamp=OPAMPS["AD712"],
        nonideal=NonIdealities(), compute_settling=True,
    ).normalized()
    s1, s2 = mk(), mk()
    assert s1 == s2 and hash(s1) == hash(s2)
    # every field of the bucket key must stay hashable — a single
    # unhashable field silently breaks dict bucketing at submit time
    assert {s1: "bucket"}[s2] == "bucket"

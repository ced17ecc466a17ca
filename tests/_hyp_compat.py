"""Optional-``hypothesis`` shim for the property-based tests.

The tier-1 suite must collect and run on machines without ``hypothesis``
installed (the container bakes in the JAX/Pallas toolchain only).  Test
modules import ``given``/``settings``/``st``/``example`` from here instead
of from ``hypothesis`` directly:

* with ``hypothesis`` present this is a pure re-export;
* without it, ``@given(...)`` falls back to a deterministic sampler:
  each strategy the suite actually uses (``st.integers``, ``st.floats``,
  ``st.booleans``) records its bounds, and the test is parametrized over
  ``FALLBACK_EXAMPLES`` seeded draws (plus the integer endpoints), so
  every property still executes — with far fewer examples than
  hypothesis would run, but deterministically and against the same
  predicates; each ``@example`` pinned under the ``@given`` is one more
  case.  A test using a strategy the fallback cannot sample is
  skipped with that strategy named in the skip reason.
"""

from __future__ import annotations

import zlib

import pytest

try:
    from hypothesis import example, given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on hypothesis-free CI
    HAVE_HYPOTHESIS = False

    # deterministic draws per @given when hypothesis is missing
    FALLBACK_EXAMPLES = 5

    class _Strategy:
        """Recorded strategy spec the fallback sampler can draw from."""

        def __init__(self, kind, args, kwargs):
            self.kind = kind
            self.args = args
            self.kwargs = kwargs

        def _bounds(self, lo_name, hi_name):
            a = list(self.args)
            lo = self.kwargs.get(lo_name, a.pop(0) if a else None)
            hi = self.kwargs.get(hi_name, a.pop(0) if a else None)
            return lo, hi

        def samples(self, rng, count):
            if self.kind == "integers":
                lo, hi = self._bounds("min_value", "max_value")
                lo = 0 if lo is None else int(lo)
                hi = lo + 1000 if hi is None else int(hi)
                out = [lo, hi] + [
                    int(rng.integers(lo, hi + 1))
                    for _ in range(max(count - 2, 0))
                ]
                return out[:count]
            if self.kind == "floats":
                lo, hi = self._bounds("min_value", "max_value")
                lo = 0.0 if lo is None else float(lo)
                hi = lo + 1.0 if hi is None else float(hi)
                return [float(rng.uniform(lo, hi)) for _ in range(count)]
            if self.kind == "booleans":
                return [bool((i + int(rng.integers(0, 2))) % 2)
                        for i in range(count)]
            return None

    class _AnyStrategy:
        """Stand-in for ``hypothesis.strategies``: records constructions."""

        def __getattr__(self, name):
            return lambda *args, **kwargs: _Strategy(name, args, kwargs)

    st = _AnyStrategy()

    def settings(*args, **kwargs):
        return lambda fn: fn

    def example(**kwargs):
        def deco(fn):
            fn._hyp_examples = [kwargs, *getattr(fn, "_hyp_examples", [])]
            return fn

        return deco

    def given(*args, **kwargs):
        if args or not kwargs:
            # the suite only uses keyword strategies; anything else has
            # no fallback sampler
            return lambda fn: pytest.mark.skip(
                reason="hypothesis not installed; positional @given has "
                "no deterministic fallback"
            )(fn)

        def deco(fn):
            import numpy as np

            # per-test deterministic seed: same draws on every run/host
            seed = zlib.crc32(fn.__name__.encode())
            rng = np.random.default_rng(seed)
            names = list(kwargs)
            columns = []
            for name in names:
                strat = kwargs[name]
                draws = (
                    strat.samples(rng, FALLBACK_EXAMPLES)
                    if isinstance(strat, _Strategy)
                    else None
                )
                if draws is None:
                    kind = getattr(strat, "kind", type(strat).__name__)
                    return pytest.mark.skip(
                        reason="hypothesis not installed; no deterministic "
                        f"fallback sampler for strategy {kind!r}"
                    )(fn)
                columns.append(draws)
            cases = list(zip(*columns))
            pinned = [tuple(ex[k] for k in names)
                      for ex in getattr(fn, "_hyp_examples", [])]
            return pytest.mark.parametrize(
                ",".join(names),
                cases + pinned,
                ids=[f"fallback{i}" for i in range(len(cases))]
                + [f"example{i}" for i in range(len(pinned))],
            )(fn)

        return deco

"""JAX-discipline static analysis + runtime contract gates.

``python -m repro.analysis src/`` runs the AST rules against the
committed baseline (exit 0 = no unbaselined findings);
``python -m repro.analysis --runtime-gate`` runs the steady-state
no-recompile / no-host-sync smoke gate over a live ``SolveService``.
See ``docs/ANALYSIS.md`` for the rule catalog and workflow.
"""

from repro.analysis.engine import (
    Analyzer,
    FileContext,
    Finding,
    Rule,
    is_suppressed,
    parse_suppressions,
)
from repro.analysis.report import (
    diff_baseline,
    human_report,
    json_report,
    load_baseline,
    write_baseline,
)
from repro.analysis.rules import ALL_RULES
from repro.analysis.runtime import (
    CompileWatch,
    SpanTotals,
    SyncWatch,
    host_callbacks,
    run_service_gate,
    span,
)

__all__ = [
    "ALL_RULES",
    "Analyzer",
    "CompileWatch",
    "FileContext",
    "Finding",
    "Rule",
    "SpanTotals",
    "SyncWatch",
    "diff_baseline",
    "host_callbacks",
    "human_report",
    "is_suppressed",
    "json_report",
    "load_baseline",
    "parse_suppressions",
    "run_service_gate",
    "span",
    "write_baseline",
]

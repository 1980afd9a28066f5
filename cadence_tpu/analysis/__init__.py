"""Static-analysis CI gate for the cadence-tpu kernel/runtime contract.

Five passes, run together by ``python -m cadence_tpu.analysis``:

1. **transition surface** (transition_surface.py) — the kernel's
   event-type × column write matrix, traced at jaxpr level, diffed
   against the host oracle's AST-extracted transition table and the
   ops/schema.py invariants (column density, EV_A windows, epoch-rebase
   coverage).
2. **jit hazards** (jit_hazards.py) — recompilation, host-sync,
   Python-branch and dtype-widening hazards over ops/ and the dispatch
   callers.
3. **lock order** (lock_order.py) — the runtime's lock graph:
   acquisition-order inversions and blocking work (store I/O, sleeps,
   joins, foreign waits) done while holding a lock.
4. **metrics** (metric_decl.py) — every literal metric emission under
   runtime/ops/matching/checkpoint must be declared in a
   utils/metrics_defs.py catalog (rule METRIC-UNDECLARED): the
   operator docs can never silently trail the code.
5. **queue effects** (queue_effects.py) — AST-derived effect
   footprints of every queue-task handler (transfer/timer/standby +
   the NDC apply path) diffed against the declared footprint table
   (runtime/queues/effects.py): rules QUEUE-EFFECT-UNKNOWN,
   QUEUE-CONFLICT-UNDECLARED, QUEUE-CROSS-WF. The footprints derive
   the task-type commutativity matrix (--emit-conflict-matrix) the
   future parallel-queue executor gates on.

Findings gate against a checked-in baseline
(config/lint_baseline.json): accepted findings carry a one-line
justification; anything new exits non-zero. See analysis/README.md for
per-rule docs and how to baseline.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .findings import Baseline, BaselineEntry, Finding, dedupe

PASSES = ("surface", "jit", "locks", "metrics", "queue")

# rule-id prefixes per pass — lets a --passes subset run scope the
# baseline to the rules that could actually fire, so entries belonging
# to skipped passes are not reported (or strict-failed) as stale
PASS_RULE_PREFIXES = {
    "surface": ("SURFACE-", "SCHEMA-"),
    "jit": ("JIT-", "PALLAS-"),
    "locks": ("LOCK-",),
    "metrics": ("METRIC-",),
    "queue": ("QUEUE-",),
}


def scope_baseline(baseline, passes):
    """Baseline restricted to entries whose rule belongs to ``passes``
    (None = all passes, returned unchanged). Entries with rules outside
    every known prefix only gate on full runs."""
    if passes is None:
        return baseline
    prefixes = tuple(
        p for name in passes for p in PASS_RULE_PREFIXES.get(name, ())
    )
    return Baseline([
        e for e in baseline.entries if e.rule.startswith(prefixes)
    ]) if prefixes else Baseline([])


def run_pass(name: str, repo_root: str) -> List[Finding]:
    if name == "surface":
        from . import transition_surface

        return transition_surface.run(repo_root)
    if name == "jit":
        from . import jit_hazards

        return jit_hazards.run(repo_root)
    if name == "locks":
        from . import lock_order

        return lock_order.run(repo_root)
    if name == "metrics":
        from . import metric_decl

        return metric_decl.run(repo_root)
    if name == "queue":
        from . import queue_effects

        return queue_effects.run(repo_root)
    raise ValueError(f"unknown pass {name!r} (have: {PASSES})")


def run_all(
    repo_root: str, passes: Optional[List[str]] = None
) -> Dict[str, List[Finding]]:
    """{pass name → deduped findings} over the real tree."""
    out: Dict[str, List[Finding]] = {}
    for name in passes or PASSES:
        out[name] = dedupe(run_pass(name, repo_root))
    return out


__all__ = [
    "Baseline", "BaselineEntry", "Finding", "PASSES",
    "PASS_RULE_PREFIXES", "dedupe", "run_all", "run_pass",
    "scope_baseline",
]

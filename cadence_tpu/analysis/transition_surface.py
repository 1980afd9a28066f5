"""Pass 1 — transition-surface checker.

The replay kernel (ops/replay.py replay_step_cols) mirrors the host
oracle's event-type × state transition function. Fuzz differentials
sample that surface; this pass covers it statically:

* **Kernel matrix** — abstract trace of ``replay_step_cols`` once per
  transition group (jaxpr level): the event row is fed in as 16
  independent column leaves, so per-column data flow survives into the
  jaxpr and "which state columns can this event type write, from which
  event columns" falls out of reachability over the equations. No
  device, no execution — just tracing.
* **Oracle table** — AST extraction (oracle_ast.py) of
  ``StateBuilder.apply_events``'s dispatch chain and the
  ``MutableState.replicate_*`` write sets, mapped onto schema columns.
* **Diff** — unhandled-by-kernel event types, dead transition blocks,
  per-group column/table writes outside the oracle's mask (and oracle
  writes the kernel misses).
* **Schema invariants** — column constants dense + unique per table,
  pack.py ``attrs[i]`` stores inside the EV_A window, and
  ``ROW_TS_COLS`` (the epoch-rebase set ``rebase_state_row`` shifts)
  exactly equal to the traced set of epoch-bearing columns. A stale
  entry here is the bug class the checkpoint ``transition_fingerprint``
  can only detect, never localize.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

import numpy as np

from .findings import Finding
from . import oracle_ast

# --------------------------------------------------------------------------
# Schema column-group reflection
# --------------------------------------------------------------------------

# (prefix, count constant) per column table — schema.py owns the tuple
# so a new table added there is automatically covered by this pass
from cadence_tpu.ops.schema import _COLUMN_GROUPS as COLUMN_GROUPS  # noqa: E402


def _schema_ns() -> dict:
    from cadence_tpu.ops import schema as S

    return vars(S)


def column_names(
    prefix: str, count_name: str, ns: Optional[dict] = None
) -> Dict[int, List[str]]:
    """{column value → constant names} for one prefix (a well-formed
    table has exactly one name per value 0..N-1)."""
    ns = ns if ns is not None else _schema_ns()
    out: Dict[int, List[str]] = {}
    for k, v in ns.items():
        if (
            k.startswith(prefix)
            and k != count_name
            and isinstance(v, int)
            and not isinstance(v, bool)
        ):
            out.setdefault(v, []).append(k)
    for names in out.values():
        names.sort()
    return out


def check_column_groups(ns: Optional[dict] = None) -> List[Finding]:
    """Density + uniqueness of the schema column constants."""
    ns = ns if ns is not None else _schema_ns()
    findings: List[Finding] = []
    for prefix, count_name in COLUMN_GROUPS:
        if count_name not in ns:
            findings.append(Finding(
                "SCHEMA-COLUMNS", f"schema:{prefix}",
                f"missing count constant {count_name}",
            ))
            continue
        n = ns[count_name]
        by_val = column_names(prefix, count_name, ns)
        for v, names in sorted(by_val.items()):
            if len(names) > 1:
                findings.append(Finding(
                    "SCHEMA-COLUMNS", f"schema:{prefix}{v}:dup",
                    f"column value {v} claimed by {', '.join(names)}",
                ))
            if not (0 <= v < n):
                findings.append(Finding(
                    "SCHEMA-COLUMNS", f"schema:{prefix}{v}:range",
                    f"{names[0]} = {v} outside [0, {count_name}={n})",
                ))
        missing = sorted(set(range(n)) - set(by_val))
        if missing:
            findings.append(Finding(
                "SCHEMA-COLUMNS", f"schema:{prefix}:dense",
                f"no constant for column value(s) {missing} "
                f"(table not dense under {count_name}={n})",
            ))
    return findings


def check_pack_attrs(pack_source: str, ns: Optional[dict] = None) -> List[Finding]:
    """Every ``attrs[i]`` store in pack_workflow must land inside the
    EV_A0..EV_A(window-1) event-row window."""
    ns = ns if ns is not None else _schema_ns()
    window = ns["EV_N"] - ns["EV_A0"]
    findings: List[Finding] = []
    for i in sorted(oracle_ast.extract_attr_indices(pack_source)):
        if not (0 <= i < window):
            findings.append(Finding(
                "SCHEMA-PACK-ATTR", f"pack:attrs[{i}]",
                f"pack_workflow stores attrs[{i}] but the event row has "
                f"only {window} attribute columns (EV_A0..EV_A{window - 1})",
            ))
    return findings


# --------------------------------------------------------------------------
# Kernel matrix: jaxpr trace of replay_step_cols per transition group
# --------------------------------------------------------------------------


def _carry_labels() -> tuple:
    """Label pytree mirroring ops.replay.state_to_cols output structure."""
    ns = _schema_ns()

    def names(prefix: str, count: str) -> List[str]:
        by_val = column_names(prefix, count, ns)
        return [by_val[i][0] for i in range(ns[count])]

    return (
        tuple(f"exec:{n}" for n in names("X_", "X_N")),
        "vh:event_id",
        "vh:version",
        "vh:len",
        tuple(f"activities:{n}" for n in names("AC_", "AC_N")),
        tuple(f"timers:{n}" for n in names("TI_", "TI_N")),
        tuple(f"children:{n}" for n in names("CH_", "CH_N")),
        tuple(f"cancels:{n}" for n in names("RC_", "RC_N")),
        tuple(f"signals:{n}" for n in names("SG_", "SG_N")),
    )


def _ev_labels() -> tuple:
    ns = _schema_ns()
    by_val = column_names("EV_", "EV_N", ns)
    return tuple(f"ev:{by_val[i][0]}" for i in range(ns["EV_N"]))


class _EvCols:
    """Duck-typed event tensor: ``ev[:, c]`` returns column leaf ``c``.

    replay_step_cols only ever does static column slices of the event
    row, so feeding the columns as independent leaves keeps per-column
    provenance visible in the jaxpr."""

    def __init__(self, cols: tuple) -> None:
        self._cols = cols

    def __getitem__(self, idx):
        return self._cols[idx[1]]


def _literal_type():
    try:
        from jax.extend.core import Literal  # jax >= 0.4.x new home
        return Literal
    except Exception:
        from jax.core import Literal
        return Literal


def _trace_written(types: Optional[tuple], batch: int = 4):
    """Trace replay_step_cols with a static type set; returns
    (written labels, {written label → input-label dependency set})."""
    import jax

    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.replay import replay_step_cols, state_to_cols

    caps = S.Capacities(
        max_events=8, max_activities=3, max_timers=2, max_children=2,
        max_request_cancels=2, max_signals_ext=2, max_version_items=2,
    )
    cols = state_to_cols(S.empty_state(batch, caps))
    ev_cols = tuple(
        np.zeros((batch,), np.int32) for _ in range(S.EV_N)
    )

    def fn(c, ev):
        return replay_step_cols(c, _EvCols(ev), types=types)

    closed = jax.make_jaxpr(fn)(cols, ev_cols)
    jaxpr = closed.jaxpr
    in_labels = list(
        jax.tree_util.tree_leaves((_carry_labels(), _ev_labels()))
    )
    Literal = _literal_type()
    env: dict = {}
    for var, lab in zip(jaxpr.invars, in_labels):
        env[var] = frozenset((lab,))
    empty: FrozenSet[str] = frozenset()

    def deps_of(atom) -> FrozenSet[str]:
        if isinstance(atom, Literal):
            return empty
        return env.get(atom, empty)

    for eqn in jaxpr.eqns:
        dep: FrozenSet[str] = empty
        for v in eqn.invars:
            dep = dep | deps_of(v)
        for ov in eqn.outvars:
            env[ov] = dep

    out_labels = list(jax.tree_util.tree_leaves(_carry_labels()))
    written: Set[str] = set()
    deps: Dict[str, FrozenSet[str]] = {}
    for i, (ov, lab) in enumerate(zip(jaxpr.outvars, out_labels)):
        if isinstance(ov, Literal) or ov is not jaxpr.invars[i]:
            written.add(lab)
            deps[lab] = deps_of(ov)
    return written, deps


@dataclasses.dataclass
class GroupTrace:
    types: Tuple[int, ...]           # event types gating the block
    written: Set[str]                # state labels written (beyond common)
    ts_cols: Set[str]                # labels whose value derives from a
                                     # timestamp-bearing event column


@dataclasses.dataclass
class KernelMatrix:
    common: Set[str]                 # preamble writes (every valid event)
    common_ts: Set[str]
    groups: List[GroupTrace]

    def handled_types(self) -> Set[int]:
        out: Set[int] = set()
        for g in self.groups:
            out.update(g.types)
        return out

    def ts_columns(self) -> Set[str]:
        out = set(self.common_ts)
        for g in self.groups:
            out.update(g.ts_cols)
        return out


def _ts_inputs_for(
    types: Sequence[int], rel_ts_attrs: Dict[str, Set[int]]
) -> Set[str]:
    """Event-column labels carrying epoch-relative timestamps for this
    group: EV_TS always, plus every EV_A{i} the packer fills from
    rel_ts() for a member type."""
    from cadence_tpu.core.enums import EventType

    out = {"ev:EV_TS"}
    for t in types:
        for i in rel_ts_attrs.get(EventType(t).name, ()):
            out.add(f"ev:EV_A{i}")
    return out


def kernel_matrix(
    rel_ts_attrs: Optional[Dict[str, Set[int]]] = None,
) -> KernelMatrix:
    """Trace every transition group; ``rel_ts_attrs`` comes from
    oracle_ast.extract_rel_ts_attrs over ops/pack.py (empty dict: only
    EV_TS counts as timestamp-bearing)."""
    from cadence_tpu.ops.replay import _type_groups

    rel_ts_attrs = rel_ts_attrs or {}
    common, common_deps = _trace_written(types=())
    common_ts_in = _ts_inputs_for([], rel_ts_attrs)
    common_ts = {
        lab for lab, d in common_deps.items() if d & common_ts_in
    }
    groups: List[GroupTrace] = []
    for g in _type_groups():
        types = tuple(sorted(int(t) for t in g))
        written, deps = _trace_written(types=types)
        ts_in = _ts_inputs_for(types, rel_ts_attrs)
        groups.append(GroupTrace(
            types=types,
            written=written - common,
            ts_cols={
                lab for lab, d in deps.items()
                if d & ts_in and lab not in common
            },
        ))
    return KernelMatrix(common=common, common_ts=common_ts, groups=groups)


def kernel_handled_types() -> Set[int]:
    """Event types with a transition block in the kernel — no trace
    needed, the group table is the source of truth."""
    from cadence_tpu.ops.replay import _type_groups

    return {int(t) for g in _type_groups() for t in g}


# --------------------------------------------------------------------------
# Oracle table → schema columns
# --------------------------------------------------------------------------

# MutableState.execution_info field → kernel exec column label.
EXEC_FIELD_TO_COL = {
    "state": "exec:X_STATE",
    "close_status": "exec:X_CLOSE_STATUS",
    "next_event_id": "exec:X_NEXT_EVENT_ID",
    "last_first_event_id": "exec:X_LAST_FIRST_EVENT_ID",
    "last_event_task_id": "exec:X_LAST_EVENT_TASK_ID",
    "last_processed_event": "exec:X_LAST_PROCESSED_EVENT",
    "start_timestamp": "exec:X_START_TS",
    "workflow_timeout": "exec:X_WORKFLOW_TIMEOUT",
    "decision_timeout_value": "exec:X_DECISION_TIMEOUT_VALUE",
    "decision_version": "exec:X_DEC_VERSION",
    "decision_schedule_id": "exec:X_DEC_SCHEDULE_ID",
    "decision_started_id": "exec:X_DEC_STARTED_ID",
    "decision_timeout": "exec:X_DEC_TIMEOUT",
    "decision_attempt": "exec:X_DEC_ATTEMPT",
    "decision_scheduled_timestamp": "exec:X_DEC_SCHEDULED_TS",
    "decision_started_timestamp": "exec:X_DEC_STARTED_TS",
    "decision_original_scheduled_timestamp":
        "exec:X_DEC_ORIGINAL_SCHEDULED_TS",
    "cancel_requested": "exec:X_CANCEL_REQUESTED",
    "signal_count": "exec:X_SIGNAL_COUNT",
    "attempt": "exec:X_ATTEMPT",
    "has_retry_policy": "exec:X_HAS_RETRY_POLICY",
    "completion_event_batch_id": "exec:X_COMPLETION_EVENT_BATCH_ID",
    "initiated_id": "exec:X_PARENT_INITIATED_ID",
    "expiration_time": "exec:X_WF_EXPIRATION_TS",
}

# Host-only execution_info fields: strings, payloads, client metadata,
# retry-policy details kept host-side, persistence bookkeeping. Writes
# here have no device column, by design (the side table carries them).
EXEC_FIELD_IGNORE = {
    "domain_id", "workflow_id", "run_id", "parent_domain_id",
    "parent_workflow_id", "parent_run_id", "task_list",
    "workflow_type_name", "execution_context", "last_updated_timestamp",
    "create_request_id", "decision_request_id", "cancel_request_id",
    "sticky_task_list", "sticky_schedule_to_start_timeout",
    "client_library_version", "client_feature_version", "client_impl",
    "auto_reset_points", "memo", "search_attributes",
    "initial_interval", "backoff_coefficient", "maximum_interval",
    "maximum_attempts", "non_retriable_errors", "branch_token",
    "cron_schedule", "expiration_seconds",
    "first_decision_backoff_deadline", "history_size",
}


@dataclasses.dataclass
class OracleEntry:
    handlers: Tuple[str, ...]
    is_noop: bool
    tables: Set[str]          # pending-map tables touched
    exec_cols: Set[str]       # mapped exec column labels
    unmapped_fields: Set[str]  # exec fields neither mapped nor ignored

    def device_writes(self) -> Set[str]:
        return set(self.exec_cols) | set(self.tables)


def oracle_table(
    state_builder_source: str, mutable_state_source: str
) -> Dict[str, OracleEntry]:
    """{EventType name → oracle write surface in schema terms}."""
    dispatch = oracle_ast.extract_event_dispatch(state_builder_source)
    writes = oracle_ast.extract_replicate_writes(mutable_state_source)
    out: Dict[str, OracleEntry] = {}
    for tname, branch in dispatch.items():
        tables: Set[str] = set()
        exec_cols: Set[str] = set()
        unmapped: Set[str] = set()
        for h in branch.handler_calls:
            ws = writes.get(h)
            if ws is None:
                continue
            tables |= ws.tables
            for f in ws.exec_fields:
                if f in EXEC_FIELD_TO_COL:
                    exec_cols.add(EXEC_FIELD_TO_COL[f])
                elif f not in EXEC_FIELD_IGNORE:
                    unmapped.add(f)
        out[tname] = OracleEntry(
            handlers=branch.handler_calls,
            is_noop=branch.is_noop,
            tables=tables,
            exec_cols=exec_cols,
            unmapped_fields=unmapped,
        )
    return out


def _split_kernel_writes(written: Set[str]) -> Tuple[Set[str], Set[str]]:
    """(exec column labels, pending-map tables) of a kernel write set.
    Slot tables are compared at table granularity: the kernel writes
    whole rows under one-hot masks, the oracle mutates map entries —
    per-field comparison across that boundary would only mirror the
    kernel back at itself."""
    exec_cols = {w for w in written if w.startswith("exec:")}
    tables = {
        w.split(":", 1)[0]
        for w in written
        if w.split(":", 1)[0] in (
            "activities", "timers", "children", "cancels", "signals"
        )
    }
    return exec_cols, tables


def diff_surface(
    kmat: KernelMatrix,
    otable: Dict[str, OracleEntry],
    pack_handled: Optional[Set[str]] = None,
) -> List[Finding]:
    """Diff the kernel matrix against the oracle table."""
    from cadence_tpu.core.enums import EventType

    findings: List[Finding] = []
    handled = kmat.handled_types()
    handled_names = {EventType(t).name for t in handled}

    # oracle handlers without unmapped-field contract coverage
    for tname, entry in sorted(otable.items()):
        if entry.unmapped_fields:
            findings.append(Finding(
                "SURFACE-UNMAPPED-FIELD", f"surface:{tname}:unmapped",
                f"oracle handler(s) {', '.join(entry.handlers)} write "
                f"execution_info fields {sorted(entry.unmapped_fields)} "
                "that are neither mapped to a device column nor in the "
                "host-only ignore set — extend "
                "analysis.transition_surface.EXEC_FIELD_TO_COL",
            ))

    # unhandled-by-kernel: the oracle mutates device-mapped state for a
    # type the kernel has no transition block for
    for tname, entry in sorted(otable.items()):
        if tname in handled_names:
            continue
        if entry.device_writes():
            findings.append(Finding(
                "SURFACE-UNHANDLED", f"surface:{tname}:unhandled",
                f"event type {tname} writes {sorted(entry.device_writes())} "
                "in the host oracle but has no kernel transition block",
            ))

    # dead transition blocks: kernel block for a type the oracle
    # dispatch chain does not even accept
    for t in sorted(handled):
        tname = EventType(t).name
        if tname not in otable:
            findings.append(Finding(
                "SURFACE-DEAD-BLOCK", f"surface:{tname}:dead",
                f"kernel has a transition block for {tname} but the "
                "oracle dispatch chain does not handle it",
            ))

    # pack-layer coverage: every oracle-handled type must be packable
    if pack_handled is not None:
        for tname in sorted(otable):
            if tname not in pack_handled:
                findings.append(Finding(
                    "SURFACE-PACK-UNKNOWN", f"surface:{tname}:pack",
                    f"oracle handles {tname} but pack_workflow's dispatch "
                    "chain would reject it (PackError: unknown event type)",
                ))

    # per-group mask diff
    for g in kmat.groups:
        names = sorted(EventType(t).name for t in g.types)
        anchor_base = f"surface:{names[0]}"
        k_exec, k_tables = _split_kernel_writes(g.written)
        o_exec: Set[str] = set()
        o_tables: Set[str] = set()
        for t in g.types:
            entry = otable.get(EventType(t).name)
            if entry is None:
                continue
            o_exec |= entry.exec_cols
            o_tables |= entry.tables
        # columns in the kernel's common preamble (written for EVERY
        # valid event) can never be "missing" from a group
        common_exec, common_tables = _split_kernel_writes(kmat.common)
        extra = sorted((k_exec - o_exec) | (k_tables - o_tables))
        missing = sorted(
            (o_exec - k_exec - common_exec)
            | (o_tables - k_tables - common_tables)
        )
        if extra:
            findings.append(Finding(
                "SURFACE-EXTRA-WRITE", f"{anchor_base}:extra",
                f"kernel group {names} writes {extra} which the oracle "
                "handlers never touch (write outside the type's mask)",
            ))
        if missing:
            findings.append(Finding(
                "SURFACE-MISSING-WRITE", f"{anchor_base}:missing",
                f"oracle handlers for {names} write {missing} which the "
                "kernel group never writes",
            ))
    return findings


def check_ts_coverage(
    kmat: KernelMatrix, ns: Optional[dict] = None
) -> List[Finding]:
    """ROW_TS_COLS (what rebase_state_row shifts between epochs) must
    equal the traced set of epoch-bearing state columns."""
    ns = ns if ns is not None else _schema_ns()
    row_ts = ns["ROW_TS_COLS"]
    field_prefix = {
        "exec_info": ("exec", "X_", "X_N"),
        "activities": ("activities", "AC_", "AC_N"),
        "timers": ("timers", "TI_", "TI_N"),
        "children": ("children", "CH_", "CH_N"),
        "cancels": ("cancels", "RC_", "RC_N"),
        "signals": ("signals", "SG_", "SG_N"),
    }
    declared: Set[str] = set()
    for field, cols in row_ts.items():
        label, prefix, count = field_prefix[field]
        by_val = column_names(prefix, count, ns)
        for c in cols:
            declared.add(f"{label}:{by_val[c][0]}")
    traced = {
        c for c in kmat.ts_columns()
        if not c.startswith("vh:")  # vh carries ids/versions, never ts
    }
    findings: List[Finding] = []
    for c in sorted(traced - declared):
        findings.append(Finding(
            "SURFACE-TS-UNCOVERED", f"ts:{c}",
            f"{c} derives from an epoch-relative timestamp in the kernel "
            "but is missing from schema.ROW_TS_COLS — rebase_state_row "
            "will not shift it and cross-epoch checkpoint resume will "
            "read a stale absolute time",
        ))
    for c in sorted(declared - traced):
        findings.append(Finding(
            "SURFACE-TS-STALE", f"ts:{c}",
            f"schema.ROW_TS_COLS declares {c} epoch-bearing but no "
            "kernel transition derives it from a timestamp column",
        ))
    return findings


# --------------------------------------------------------------------------
# Orchestration + matrix artifact
# --------------------------------------------------------------------------


def _read(repo_root: str, rel: str) -> str:
    with open(os.path.join(repo_root, rel)) as f:
        return f.read()


def build(repo_root: str):
    """(kernel matrix, oracle table, pack-handled names, rel_ts attrs)."""
    sb_src = _read(repo_root, "cadence_tpu/core/state_builder.py")
    ms_src = _read(repo_root, "cadence_tpu/core/mutable_state.py")
    pack_src = _read(repo_root, "cadence_tpu/ops/pack.py")
    rel_ts = oracle_ast.extract_rel_ts_attrs(pack_src)
    kmat = kernel_matrix(rel_ts_attrs=rel_ts)
    otable = oracle_table(sb_src, ms_src)
    pack_handled = set(
        oracle_ast.extract_event_dispatch(
            pack_src, func_name="pack_workflow"
        )
    )
    return kmat, otable, pack_handled, rel_ts


def run(repo_root: str) -> List[Finding]:
    pack_src = _read(repo_root, "cadence_tpu/ops/pack.py")
    findings = check_column_groups()
    findings += check_pack_attrs(pack_src)
    kmat, otable, pack_handled, _ = build(repo_root)
    findings += diff_surface(kmat, otable, pack_handled=pack_handled)
    findings += check_ts_coverage(kmat)
    return findings


def emit_matrix(repo_root: str, path: str) -> None:
    """Write the transition coverage matrix as a JSON build artifact
    (versioned via the shared artifact envelope, like the queue
    conflict matrix — downstream consumers validate schema_version +
    kind instead of guessing from the file name)."""
    from cadence_tpu.core.enums import EventType

    from .artifact import write_artifact

    kmat, otable, pack_handled, rel_ts = build(repo_root)
    doc = {
        "common": sorted(kmat.common),
        "common_ts": sorted(kmat.common_ts),
        "kernel_handled_types": sorted(
            EventType(t).name for t in kmat.handled_types()
        ),
        "pack_handled_types": sorted(pack_handled),
        "rel_ts_attrs": {k: sorted(v) for k, v in sorted(rel_ts.items())},
        "groups": [
            {
                "types": sorted(EventType(t).name for t in g.types),
                "written": sorted(g.written),
                "ts_columns": sorted(g.ts_cols),
            }
            for g in kmat.groups
        ],
        "oracle": {
            tname: {
                "handlers": list(e.handlers),
                "noop": e.is_noop,
                "tables": sorted(e.tables),
                "exec_cols": sorted(e.exec_cols),
            }
            for tname, e in sorted(otable.items())
        },
    }
    write_artifact(path, "transition_matrix", doc)

"""Tests for the static-analysis gate (cadence_tpu/analysis).

Two halves:

* **known-bad fixtures** — per pass, a minimal snippet that violates
  each rule, proving the rule actually fires (a lint that never fires
  is indistinguishable from no lint);
* **clean-tree gate** — running all five passes over this repository
  yields zero non-baselined findings, within the < 5 s CPU budget.
  This is the tier-1 embodiment of the CI gate (scripts/run_lint.sh is
  the standalone wrapper).
"""

import ast as astmod
import json
import os
import textwrap
import time

import pytest

from cadence_tpu.analysis import Baseline, BaselineEntry, Finding, run_all
from cadence_tpu.analysis import jit_hazards, lock_order, transition_surface
from cadence_tpu.analysis.findings import dedupe
from cadence_tpu.analysis import oracle_ast

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return {f.rule for f in findings}


# --------------------------------------------------------------------------
# baseline plumbing
# --------------------------------------------------------------------------


class TestBaseline:
    def test_exact_and_wildcard_matching(self):
        bl = Baseline([
            BaselineEntry("R1", "mod.py:Class.m:_lock:io", "known"),
            BaselineEntry("R2", "mod.py:Class.*", "family"),
        ])
        fs = [
            Finding("R1", "mod.py:Class.m:_lock:io", "x"),
            Finding("R2", "mod.py:Class.other:_lock:io", "y"),
            Finding("R1", "mod.py:Class.NEW:_lock:io", "z"),  # new
        ]
        new, accepted, stale = bl.split(fs)
        assert [f.anchor for f in new] == ["mod.py:Class.NEW:_lock:io"]
        assert len(accepted) == 2 and not stale

    def test_stale_entries_reported(self):
        bl = Baseline([BaselineEntry("R1", "gone:*", "fixed long ago")])
        new, accepted, stale = bl.split([])
        assert not new and not accepted and len(stale) == 1

    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "bl.json")
        Baseline([BaselineEntry("R", "a:*", "j")]).save(p)
        loaded = Baseline.load(p)
        assert loaded.entries[0].anchor == "a:*"
        assert loaded.entries[0].justification == "j"


# --------------------------------------------------------------------------
# pass 1 — transition surface
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def surface():
    """(kernel matrix, oracle table, pack handled) over the real tree —
    traced once per test module, shared by the fixture tests."""
    return transition_surface.build(REPO_ROOT)


class TestTransitionSurface:
    def test_schema_invariants_clean(self):
        assert transition_surface.check_column_groups() == []

    def test_duplicate_column_fires(self):
        ns = {"EV_A": 0, "EV_B": 0, "EV_N": 1}
        fs = transition_surface.check_column_groups(
            {**{c: 0 for _, c in transition_surface.COLUMN_GROUPS}, **ns}
        )
        assert any(
            f.rule == "SCHEMA-COLUMNS" and "EV_A" in f.message for f in fs
        )

    def test_gap_and_range_fire(self):
        base = {c: 0 for _, c in transition_surface.COLUMN_GROUPS}
        ns = {**base, "X_N": 3, "X_A": 0, "X_B": 5}
        fs = transition_surface.check_column_groups(ns)
        assert any("outside" in f.message for f in fs)          # X_B=5
        assert any("not dense" in f.message or "no constant"
                   in f.message for f in fs)                    # 1,2 missing

    def test_pack_attr_window_fires(self):
        src = textwrap.dedent("""
            def pack_workflow(batches):
                attrs = [0] * 8
                attrs[3] = 1
                attrs[9] = 2
        """)
        fs = transition_surface.check_pack_attrs(src)
        assert [f.rule for f in fs] == ["SCHEMA-PACK-ATTR"]
        assert "attrs[9]" in fs[0].message

    def test_unhandled_type_fires(self, surface):
        kmat, _, _, _ = surface
        # MarkerRecorded has no kernel block; claim the oracle writes
        # device state for it → the checker must flag the gap
        fake = {
            "MarkerRecorded": transition_surface.OracleEntry(
                handlers=("replicate_marker",), is_noop=False,
                tables={"timers"}, exec_cols=set(), unmapped_fields=set(),
            )
        }
        fs = transition_surface.diff_surface(kmat, fake)
        assert any(f.rule == "SURFACE-UNHANDLED" for f in fs)

    def test_dead_block_fires(self, surface):
        kmat, _, _, _ = surface
        # empty oracle table → every kernel block is dead
        fs = transition_surface.diff_surface(kmat, {})
        dead = [f for f in fs if f.rule == "SURFACE-DEAD-BLOCK"]
        assert len(dead) == len(kmat.handled_types())

    def test_mask_mismatch_fires(self, surface):
        kmat, otable, _, _ = surface
        # claim TimerStarted touches children instead of timers
        fake = dict(otable)
        fake["TimerStarted"] = transition_surface.OracleEntry(
            handlers=("replicate_timer_started_event",), is_noop=False,
            tables={"children"}, exec_cols=set(), unmapped_fields=set(),
        )
        fs = transition_surface.diff_surface(kmat, fake)
        anchors = {f.anchor for f in fs}
        assert "surface:TimerStarted:extra" in anchors     # kernel: timers
        assert "surface:TimerStarted:missing" in anchors   # oracle: children

    def test_ts_coverage_gap_fires(self, surface):
        kmat, _, _, _ = surface
        from cadence_tpu.ops import schema as S

        ns = dict(vars(S))
        # drop the timer-expiry column from the rebase set
        ns["ROW_TS_COLS"] = {
            k: tuple(c for c in v if (k, c) != ("timers", S.TI_EXPIRY_TS))
            for k, v in S.ROW_TS_COLS.items()
        }
        fs = transition_surface.check_ts_coverage(kmat, ns)
        assert any(
            f.rule == "SURFACE-TS-UNCOVERED" and "TI_EXPIRY_TS" in f.anchor
            for f in fs
        )

    def test_ts_stale_fires(self, surface):
        kmat, _, _, _ = surface
        from cadence_tpu.ops import schema as S

        ns = dict(vars(S))
        # declare a non-timestamp column epoch-bearing
        ns["ROW_TS_COLS"] = {
            **S.ROW_TS_COLS,
            "children": (S.CH_POLICY,),
        }
        fs = transition_surface.check_ts_coverage(kmat, ns)
        assert any(f.rule == "SURFACE-TS-STALE" for f in fs)

    def test_kernel_matrix_sanity(self, surface):
        kmat, otable, pack_handled, rel_ts = surface
        from cadence_tpu.core.enums import EventType, NUM_EVENT_TYPES

        handled = kmat.handled_types()
        # the four deliberate device-no-ops are the only unhandled types
        unhandled = {
            EventType(t).name
            for t in range(NUM_EVENT_TYPES) if t not in handled
        }
        assert unhandled == {
            "RequestCancelActivityTaskFailed", "CancelTimerFailed",
            "MarkerRecorded", "UpsertWorkflowSearchAttributes",
        }
        # pack accepts everything the oracle replays
        assert set(otable) <= pack_handled
        # the traced matrix sees through the packer: wf expiration rides
        # EV_A4 (rel_ts) into X_WF_EXPIRATION_TS
        assert rel_ts.get("WorkflowExecutionStarted") == {4}
        started = next(
            g for g in kmat.groups
            if g.types == (int(EventType.WorkflowExecutionStarted),)
        )
        assert "exec:X_WF_EXPIRATION_TS" in started.ts_cols
        assert "exec:X_START_TS" in started.ts_cols

    def test_oracle_ast_extraction(self):
        src = textwrap.dedent("""
            def apply_events(self, history):
                for event in history:
                    et = event.event_type
                    if et == EventType.TimerStarted:
                        ms.replicate_timer_started_event(event)
                    elif et in (EventType.TimerFired, EventType.TimerCanceled):
                        ms.replicate_timer_closed(event)
                    elif et == EventType.MarkerRecorded:
                        pass
                    else:
                        raise ValueError
        """)
        table = oracle_ast.extract_event_dispatch(src)
        assert table["TimerStarted"].handler_calls == (
            "replicate_timer_started_event",
        )
        assert table["TimerFired"].handler_calls == ("replicate_timer_closed",)
        assert table["MarkerRecorded"].is_noop
        assert "WorkflowExecutionStarted" not in table

    def test_replicate_write_closure(self):
        src = textwrap.dedent("""
            class MutableState:
                def _helper(self):
                    self.execution_info.state = 1
                    del self.pending_timers[0]
                def replicate_x(self, event):
                    ei = self.execution_info
                    ei.signal_count += 1
                    self._helper()
        """)
        writes = oracle_ast.extract_replicate_writes(src)
        ws = writes["replicate_x"]
        assert ws.exec_fields == {"signal_count", "state"}
        assert ws.tables == {"timers"}

    def test_emit_matrix_artifact(self, tmp_path):
        from cadence_tpu.analysis.artifact import SCHEMA_VERSION

        path = str(tmp_path / "matrix.json")
        transition_surface.emit_matrix(REPO_ROOT, path)
        doc = json.load(open(path))
        assert doc["groups"] and doc["oracle"]
        assert "WorkflowExecutionStarted" in doc["kernel_handled_types"]
        assert "exec:X_NEXT_EVENT_ID" in doc["common"]
        # versioned envelope shared with the conflict matrix
        assert doc["schema_version"] == SCHEMA_VERSION
        assert doc["artifact"] == "transition_matrix"



# --------------------------------------------------------------------------
# pass 2 — jit hazards
# --------------------------------------------------------------------------


class TestJitHazards:
    def test_host_sync_fixtures_fire(self):
        src = textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np

            def step(state, ev):
                x = state[0].item()
                y = float(ev[0])
                z = np.asarray(state[1])
                return state

            step_jit = jax.jit(step)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        sync = [f for f in fs if f.rule == "JIT-HOST-SYNC"]
        kinds = {f.anchor.rsplit(":", 1)[-1] for f in sync}
        assert {"item", "float", "np.asarray"} <= kinds

    def test_py_branch_fixture_fires(self):
        src = textwrap.dedent("""
            import jax

            def step(state, ev):
                if ev[0] > 0:
                    state = state
                return state

            step_jit = jax.jit(step)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert any(f.rule == "JIT-PY-BRANCH" for f in fs)

    def test_none_checks_stay_legal(self):
        src = textwrap.dedent("""
            import jax

            def step(state, mask):
                if mask is not None:
                    state = state
                return state

            step_jit = jax.jit(step)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert not any(f.rule == "JIT-PY-BRANCH" for f in fs)

    def test_unrounded_shape_fixture_fires(self):
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def drive(histories):
                state = jnp.zeros((len(histories), 16))
                return replay_scan_jit(state)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert any(f.rule == "JIT-SHAPE-ROUND" for f in fs)

    def test_rounded_shape_passes(self):
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def drive(histories):
                state = jnp.zeros((round_scan_len(len(histories)), 16))
                return replay_scan_jit(state)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert not any(f.rule == "JIT-SHAPE-ROUND" for f in fs)

    def test_narrow_force_wide_fixture_fires(self):
        src = textwrap.dedent("""
            def pack(teb):
                return narrow_events_teb(teb)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert [f.rule for f in fs] == ["JIT-NARROW-FORCE-WIDE"]

    def test_traced_function_discovery(self):
        src = textwrap.dedent("""
            import jax

            def leaf(x):
                return x

            def root(x):
                return leaf(x)

            def host(x):
                return root_jit(x)

            root_jit = jax.jit(root, donate_argnums=(0,))
        """)
        import ast as astmod

        traced = jit_hazards.traced_functions(astmod.parse(src))
        assert traced == {"root", "leaf"}

    def test_dtype_widen_fires_on_float(self):
        import jax
        import numpy as np

        def bad(x):
            return x * 1.5  # promotes to float

        closed = jax.make_jaxpr(bad)(np.zeros((2,), np.int32))
        fs = jit_hazards.trace_dtype_findings(closed, "fix:bad")
        assert any(f.rule == "JIT-DTYPE-WIDEN" for f in fs)

    def test_real_step_stays_int32(self):
        assert jit_hazards.check_step_dtypes() == []



    def test_pallas_int16_arith_fixture_fires(self):
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def kern(ev_ref, out_ref):
                lo = ev_ref[0].astype(jnp.int16)
                acc = lo * 3
                out_ref[0] = acc + lo

            def call(ev):
                return pl.pallas_call(kern)(ev)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert any(f.rule == "PALLAS-INT16-ARITH" for f in fs), fs

    def test_pallas_int16_widened_passes(self):
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def kern(ev_ref, out_ref):
                lo = ev_ref[0].astype(jnp.int16).astype(jnp.int32)
                out_ref[0] = lo * 3 + 1

            def call(ev):
                return pl.pallas_call(kern)(ev)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert not any(f.rule == "PALLAS-INT16-ARITH" for f in fs), fs

    def test_pallas_int16_renarrowed_after_widen_fires(self):
        # classification is line-ordered: a name widened early but
        # re-assigned from an int16 cast later is narrow at the use —
        # a whole-function widened-set would miss this
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def kern(a_ref, b_ref, out_ref):
                x = a_ref[0].astype(jnp.int32)
                y = x + 1
                x = b_ref[0].astype(jnp.int16)
                out_ref[0] = x * 3

            def call(ev):
                return pl.pallas_call(kern)(ev)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert any(f.rule == "PALLAS-INT16-ARITH" for f in fs), fs

    def test_pallas_int16_rewiden_after_narrow_passes(self):
        # the inverse order stays clean: narrow first, widened before
        # every arithmetic use
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def kern(a_ref, out_ref):
                x = a_ref[0].astype(jnp.int16)
                x = x.astype(jnp.int32)
                out_ref[0] = x * 3

            def call(ev):
                return pl.pallas_call(kern)(ev)
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert not any(f.rule == "PALLAS-INT16-ARITH" for f in fs), fs

    def test_pallas_int16_outside_kernel_ignored(self):
        # host-side narrowing (the packer) is the narrow stream's
        # legitimate producer — only Pallas kernel bodies are in scope
        src = textwrap.dedent("""
            import jax.numpy as jnp

            def host_pack(ev):
                lo = ev.astype(jnp.int16)
                return lo * 1
        """)
        fs = jit_hazards.lint_source(src, "fix.py")
        assert not any(f.rule == "PALLAS-INT16-ARITH" for f in fs), fs


# --------------------------------------------------------------------------
# pass 3 — lock order
# --------------------------------------------------------------------------


def _lock_findings(src: str):
    classes = lock_order.analyze_module(src, "fix.py")
    return lock_order.collect_findings(classes)


class TestLockOrder:
    def test_sleep_under_lock_fires(self):
        src = textwrap.dedent("""
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def bad(self):
                    with self._lock:
                        time.sleep(1)
        """)
        fs = _lock_findings(src)
        assert any(
            f.rule == "LOCK-BLOCKING" and "sleep" in f.message for f in fs
        )

    def test_store_io_under_lock_fires(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def bad(self):
                    with self._lock:
                        self.persistence.shard.update_shard(1)
        """)
        fs = _lock_findings(src)
        assert any(f.rule == "LOCK-BLOCKING" for f in fs)

    def test_store_receiver_chain_fires_without_known_method(self):
        # the method name is NOT in STORE_METHODS; the receiver chain
        # naming a persistence manager must be enough
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def bad(self):
                    with self._lock:
                        self.persistence.workflow.load_everything(1)
        """)
        fs = _lock_findings(src)
        assert any(
            f.rule == "LOCK-BLOCKING" and "load_everything" in f.message
            for f in fs
        )

    def test_inversion_fires(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def one(self):
                    with self._a:
                        with self._b:
                            pass
                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        fs = _lock_findings(src)
        assert any(f.rule == "LOCK-INVERSION" for f in fs)

    def test_consistent_order_passes(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def one(self):
                    with self._a:
                        with self._b:
                            pass
                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """)
        fs = _lock_findings(src)
        assert not any(f.rule == "LOCK-INVERSION" for f in fs)

    def test_trylock_exempt(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def ok(self, other):
                    with self._lock:
                        if other.lock.acquire(blocking=False):
                            other.lock.release()
        """)
        assert _lock_findings(src) == []

    def test_wait_on_held_condition_exempt(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._cond = threading.Condition()
                def ok(self):
                    with self._cond:
                        self._cond.wait(1.0)
                def bad(self, event):
                    with self._cond:
                        event.wait(1.0)
        """)
        fs = _lock_findings(src)
        assert len(fs) == 1 and "ok" not in fs[0].anchor
        assert "C.bad" in fs[0].anchor

    def test_blocking_via_self_call_propagates(self):
        src = textwrap.dedent("""
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                def _persist(self):
                    self.persistence.shard.update_shard(1)
                def bad(self):
                    with self._lock:
                        self._persist()
        """)
        fs = _lock_findings(src)
        assert any("C.bad" in f.anchor and "_persist" in f.anchor for f in fs)

    # -- cross-class lock propagation ----------------------------------

    def test_cross_class_blocking_propagates(self):
        """A non-self receiver's method resolved by name: the callee's
        store I/O fires LOCK-CROSS-BLOCKING at the caller."""
        src = textwrap.dedent("""
            import threading

            class Shard:
                def fence_lease(self):
                    self.persistence.shard.update_shard(1)

            class Coordinator:
                def __init__(self):
                    self._lock = threading.Lock()
                def handoff(self, handle):
                    with self._lock:
                        handle.shard.fence_lease()
        """)
        fs = _lock_findings(src)
        hits = [f for f in fs if f.rule == "LOCK-CROSS-BLOCKING"]
        assert len(hits) == 1, fs
        assert "Coordinator.handoff" in hits[0].anchor
        assert "fence_lease" in hits[0].anchor
        assert "Shard.fence_lease" in hits[0].message

    def test_cross_class_ambiguous_name_skipped(self):
        """Two scope classes define the name and DISAGREE on blocking:
        name resolution must not guess (no finding)."""
        src = textwrap.dedent("""
            import threading

            class A:
                def work(self):
                    self.persistence.shard.update_shard(1)

            class B:
                def work(self):
                    return 1

            class Caller:
                def __init__(self):
                    self._lock = threading.Lock()
                def go(self, x):
                    with self._lock:
                        x.work()
        """)
        fs = _lock_findings(src)
        assert not any(f.rule == "LOCK-CROSS-BLOCKING" for f in fs), fs

    def test_cross_class_agreeing_candidates_fire(self):
        """Several scope classes define the name but ALL block —
        whichever instance it is, the caller stalls: fire."""
        src = textwrap.dedent("""
            import threading

            class A:
                def work(self):
                    self.persistence.shard.update_shard(1)

            class B:
                def work(self):
                    import time
                    time.sleep(1)

            class Caller:
                def __init__(self):
                    self._lock = threading.Lock()
                def go(self, x):
                    with self._lock:
                        x.work()
        """)
        fs = _lock_findings(src)
        assert any(f.rule == "LOCK-CROSS-BLOCKING" for f in fs), fs

    def test_cross_class_builtin_names_exempt(self):
        """A scope class named ``append`` must not hijack list.append —
        builtin container/protocol names never resolve cross-class."""
        src = textwrap.dedent("""
            import threading

            class Writer:
                def append(self):
                    self.persistence.shard.update_shard(1)

            class Caller:
                def __init__(self):
                    self._lock = threading.Lock()
                def go(self, items):
                    with self._lock:
                        items.append(1)
        """)
        fs = _lock_findings(src)
        assert not any(f.rule == "LOCK-CROSS-BLOCKING" for f in fs), fs

    def test_cross_class_inversion_fires(self):
        """The callee's lock joins the caller's edge graph: A holds its
        lock then takes B's (through b_hold()); B holds its lock then
        takes A's (through a_hold()) — deadlock-capable, and invisible
        to the in-class pass."""
        src = textwrap.dedent("""
            import threading

            class A:
                def __init__(self):
                    self._alock = threading.Lock()
                def a_then_b(self, b):
                    with self._alock:
                        b.b_hold()
                def a_hold(self):
                    with self._alock:
                        pass

            class B:
                def __init__(self):
                    self._block = threading.Lock()
                def b_then_a(self, a):
                    with self._block:
                        a.a_hold()
                def b_hold(self):
                    with self._block:
                        pass
        """)
        fs = _lock_findings(src)
        inv = [f for f in fs if f.rule == "LOCK-INVERSION"]
        assert len(inv) == 1, fs
        assert "A._alock" in inv[0].message and "B._block" in inv[0].message


# --------------------------------------------------------------------------
# the gate: clean tree against the checked-in baseline
# --------------------------------------------------------------------------


class TestMetricDecl:
    """Known-bad fixtures for pass 4 (METRIC-UNDECLARED): literal
    metric emissions must appear in a utils/metrics_defs.py catalog."""

    def _scan(self, src):
        from cadence_tpu.analysis import metric_decl

        return metric_decl.scan_source(
            textwrap.dedent(src), "fixture/mod.py",
            metric_decl.declared_names(),
        )

    def test_undeclared_literal_fires(self):
        fs = self._scan("""
            def emit(scope):
                scope.inc("totally_undocumented_counter")
        """)
        assert [f.rule for f in fs] == ["METRIC-UNDECLARED"]
        assert fs[0].anchor == (
            "fixture/mod.py:totally_undocumented_counter"
        )

    def test_all_emit_methods_covered(self):
        fs = self._scan("""
            def emit(scope):
                scope.inc("mystery_a")
                scope.gauge("mystery_b", 1.0)
                scope.record("mystery_c", 0.5)
        """)
        assert {f.anchor.split(":")[1] for f in fs} == {
            "mystery_a", "mystery_b", "mystery_c"
        }

    def test_declared_names_pass(self):
        fs = self._scan("""
            def emit(scope):
                scope.inc("task_requests")
                scope.gauge("replication_lag_events", 3)
                scope.record("host_stage_seconds", 0.1)
                scope.inc("requests")
        """)
        assert fs == []

    def test_dynamic_names_skipped(self):
        # f-strings and variables are outside the catalog contract
        # (the persistence decorator's per-API family)
        fs = self._scan("""
            def emit(scope, name):
                scope.inc(f"{name}.errors")
                scope.record(name, 0.1)
                scope.gauge(name + "_depth", 1)
        """)
        assert fs == []

    def test_unparseable_source_fails_loudly(self):
        fs = self._scan("def broken(:")
        assert [f.rule for f in fs] == ["METRIC-UNDECLARED"]
        assert "unparseable" in fs[0].message

    def test_catalog_union_includes_every_tuple(self):
        from cadence_tpu.analysis.metric_decl import declared_names
        from cadence_tpu.utils import metrics_defs as defs

        names = declared_names()
        for tup in (defs.QUEUE_METRICS, defs.REPLICATION_METRICS,
                    defs.CHECKPOINT_METRICS, defs.RESHARD_METRICS,
                    defs.DEVICE_METRICS, defs.TELEMETRY_METRICS,
                    defs.ENGINE_METRICS, defs.FAULT_METRICS):
            assert set(tup) <= names

    def test_pass_registered_in_run_all(self):
        from cadence_tpu.analysis import PASSES

        assert "metrics" in PASSES

    def test_real_tree_scan_is_clean(self):
        from cadence_tpu.analysis import metric_decl

        assert metric_decl.run(REPO_ROOT) == []


# --------------------------------------------------------------------------
# pass 5 — queue-task effect analysis
# --------------------------------------------------------------------------


def _queue_extract(src, clsname="P", enum="TransferTaskType"):
    """(dispatch table, per-method footprints) over a synthetic
    processor module."""
    from cadence_tpu.analysis import queue_effects

    tree = astmod.parse(textwrap.dedent(src))
    cls = queue_effects._class_def(tree, clsname)
    assert cls is not None
    module_funcs = {
        n.name for n in tree.body if isinstance(n, astmod.FunctionDef)
    }
    dispatch = queue_effects.extract_dispatch(cls, enum)
    fps = queue_effects.extract_method_footprints(cls, module_funcs)
    return dispatch, fps


def _queue_diff(src, declared, plane="transfer", enum="TransferTaskType"):
    from cadence_tpu.analysis import queue_effects

    dispatch, fps = _queue_extract(src, enum=enum)
    extracted = {
        (plane, t): ("fix.py", h,
                     queue_effects.ExtractedFootprint() if h == "<noop>"
                     else fps.get(h))
        for t, h in dispatch.items()
    }
    return queue_effects.diff_footprints(extracted, declared)


_CLEAN_PROCESSOR = """
    class P:
        def _process(self, task):
            handler = {
                TransferTaskType.DecisionTask: self._process_decision,
                TransferTaskType.ResetWorkflow: lambda t: None,
            }.get(task.task_type)
            handler(task)

        def _process_decision(self, task):
            target = self._read(task)
            self.matching.add_decision_task(task.domain_id)

        def _read(self, task):
            return self.engine.with_workflow(
                task.domain_id, lambda ctx, ms: ms
            )
"""


class TestQueueEffects:
    def test_dispatch_extraction_dict_and_noop(self):
        dispatch, _ = _queue_extract(_CLEAN_PROCESSOR)
        assert dispatch == {
            "DecisionTask": "_process_decision",
            "ResetWorkflow": "<noop>",
        }

    def test_dispatch_extraction_guard_idiom(self):
        dispatch, _ = _queue_extract("""
            class P:
                def _process(self, task):
                    if task.task_type == TimerTaskType.DeleteHistoryEvent:
                        self._delete_history(task)
                        return
                def _delete_history(self, task):
                    pass
        """, enum="TimerTaskType")
        assert dispatch == {"DeleteHistoryEvent": "_delete_history"}

    def test_footprint_closure_through_self_calls(self):
        _, fps = _queue_extract(_CLEAN_PROCESSOR)
        fp = fps["_process_decision"]
        # _read's with_workflow read folds into the caller (fixpoint)
        assert fp.reads == {"execution"}
        assert fp.writes == {"task_store"}
        assert not fp.unknown

    def test_clean_handler_passes(self):
        from cadence_tpu.runtime.queues.effects import Footprint

        declared = {("transfer", "DecisionTask"): Footprint(
            frozenset({"execution"}), frozenset({"task_store"}),
        ), ("transfer", "ResetWorkflow"): Footprint()}
        assert _queue_diff(_CLEAN_PROCESSOR, declared) == []

    def test_unknown_fires_on_untracked_helper(self):
        fs = _queue_diff("""
            class P:
                def _process(self, task):
                    handler = {
                        TransferTaskType.DecisionTask: self._h,
                    }.get(task.task_type)
                    handler(task)
                def _h(self, task):
                    mystery_helper(task)
        """, {})
        assert any(
            f.rule == "QUEUE-EFFECT-UNKNOWN"
            and "mystery_helper" in f.message for f in fs
        ), fs

    def test_unknown_fires_on_unvocabularied_effect_receiver(self):
        fs = _queue_diff("""
            class P:
                def _process(self, task):
                    handler = {
                        TransferTaskType.DecisionTask: self._h,
                    }.get(task.task_type)
                    handler(task)
                def _h(self, task):
                    self.engine.transmogrify(task)
        """, {})
        assert any(
            f.rule == "QUEUE-EFFECT-UNKNOWN"
            and "transmogrify" in f.message for f in fs
        ), fs

    def test_unknown_fires_on_dynamic_dispatch_in_handler(self):
        fs = _queue_diff("""
            class P:
                def _process(self, task):
                    handler = {
                        TransferTaskType.DecisionTask: self._h,
                    }.get(task.task_type)
                    handler(task)
                def _h(self, task):
                    self._table[task.kind](task)
        """, {})
        assert any(f.rule == "QUEUE-EFFECT-UNKNOWN" for f in fs), fs

    def test_local_callables_stay_neutral(self):
        """Nested defs, parameters and lambda bindings are visited
        where they are defined/bound — calling them is never an
        untracked helper (the false-positive direction)."""
        _, fps = _queue_extract("""
            class P:
                def _h(self, task):
                    def read(ms):
                        return ms
                    picker = lambda t: t
                    self._apply(task, read)
                    picker(task)
                def _apply(self, task, reader):
                    reader(task)
        """)
        assert not fps["_h"].unknown, fps["_h"].unknown

    def test_bundle_alias_classifies_manager_calls(self):
        """`p = self.shard.persistence` then `p.execution.update(...)`
        must classify by the manager segment, not fall through to
        neutral (the silent-footprint-gap direction)."""
        _, fps = _queue_extract("""
            class P:
                def _h(self, task):
                    p = self.shard.persistence
                    p.execution.update_workflow_execution(task)
                    p.visibility.get_closed(task)
        """)
        fp = fps["_h"]
        assert {"execution", "queue_tasks"} <= fp.writes
        assert "visibility" in fp.reads
        assert not fp.unknown

    def test_call_in_chain_to_persistence_classifies(self):
        """A bundle reached through a helper call still classifies when
        the chain names persistence (`self._persistence().history`)."""
        _, fps = _queue_extract("""
            class P:
                def get_persistence(self):
                    return self.shard.persistence
                def _h(self, task):
                    self.get_persistence().history.append_history_nodes(
                        task
                    )
        """)
        fp = fps["_h"]
        assert "history" in fp.writes
        assert not fp.unknown

    def test_undeclared_write_fires(self):
        from cadence_tpu.runtime.queues.effects import Footprint

        declared = {("transfer", "DecisionTask"): Footprint(
            frozenset({"execution"}), frozenset({"task_store"}),
        )}
        fs = _queue_diff("""
            class P:
                def _process(self, task):
                    handler = {
                        TransferTaskType.DecisionTask: self._h,
                    }.get(task.task_type)
                    handler(task)
                def _h(self, task):
                    self.matching.add_decision_task(task.domain_id)
                    self.visibility.upsert_workflow_execution(task)
        """, declared)
        assert any(
            f.rule == "QUEUE-CONFLICT-UNDECLARED"
            and "visibility" in f.message for f in fs
        ), fs

    def test_missing_declaration_fires(self):
        fs = _queue_diff(_CLEAN_PROCESSOR, {})
        assert any(
            f.rule == "QUEUE-CONFLICT-UNDECLARED"
            and f.anchor.endswith(":undeclared") for f in fs
        ), fs

    def test_cross_wf_fires_when_undeclared(self):
        from cadence_tpu.runtime.queues.effects import Footprint

        src = """
            class P:
                def _process(self, task):
                    handler = {
                        TransferTaskType.CloseExecution: self._h,
                    }.get(task.task_type)
                    handler(task)
                def _h(self, task):
                    self.history_client.terminate_workflow_execution(
                        task.domain_id
                    )
        """
        mint = frozenset(
            {"execution", "history", "queue_tasks", "shard_seq"}
        )
        undeclared = {("transfer", "CloseExecution"): Footprint(
            frozenset(), mint,
        )}
        fs = _queue_diff(src, undeclared)
        assert any(
            f.rule == "QUEUE-CROSS-WF" and "xwf.terminate" in f.message
            for f in fs
        ), fs

        declared = {("transfer", "CloseExecution"): Footprint(
            frozenset(), mint, frozenset({"xwf.terminate"}),
        )}
        assert _queue_diff(src, declared) == []

    def test_declared_footprints_validate(self):
        from cadence_tpu.runtime.queues import effects as rt

        for fp in rt.TASK_FOOTPRINTS.values():
            fp.validate()  # unknown surface/xwf names raise
        with pytest.raises(ValueError, match="unknown surface"):
            rt.Footprint(frozenset({"warp_core"})).validate()

    def test_pass_registered_in_run_all(self):
        from cadence_tpu.analysis import PASSES

        assert "queue" in PASSES

    def test_real_tree_scan_is_clean(self):
        from cadence_tpu.analysis import queue_effects

        assert queue_effects.run(REPO_ROOT) == []

    def test_real_tree_extracts_cross_wf_effects(self):
        """The extractor sees through the real CloseExecution handler:
        parent notify + parent-close-policy fan-out (the pair the
        conflict matrix must mark conflicting)."""
        from cadence_tpu.analysis import queue_effects

        fps = queue_effects.handler_footprints(REPO_ROOT)
        _, _, close = fps[("transfer", "CloseExecution")]
        assert {"xwf.record_child_close", "xwf.terminate",
                "xwf.request_cancel"} <= close.cross_workflow
        _, _, user_timer = fps[("timer", "UserTimer")]
        assert not user_timer.cross_workflow
        assert "execution" in user_timer.writes
        # ms-column granularity (oracle_ast machinery reuse)
        assert "timers" in user_timer.ms_reads


# --------------------------------------------------------------------------
# the conflict matrix + artifact envelope
# --------------------------------------------------------------------------


class TestConflictMatrix:
    """Contract tests pinning known-commuting and known-conflicting
    task-type pairs — the verdicts the parallel-queue executor will
    schedule by."""

    @pytest.fixture(scope="class")
    def matrix(self):
        from cadence_tpu.runtime.queues.effects import (
            build_conflict_matrix,
        )

        doc = build_conflict_matrix()
        return {
            (p["a"], p["b"]): p for p in doc["pairs"]
        }, doc

    def _pair(self, pairs, a, b):
        return pairs.get((a, b)) or pairs[(b, a)]

    def test_timer_fire_vs_transfer_activity_commute_distinct(
        self, matrix
    ):
        pairs, _ = matrix
        v = self._pair(pairs, "timer:UserTimer", "transfer:ActivityTask")
        assert v["distinct_workflows"] == "commute"
        # same workflow: the timer mutates the execution row the
        # activity push reads — ordered, not parallel
        assert v["same_workflow"] == "conflict"

    def test_close_vs_parent_close_policy_conflict(self, matrix):
        pairs, _ = matrix
        v = self._pair(pairs, "transfer:CloseExecution",
                       "transfer:CloseExecution")
        assert v["same_workflow"] == "conflict"
        assert v["distinct_workflows"] == "conflict"
        assert any("cross-workflow" in r for r in v["reasons"])

    def test_same_workflow_disjoint_surfaces_commute(self, matrix):
        pairs, _ = matrix
        v = self._pair(pairs, "transfer:DecisionTask",
                       "transfer:RecordWorkflowStarted")
        assert v["same_workflow"] == "commute"
        assert v["distinct_workflows"] == "commute"

    def test_counter_and_shared_read_surfaces_commute(self):
        from cadence_tpu.runtime.queues.effects import (
            Footprint,
            pair_verdict,
        )

        a = Footprint(frozenset({"metadata"}), frozenset({"shard_seq"}))
        b = Footprint(frozenset({"metadata"}), frozenset({"shard_seq"}))
        v = pair_verdict(a, b)
        assert v["same_workflow"] == "commute"

    def test_matrix_proves_both_verdicts_exist(self, matrix):
        _, doc = matrix
        verdicts = {
            (p["same_workflow"], p["distinct_workflows"])
            for p in doc["pairs"]
        }
        assert ("commute", "commute") in verdicts
        assert ("conflict", "conflict") in verdicts

    def test_every_footprint_keyed_pair_present(self, matrix):
        from cadence_tpu.runtime.queues.effects import TASK_FOOTPRINTS

        _, doc = matrix
        n = len(TASK_FOOTPRINTS)
        assert len(doc["pairs"]) == n * (n + 1) // 2


class TestArtifactEnvelope:
    def test_round_trip_and_validation(self, tmp_path):
        from cadence_tpu.analysis import artifact

        path = str(tmp_path / "a.json")
        artifact.write_artifact(path, "test_kind", {"x": 1})
        doc = artifact.load_artifact(path, kind="test_kind")
        assert doc["x"] == 1
        with pytest.raises(ValueError, match="kind"):
            artifact.load_artifact(path, kind="other_kind")

    def test_version_mismatch_fails_loudly(self, tmp_path):
        from cadence_tpu.analysis import artifact

        path = str(tmp_path / "a.json")
        with open(path, "w") as f:
            json.dump({"schema_version": 999, "artifact": "k"}, f)
        with pytest.raises(ValueError, match="schema_version"):
            artifact.load_artifact(path)

    def test_payload_cannot_spoof_envelope(self, tmp_path):
        from cadence_tpu.analysis import artifact

        path = str(tmp_path / "a.json")
        artifact.write_artifact(
            path, "real", {"schema_version": 999, "artifact": "fake"}
        )
        doc = artifact.load_artifact(path, kind="real")
        assert doc["schema_version"] == artifact.SCHEMA_VERSION

    def test_emit_conflict_matrix_artifact(self, tmp_path):
        from cadence_tpu.analysis import artifact, queue_effects
        from cadence_tpu.runtime.queues.effects import (
            CONFLICT_MATRIX_SCHEMA,
        )

        path = str(tmp_path / "conflicts.json")
        queue_effects.emit_conflict_matrix(REPO_ROOT, path)
        doc = artifact.load_artifact(path, kind=CONFLICT_MATRIX_SCHEMA)
        # the acceptance bar: at least one pair proven commuting and
        # one proven conflicting, so the artifact is non-vacuous
        assert any(
            p["same_workflow"] == "commute"
            and p["distinct_workflows"] == "commute"
            for p in doc["pairs"]
        )
        assert any(p["same_workflow"] == "conflict" for p in doc["pairs"])
        assert doc["footprints"]["transfer:CloseExecution"][
            "cross_workflow"
        ]
        # ms-column granularity rides along
        assert "timers" in doc["ms_columns"]["timer:UserTimer"]["ms_reads"]


class TestStrictStale:
    def test_strict_stale_fails_the_gate(self, tmp_path):
        from cadence_tpu.analysis.__main__ import main

        bl = str(tmp_path / "bl.json")
        Baseline([
            BaselineEntry("QUEUE-GONE", "matches:nothing:*", "long fixed")
        ]).save(bl)
        # stale entry: warning (rc 0) by default, error under strict
        assert main([
            "--passes", "queue", "--baseline", bl, "-q",
        ]) == 0
        assert main([
            "--passes", "queue", "--baseline", bl, "--strict-stale", "-q",
        ]) == 1

    def test_pass_subset_scopes_the_baseline(self):
        """`--passes queue --strict-stale` against the REAL baseline
        must exit 0: entries belonging to the skipped passes
        (SURFACE-*/LOCK-*) are out of scope, not stale."""
        from cadence_tpu.analysis.__main__ import main

        rc = main([
            "--passes", "queue",
            "--baseline",
            os.path.join(REPO_ROOT, "config", "lint_baseline.json"),
            "--strict-stale", "-q", "--root", REPO_ROOT,
        ])
        assert rc == 0

    def test_scope_baseline_filters_by_rule_prefix(self):
        from cadence_tpu.analysis import scope_baseline

        bl = Baseline([
            BaselineEntry("LOCK-BLOCKING", "a:*", "x"),
            BaselineEntry("QUEUE-CROSS-WF", "b:*", "y"),
        ])
        scoped = scope_baseline(bl, ["queue"])
        assert [e.rule for e in scoped.entries] == ["QUEUE-CROSS-WF"]
        assert scope_baseline(bl, None) is bl


class TestCleanTreeGate:
    def test_zero_new_findings(self):
        baseline = Baseline.load(
            os.path.join(REPO_ROOT, "config", "lint_baseline.json")
        )
        t0 = time.process_time()
        by_pass = run_all(REPO_ROOT)
        elapsed = time.process_time() - t0
        # the CI budget: all five passes trace + scan well under a
        # minute; ~2 s CPU standalone. The bound is 20 s because the
        # guarded failure mode is a RUNAWAY pass (accidental
        # quadratic closure, tracing the kernel per event type), not
        # percent drift: late in a full suite run the surface/jit
        # jaxpr tracing pays 3-4 s extra CPU against the
        # suite-polluted JAX caches — the old 5 s bound flaked at
        # 5.1 s on an unmodified tree, and 10 s flaked at 11.1 s once
        # the tree grew the autopilot subsystem (~2.7k more lines for
        # the passes to scan). A runaway pass blows through 20 s by an
        # order of magnitude, so the guard keeps its teeth
        assert elapsed < 20.0, (
            f"analysis gate took {elapsed:.1f}s CPU (budget 20s)"
        )
        all_findings = dedupe(
            [f for fs in by_pass.values() for f in fs]
        )
        new, accepted, stale = baseline.split(all_findings)
        assert not new, (
            "non-baselined static-analysis findings (fix them or add a "
            "justified baseline entry in config/lint_baseline.json):\n"
            + "\n".join(f.format() for f in new)
        )
        # stale entries warn, matching the CLI contract ("a fixed
        # finding shouldn't break the build") — clean them up when seen
        for e in stale:
            import warnings

            warnings.warn(
                f"stale lint baseline entry [{e.rule}] {e.anchor} — the "
                "finding it accepts no longer exists; remove it from "
                "config/lint_baseline.json"
            )


# --------------------------------------------------------------------------
# pass 3, PR 12 additions — tracked factory, call-closure edges, the
# lock graph the runtime witness cross-validates against
# --------------------------------------------------------------------------


def _lock_graph(src: str):
    classes = lock_order.analyze_module(src, "fix.py")
    return lock_order.collect_graph(classes)


class TestLockGraphStatic:
    def test_tracked_factory_recognized_as_lock(self):
        """utils/locks.make_lock construction sites stay in the
        inventory — moving the tree to the tracked factory must not
        blind the static pass."""
        src = textwrap.dedent("""
            import time
            from cadence_tpu.utils.locks import make_lock

            class C:
                def __init__(self):
                    self._lock = make_lock("C._lock")
                def bad(self):
                    with self._lock:
                        time.sleep(1)
        """)
        fs = _lock_findings(src)
        assert any(
            f.rule == "LOCK-BLOCKING" and "sleep" in f.message for f in fs
        )

    def test_same_class_call_closure_produces_edge(self):
        """A lock acquired two self-call hops below the held region
        joins the edge graph (the hole the runtime witness exposed:
        assign_task_ids → next_task_id → _lock)."""
        src = textwrap.dedent("""
            import threading

            class Holder:
                def __init__(self):
                    self._lock = threading.Lock()
                def outer(self, shard):
                    with self._lock:
                        shard.assign_ids()

            class Shard:
                def __init__(self):
                    self._lock = threading.Lock()
                def assign_ids(self):
                    self.next_id()
                def next_id(self):
                    with self._lock:
                        return 1
        """)
        _, edges = _lock_graph(src)
        assert ("fix.py:Holder._lock", "fix.py:Shard._lock") in edges

    def test_constructor_under_lock_produces_edge(self):
        """ClassName(...) under a held lock closes into the class's
        __init__ (a store-leasing constructor acquires locks)."""
        src = textwrap.dedent("""
            import threading

            class Engine:
                def __init__(self):
                    self._lock = threading.Lock()
                def get(self):
                    with self._lock:
                        return Managed()

            class Managed:
                def __init__(self):
                    self._lock = threading.Lock()
                    with self._lock:
                        pass
        """)
        _, edges = _lock_graph(src)
        assert ("fix.py:Engine._lock", "fix.py:Managed._lock") in edges

    def test_blocking_classified_call_still_propagates_edge(self):
        """A store call under a lock is BOTH a LOCK-BLOCKING finding
        and an edge into the store's lock — the two reports are not
        mutually exclusive (the runtime witness observes the edge, so
        the static graph must carry it)."""
        src = textwrap.dedent("""
            import threading

            class Ctx:
                def __init__(self):
                    self._lock = threading.Lock()
                def persist(self, store):
                    with self._lock:
                        store.update_shard(1)

            class Store:
                def __init__(self):
                    self._lock = threading.Lock()
                def update_shard(self, info):
                    with self._lock:
                        return 1
        """)
        findings, edges = _lock_graph(src)
        assert any(f.rule == "LOCK-BLOCKING" for f in findings)
        assert ("fix.py:Ctx._lock", "fix.py:Store._lock") in edges

    def test_ambiguous_non_store_name_not_resolved(self):
        """A name defined by several non-store classes resolves to
        none of them — 'merge' on a histogram must not drag in an
        unrelated coordinator's locks (the false-inversion noise the
        may-union guard exists for)."""
        src = textwrap.dedent("""
            import threading

            class Caller:
                def __init__(self):
                    self._lock = threading.Lock()
                def go(self, thing):
                    with self._lock:
                        thing.merge(1)

            class A:
                def merge(self, x):
                    return x

            class B:
                def __init__(self):
                    self._lock = threading.Lock()
                def merge(self, x):
                    with self._lock:
                        return x
        """)
        _, edges = _lock_graph(src)
        assert ("fix.py:Caller._lock", "fix.py:B._lock") not in edges

    def test_scope_covers_serving_edge(self):
        """Satellite: frontend/, client/ and rpc/ are scanned — the
        host resharder lock (moved from the admin handler to
        HistoryService so the autopilot shares the coordinator) and
        the routed client's stub cache are in the inventory."""
        for scope in ("cadence_tpu/frontend", "cadence_tpu/client",
                      "cadence_tpu/rpc"):
            assert scope in lock_order.SCOPE_DIRS
        graph = lock_order.build_graph(REPO_ROOT)
        assert (
            "cadence_tpu/runtime/service.py:"
            "HistoryService._resharder_lock" in graph.locks
        )
        assert (
            "cadence_tpu/client/routed.py:_StubCache._lock"
            in graph.locks
        )

    def test_real_tree_graph_nonempty_and_inversion_free(self):
        """The static graph the runtime witness validates against:
        dozens of edges on the real tree, and the tree itself is
        inversion-free outside the baseline (the gate test covers the
        baseline matching; this pins the graph's shape)."""
        graph = lock_order.build_graph(REPO_ROOT)
        assert len(graph.edges) >= 20
        assert len(graph.locks) >= 30
        # the closure found the entity-lock → shard-lease edge the
        # runtime observes on every workflow write
        assert lock_order.edge_in_static(
            (
                "cadence_tpu/runtime/engine/context.py:"
                "WorkflowExecutionContext.lock",
                "cadence_tpu/runtime/shard.py:ShardContext._lock",
            ),
            list(graph.edges),
        )

"""The batched replay kernel — the north star.

Replays thousands of workflow histories as one vectorized finite-state-
machine simulation: ``lax.scan`` over the (padded) time axis, every step
applying one event row per workflow to the dense state tensors with masked
updates. Branchless by construction: the event-type × transition function
is expressed as per-type masks blended with ``jnp.where`` (all transitions
are computed for all lanes and selected — the VPU-friendly formulation),
and pending-map scatter writes use one-hot slot masks precomputed by the
packer.

Semantics are the oracle's (cadence_tpu/core/state_builder.py ==
/root/reference/service/history/stateBuilder.go:112-613 +
mutableStateBuilder Replicate* methods); differential tests assert parity.
Two deliberate deviations, both matching the reference's *rebuild* path
(nDCStateRebuilder.go:92-160):

  * timer-task dedup bits (AC_TIMER_STATUS / TI_STATUS) are not tracked
    in-scan; the reference's taskRefresher resets and regenerates them
    after a rebuild, which ops/refresh.py does vectorized.
  * per-event transfer/timer tasks are not emitted from the scan (O(B*T)
    memory); they're regenerated from final state by ops/refresh.py.

TPU notes: all state is int32 (VPU-native); the scan is memory-bound on
HBM (state read+write per step), so capacities directly set the bytes/step
— keep slot tables as small as the workload allows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from cadence_tpu.core.enums import (
    CloseStatus, EventType as E, WorkflowState,
    WORKFLOW_CLOSE_STATUS, decision_attempt_increment,
)
from cadence_tpu.core.ids import EMPTY_EVENT_ID, EMPTY_VERSION

from cadence_tpu.utils.tracing import TRACER

from . import schema as S
from .pack import PackedHistories, PackedLanes, round_scan_len


# Transition-table groups: each tuple is the event-type set gating one
# update block of replay_step_cols. ``type_signature`` canonicalizes a
# batch's present-type set to the union of touched groups, so the
# jit specialization key is "which blocks run", not the raw type list —
# a bounded, storm-stable set of executables.
_TYPE_GROUPS = None  # populated lazily (E enum below)


def _type_groups():
    global _TYPE_GROUPS
    if _TYPE_GROUPS is None:
        _TYPE_GROUPS = (
            (E.WorkflowExecutionStarted,),
            (E.WorkflowExecutionCompleted, E.WorkflowExecutionFailed,
             E.WorkflowExecutionTimedOut, E.WorkflowExecutionCanceled,
             E.WorkflowExecutionTerminated,
             E.WorkflowExecutionContinuedAsNew),
            (E.WorkflowExecutionCancelRequested,),
            (E.WorkflowExecutionSignaled,),
            (E.DecisionTaskScheduled,),
            (E.DecisionTaskStarted,),
            (E.DecisionTaskCompleted,),
            (E.DecisionTaskTimedOut, E.DecisionTaskFailed),
            (E.ActivityTaskScheduled,),
            (E.ActivityTaskStarted,),
            (E.ActivityTaskCompleted, E.ActivityTaskFailed,
             E.ActivityTaskTimedOut, E.ActivityTaskCanceled),
            (E.ActivityTaskCancelRequested,),
            (E.TimerStarted,),
            (E.TimerFired, E.TimerCanceled),
            (E.StartChildWorkflowExecutionInitiated,),
            (E.ChildWorkflowExecutionStarted,),
            (E.StartChildWorkflowExecutionFailed,
             E.ChildWorkflowExecutionCompleted,
             E.ChildWorkflowExecutionFailed,
             E.ChildWorkflowExecutionCanceled,
             E.ChildWorkflowExecutionTimedOut,
             E.ChildWorkflowExecutionTerminated),
            (E.RequestCancelExternalWorkflowExecutionInitiated,),
            (E.RequestCancelExternalWorkflowExecutionFailed,
             E.ExternalWorkflowExecutionCancelRequested),
            (E.SignalExternalWorkflowExecutionInitiated,),
            (E.SignalExternalWorkflowExecutionFailed,
             E.ExternalWorkflowExecutionSignaled),
        )
    return _TYPE_GROUPS


def type_signature(present) -> tuple:
    """Canonical static type set for ``replay_step_cols(types=...)``.

    Expands the batch's present event types to whole transition groups
    (a group either runs or is statically skipped), returned as a sorted
    tuple usable as a jit static argument. Skipped groups cost nothing
    at trace or run time; retained groups still test exact types at
    runtime, so the result is bit-identical to the unspecialized step.
    """
    ps = {int(t) for t in present}
    out = set()
    for g in _type_groups():
        if any(int(t) in ps for t in g):
            out.update(int(t) for t in g)
    return tuple(sorted(out))


# --------------------------------------------------------------------------
# Column-major carry layout.
#
# The scan carries state as flat per-column vectors ([B] exec columns,
# [B, N] slot-table columns) instead of the packed [B, X_N] / [B, N, C]
# tensors: a masked update then touches one small vector, where the
# packed layout's ``.at[:, col].set`` forces XLA:CPU to rewrite the whole
# tensor per update (~6x measured on the exec table at B=512 — the step
# body is the throughput bound for shallow workloads). Conversion happens
# once per scan at the boundaries; element values and update order are
# identical, so results are bit-identical to the packed formulation.
# --------------------------------------------------------------------------


def state_to_cols(state: S.StateTensors):
    """StateTensors → flat column pytree (the scan-carry layout)."""
    ex = state.exec_info
    return (
        tuple(ex[:, c] for c in range(ex.shape[1])),
        state.vh_items[:, :, 0],
        state.vh_items[:, :, 1],
        state.vh_len,
        tuple(state.activities[:, :, c] for c in range(S.AC_N)),
        tuple(state.timers[:, :, c] for c in range(S.TI_N)),
        tuple(state.children[:, :, c] for c in range(S.CH_N)),
        tuple(state.cancels[:, :, c] for c in range(S.RC_N)),
        tuple(state.signals[:, :, c] for c in range(S.SG_N)),
    )


def cols_to_state(cols) -> S.StateTensors:
    exc, vh_e, vh_v, vh_len, ac, ti, ch, rc, sg = cols
    return S.StateTensors(
        exec_info=jnp.stack(exc, axis=1),
        activities=jnp.stack(ac, axis=-1),
        timers=jnp.stack(ti, axis=-1),
        children=jnp.stack(ch, axis=-1),
        cancels=jnp.stack(rc, axis=-1),
        signals=jnp.stack(sg, axis=-1),
        vh_items=jnp.stack([vh_e, vh_v], axis=-1),
        vh_len=vh_len,
    )


def _tbl_set(tbl, onehot, col, val):
    """tbl[col][B, N] ← val[B] (broadcast over slots) where onehot."""
    if onehot is not None:
        tbl[col] = jnp.where(onehot, val[:, None], tbl[col])


def _tbl_blend(tbl, onehot, row_vals):
    """Whole-row write: tbl[c] ← row_vals[c] where onehot[B, N].
    row_vals entries are [B] vectors or scalars."""
    if onehot is None:
        return
    for c, v in enumerate(row_vals):
        vv = v[:, None] if getattr(v, "ndim", 0) == 1 else v
        tbl[c] = jnp.where(onehot, vv, tbl[c])


def _tbl_clear(tbl, onehot):
    if onehot is not None:
        for c in range(len(tbl)):
            tbl[c] = jnp.where(onehot, 0, tbl[c])


def replay_step_cols(cols, ev: jnp.ndarray, types: Optional[tuple] = None):
    """Apply one event row per workflow to the column-layout carry.

    ev: [B, EV_N] int32. ``types``: static sorted tuple of event types
    present in the batch (``type_signature``); transition blocks whose
    types are statically absent are skipped entirely — a shallow storm
    touches a fraction of the transition table. ``None`` keeps every
    block."""
    et = ev[:, S.EV_TYPE]
    valid = et >= 0
    type_set = None if types is None else frozenset(types)

    def m(*query):
        if type_set is not None:
            query = [t for t in query if int(t) in type_set]
            if not query:
                return None
        out = jnp.zeros_like(valid)
        for t in query:
            out = out | (et == int(t))
        return valid & out

    def slot_mask(mask, capacity):
        """[B, capacity] one-hot of EV_SLOT under ``mask``."""
        if mask is None:
            return None
        slot = ev[:, S.EV_SLOT]
        return mask[:, None] & (
            slot[:, None] == jnp.arange(capacity)[None, :]
        )

    ev_id = ev[:, S.EV_ID]
    version = ev[:, S.EV_VERSION]
    task_id = ev[:, S.EV_TASK_ID]
    ts = ev[:, S.EV_TS]
    batch_first = ev[:, S.EV_BATCH_FIRST]
    a0, a1, a2, a3 = (ev[:, S.EV_A0], ev[:, S.EV_A1], ev[:, S.EV_A2], ev[:, S.EV_A3])
    a4, a5, a6, a7 = (ev[:, S.EV_A4], ev[:, S.EV_A5], ev[:, S.EV_A6], ev[:, S.EV_A7])

    exc, vh_e, vh_v, vh_len, ac, ti, ch, rc, sg = cols
    exc = list(exc)
    ac, ti, ch = list(ac), list(ti), list(ch)
    rc, sg = list(rc), list(sg)

    def xset(col, mask, val):
        """exec column masked update (no-op on statically absent mask)."""
        if mask is not None:
            exc[col] = jnp.where(mask, val, exc[col])

    # ---- common preamble (stateBuilder.go:134-155 + batch-end bookkeeping)
    xset(S.X_LAST_EVENT_TASK_ID, valid, task_id)
    xset(S.X_CUR_VERSION, valid, version)
    xset(S.X_NEXT_EVENT_ID, valid, ev_id + 1)
    xset(S.X_LAST_FIRST_EVENT_ID, valid, batch_first)

    # ---- version-history add_or_update (versionHistory.go AddOrUpdateItem)
    cap_v = vh_v.shape[1]
    last_idx = jnp.maximum(vh_len - 1, 0)
    # read the last *materialized* slot: past capacity, last_idx exceeds
    # the table and an unclamped gather's out-of-bounds semantics are
    # backend-defined; the clamped read keeps overflowed states (chained
    # bench iterations, not real histories) deterministic and identical
    # across the scan and Pallas kernels. write_idx keeps the raw
    # last_idx so same-version writes past capacity still match no slot.
    last_ver = jnp.take_along_axis(
        vh_v, jnp.minimum(last_idx, cap_v - 1)[:, None], axis=1)[:, 0]
    same = (vh_len > 0) & (last_ver == version)
    write_idx = jnp.where(same, last_idx, jnp.minimum(vh_len, cap_v - 1))
    wmask = valid[:, None] & (write_idx[:, None] == jnp.arange(cap_v)[None, :])
    vh_e = jnp.where(wmask, ev_id[:, None], vh_e)
    vh_v = jnp.where(wmask, version[:, None], vh_v)
    vh_len = jnp.where(valid & ~same, vh_len + 1, vh_len)

    # ---- workflow lifecycle ------------------------------------------------
    m_start = m(E.WorkflowExecutionStarted)
    xset(S.X_STATE, m_start, int(WorkflowState.Created))
    xset(S.X_CLOSE_STATUS, m_start, int(CloseStatus.NONE))
    xset(S.X_LAST_PROCESSED_EVENT, m_start, EMPTY_EVENT_ID)
    xset(S.X_START_TS, m_start, ts)
    xset(S.X_WORKFLOW_TIMEOUT, m_start, a0)
    xset(S.X_DECISION_TIMEOUT_VALUE, m_start, a1)
    xset(S.X_ATTEMPT, m_start, a2)
    xset(S.X_HAS_RETRY_POLICY, m_start, a3)
    xset(S.X_WF_EXPIRATION_TS, m_start, a4)
    xset(S.X_PARENT_INITIATED_ID, m_start, a7)
    for col in (S.X_DEC_SCHEDULE_ID, S.X_DEC_STARTED_ID):
        xset(col, m_start, EMPTY_EVENT_ID)
    xset(S.X_DEC_VERSION, m_start, EMPTY_VERSION)
    for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT, S.X_DEC_SCHEDULED_TS,
                S.X_DEC_STARTED_TS, S.X_DEC_ORIGINAL_SCHEDULED_TS):
        xset(col, m_start, 0)

    close_terms = []
    for t, cs in WORKFLOW_CLOSE_STATUS:
        mk = m(t)
        if mk is not None:
            close_terms.append((mk, int(cs)))
    if close_terms:
        close_status = sum(mk * cs for mk, cs in close_terms)
        m_close = close_status > 0
        xset(S.X_STATE, m_close, int(WorkflowState.Completed))
        xset(S.X_CLOSE_STATUS, m_close, close_status)
        xset(S.X_COMPLETION_EVENT_BATCH_ID, m_close, batch_first)

    xset(S.X_CANCEL_REQUESTED, m(E.WorkflowExecutionCancelRequested), 1)
    m_sig = m(E.WorkflowExecutionSignaled)
    if m_sig is not None:
        xset(S.X_SIGNAL_COUNT, m_sig, exc[S.X_SIGNAL_COUNT] + 1)

    # ---- decision sub-FSM (mutableStateDecisionTaskManager.go) -------------
    m_dsch = m(E.DecisionTaskScheduled)
    xset(S.X_DEC_VERSION, m_dsch, version)
    xset(S.X_DEC_SCHEDULE_ID, m_dsch, ev_id)
    xset(S.X_DEC_STARTED_ID, m_dsch, EMPTY_EVENT_ID)
    xset(S.X_DEC_TIMEOUT, m_dsch, a0)
    xset(S.X_DEC_ATTEMPT, m_dsch, a1)
    xset(S.X_DEC_SCHEDULED_TS, m_dsch, ts)
    xset(S.X_DEC_ORIGINAL_SCHEDULED_TS, m_dsch, ts)
    xset(S.X_DEC_STARTED_TS, m_dsch, 0)

    m_dsta = m(E.DecisionTaskStarted)
    if m_dsta is not None:
        # Created → Running on first decision start (:228-235)
        xset(
            S.X_STATE,
            m_dsta & (exc[S.X_STATE] == int(WorkflowState.Created)),
            int(WorkflowState.Running),
        )
        xset(S.X_DEC_VERSION, m_dsta, version)
        xset(S.X_DEC_STARTED_ID, m_dsta, ev_id)
        xset(S.X_DEC_ATTEMPT, m_dsta, 0)  # replication magic (:216-224)
        xset(S.X_DEC_STARTED_TS, m_dsta, ts)

    m_dcom = m(E.DecisionTaskCompleted)
    # delete decision, keep original-scheduled ts (:659-674)
    xset(S.X_DEC_VERSION, m_dcom, EMPTY_VERSION)
    xset(S.X_DEC_SCHEDULE_ID, m_dcom, EMPTY_EVENT_ID)
    xset(S.X_DEC_STARTED_ID, m_dcom, EMPTY_EVENT_ID)
    for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT, S.X_DEC_SCHEDULED_TS,
                S.X_DEC_STARTED_TS):
        xset(col, m_dcom, 0)
    xset(S.X_LAST_PROCESSED_EVENT, m_dcom, a0)

    # fail/timeout → fail_decision(+transient schedule) fused:
    m_dto = m(E.DecisionTaskTimedOut)
    m_dfail = m(E.DecisionTaskFailed)
    if m_dto is not None or m_dfail is not None:
        fill = jnp.zeros_like(valid)
        dto = fill if m_dto is None else m_dto
        dfail = fill if m_dfail is None else m_dfail
        increment = decision_attempt_increment(dfail, dto, a0)
        no_increment = (dto | dfail) & ~increment
        # transient decision fires iff attempt was incremented (oracle:
        # replicate_transient_decision_task_scheduled precondition
        # collapses to `increment` right after fail_decision)
        new_attempt = exc[S.X_DEC_ATTEMPT] + 1
        xset(S.X_DEC_VERSION, increment, exc[S.X_CUR_VERSION])
        xset(S.X_DEC_SCHEDULE_ID, increment, batch_first)
        xset(S.X_DEC_STARTED_ID, increment, EMPTY_EVENT_ID)
        xset(S.X_DEC_TIMEOUT, increment, exc[S.X_DECISION_TIMEOUT_VALUE])
        xset(S.X_DEC_ATTEMPT, increment, new_attempt)
        xset(S.X_DEC_SCHEDULED_TS, increment, ts)
        xset(S.X_DEC_STARTED_TS, increment, 0)
        xset(S.X_DEC_ORIGINAL_SCHEDULED_TS, increment, 0)

        xset(S.X_DEC_VERSION, no_increment, EMPTY_VERSION)
        xset(S.X_DEC_SCHEDULE_ID, no_increment, EMPTY_EVENT_ID)
        xset(S.X_DEC_STARTED_ID, no_increment, EMPTY_EVENT_ID)
        for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT, S.X_DEC_SCHEDULED_TS,
                    S.X_DEC_STARTED_TS, S.X_DEC_ORIGINAL_SCHEDULED_TS):
            xset(col, no_increment, 0)

    # ---- pending activities ------------------------------------------------
    cap_a = ac[0].shape[1]

    oh_sched = slot_mask(m(E.ActivityTaskScheduled), cap_a)
    if oh_sched is not None:
        # expiration: scheduled + max(schedule_to_close, retry expiration
        # if larger) — mutableStateBuilder.go:2012-2022
        exp_interval = jnp.where((a5 > 0) & (a6 > a2), a6, a2)
        _tbl_blend(ac, oh_sched, [
            1,                      # AC_OCC
            version,                # AC_VERSION
            ev_id,                  # AC_SCHEDULE_ID
            batch_first,            # AC_SCHEDULED_BATCH_ID
            ts,                     # AC_SCHEDULED_TS
            EMPTY_EVENT_ID,         # AC_STARTED_ID
            0,                      # AC_STARTED_TS
            a0,                     # AC_ID_HASH
            a1,                     # AC_SCH_TO_START
            a2,                     # AC_SCH_TO_CLOSE
            a3,                     # AC_START_TO_CLOSE
            a4,                     # AC_HEARTBEAT
            0,                      # AC_CANCEL_REQUESTED
            EMPTY_EVENT_ID,         # AC_CANCEL_REQUEST_ID
            0,                      # AC_ATTEMPT
            a5,                     # AC_HAS_RETRY
            ts + exp_interval,      # AC_EXPIRATION_TS
            0,                      # AC_LAST_HB_TS
            0,                      # AC_TIMER_STATUS
        ])

    oh_start = slot_mask(m(E.ActivityTaskStarted), cap_a)
    _tbl_set(ac, oh_start, S.AC_VERSION, version)
    _tbl_set(ac, oh_start, S.AC_STARTED_ID, ev_id)
    _tbl_set(ac, oh_start, S.AC_STARTED_TS, ts)
    _tbl_set(ac, oh_start, S.AC_LAST_HB_TS, ts)
    _tbl_set(ac, oh_start, S.AC_ATTEMPT, a1)

    _tbl_clear(ac, slot_mask(
        m(E.ActivityTaskCompleted, E.ActivityTaskFailed,
          E.ActivityTaskTimedOut, E.ActivityTaskCanceled),
        cap_a,
    ))

    oh_acreq = slot_mask(m(E.ActivityTaskCancelRequested), cap_a)
    _tbl_set(ac, oh_acreq, S.AC_VERSION, version)
    _tbl_set(ac, oh_acreq, S.AC_CANCEL_REQUESTED, jnp.ones_like(ev_id))
    _tbl_set(ac, oh_acreq, S.AC_CANCEL_REQUEST_ID, ev_id)

    # ---- pending timers ----------------------------------------------------
    cap_t = ti[0].shape[1]
    oh_tstart = slot_mask(m(E.TimerStarted), cap_t)
    _tbl_blend(ti, oh_tstart, [
        1,          # TI_OCC
        version,    # TI_VERSION
        ev_id,      # TI_STARTED_ID
        a0,         # TI_ID_HASH
        ts + a1,    # TI_EXPIRY_TS
        0,          # TI_STATUS
    ] if oh_tstart is not None else [])
    _tbl_clear(ti, slot_mask(m(E.TimerFired, E.TimerCanceled), cap_t))

    # ---- pending children --------------------------------------------------
    cap_c = ch[0].shape[1]
    oh_cinit = slot_mask(m(E.StartChildWorkflowExecutionInitiated), cap_c)
    _tbl_blend(ch, oh_cinit, [
        1,                  # CH_OCC
        version,            # CH_VERSION
        ev_id,              # CH_INITIATED_ID
        batch_first,        # CH_INITIATED_BATCH_ID
        EMPTY_EVENT_ID,     # CH_STARTED_ID
        a0,                 # CH_WF_ID_HASH
        0,                  # CH_RUN_ID_HASH
        a1,                 # CH_POLICY
    ] if oh_cinit is not None else [])

    oh_cstart = slot_mask(m(E.ChildWorkflowExecutionStarted), cap_c)
    _tbl_set(ch, oh_cstart, S.CH_STARTED_ID, ev_id)
    _tbl_set(ch, oh_cstart, S.CH_RUN_ID_HASH, a1)

    _tbl_clear(ch, slot_mask(
        m(E.StartChildWorkflowExecutionFailed,
          E.ChildWorkflowExecutionCompleted, E.ChildWorkflowExecutionFailed,
          E.ChildWorkflowExecutionCanceled, E.ChildWorkflowExecutionTimedOut,
          E.ChildWorkflowExecutionTerminated),
        cap_c,
    ))

    # ---- pending external cancels / signals --------------------------------
    cap_rc = rc[0].shape[1]
    oh_rcinit = slot_mask(
        m(E.RequestCancelExternalWorkflowExecutionInitiated), cap_rc
    )
    _tbl_blend(rc, oh_rcinit,
               [1, version, ev_id, batch_first]
               if oh_rcinit is not None else [])
    _tbl_clear(rc, slot_mask(
        m(E.RequestCancelExternalWorkflowExecutionFailed,
          E.ExternalWorkflowExecutionCancelRequested),
        cap_rc,
    ))

    cap_sg = sg[0].shape[1]
    oh_sginit = slot_mask(
        m(E.SignalExternalWorkflowExecutionInitiated), cap_sg
    )
    _tbl_blend(sg, oh_sginit,
               [1, version, ev_id, batch_first]
               if oh_sginit is not None else [])
    _tbl_clear(sg, slot_mask(
        m(E.SignalExternalWorkflowExecutionFailed,
          E.ExternalWorkflowExecutionSignaled),
        cap_sg,
    ))

    return (
        tuple(exc), vh_e, vh_v, vh_len,
        tuple(ac), tuple(ti), tuple(ch), tuple(rc), tuple(sg),
    )


def replay_scan(
    state: S.StateTensors, events_tm: jnp.ndarray,
    unroll: Optional[int] = None,
    types: Optional[tuple] = None,
) -> S.StateTensors:
    """Scan the full (time-major [T, B, EV_N]) event tensor.

    ``unroll``: steps fused per scan iteration — the scan is HBM-bound
    on the state carry, and unrolling lets XLA keep intermediates on
    chip across fused steps (~10-15% on v5e at unroll=8; measured in
    bench.py's configuration). Defaults to 8 on TPU and 1 elsewhere:
    unrolling only pays on the device, while on CPU (the test suite) it
    multiplies XLA compile time by the unroll factor.

    ``types``: static present-type tuple (``type_signature``) —
    statically skips transition blocks the batch cannot touch."""
    if unroll is None:
        unroll = _unroll()
    final, _ = lax.scan(
        lambda s, ev: (replay_step_cols(s, ev, types=types), None),
        state_to_cols(state), events_tm, unroll=unroll,
    )
    return cols_to_state(final)


def _unroll() -> int:
    from .replay_pallas import on_tpu

    return 8 if on_tpu() else 1


replay_scan_jit = jax.jit(
    replay_scan, donate_argnums=(0,), static_argnames=("unroll", "types"),
)


def _lane_mask(flag, leaf):
    return flag.reshape(flag.shape + (1,) * (leaf.ndim - 1))


def cols_to_mat(cols) -> jnp.ndarray:
    """Column carry → one [B, R] int32 matrix (R = total state columns).

    The packed scan's snapshot flush scatters this single buffer instead
    of ~60 column leaves: one dynamic-update-scatter per flush step, one
    extra carry array — the per-leaf formulation pays per-op dispatch on
    every leaf every flush, which dominates on CPU."""
    exc, vh_e, vh_v, vh_len, ac, ti, ch, rc, sg = cols
    parts = [jnp.stack(exc, axis=1), vh_e, vh_v, vh_len[:, None]]
    for tbl in (ac, ti, ch, rc, sg):
        parts.extend(tbl)
    return jnp.concatenate(parts, axis=1)


def mat_to_state(mat, caps: S.Capacities) -> S.StateTensors:
    """Inverse of ``cols_to_mat`` (rows → StateTensors)."""
    o = 0

    def take(n):
        nonlocal o
        sl = mat[:, o : o + n]
        o += n
        return sl

    ex = take(S.X_N)
    v = caps.max_version_items
    vh_e, vh_v = take(v), take(v)
    vh_len = take(1)[:, 0]

    def tbl(ncols, cap):
        return jnp.stack([take(cap) for _ in range(ncols)], axis=-1)

    return S.StateTensors(
        exec_info=ex,
        vh_items=jnp.stack([vh_e, vh_v], axis=-1),
        vh_len=vh_len,
        activities=tbl(S.AC_N, caps.max_activities),
        timers=tbl(S.TI_N, caps.max_timers),
        children=tbl(S.CH_N, caps.max_children),
        cancels=tbl(S.RC_N, caps.max_request_cancels),
        signals=tbl(S.SG_N, caps.max_signals_ext),
    )


def _caps_of(state: S.StateTensors) -> S.Capacities:
    return S.Capacities(
        max_events=0,
        max_activities=state.activities.shape[1],
        max_timers=state.timers.shape[1],
        max_children=state.children.shape[1],
        max_request_cancels=state.cancels.shape[1],
        max_signals_ext=state.signals.shape[1],
        max_version_items=state.vh_items.shape[1],
    )


def replay_scan_packed(
    state: S.StateTensors,
    out0: S.StateTensors,
    events_tm: jnp.ndarray,
    seg_end_tm: jnp.ndarray,
    out_row_tm: jnp.ndarray,
    unroll: Optional[int] = None,
    types: Optional[tuple] = None,
    init: Optional[S.StateTensors] = None,
    reset_row_tm: Optional[jnp.ndarray] = None,
):
    """Scan a lane-packed event tensor (ops/pack.py pack_lanes).

    ``state``: [L] lane carry — ``empty_state(L)``, or each lane's FIRST
    segment's initial row (``PackedLanes.lane_state0()``) when resuming
    from checkpoints. ``out0``: [n_out] output snapshot buffer, MUST be
    ``empty_state(n_out)`` — rows never written (padding) stay pristine
    and lane resets reuse its row 0 as the empty template.
    ``events_tm``/``seg_end_tm``/``out_row_tm``: [T, L(, EV_N)] from
    ``PackedLanes.time_major()``.

    At a segment-end step each flagged lane scatters its full state into
    its precomputed output row and resets to the NEXT segment's initial
    carry — ``empty_state`` normally, or its row of ``init`` when that
    segment resumes from a checkpoint (``reset_row_tm``: [T, L] indices
    into ``init``; the sentinel ``init.batch`` selects the appended
    pristine empty row). So each history's snapshot is bit-identical to
    replaying it alone from its initial state. Steps with no segment end
    skip the flush entirely (lax.cond).

    Returns (final_lane_state, out) — callers read ``out``.
    """
    if unroll is None:
        unroll = _unroll()
    caps = _caps_of(out0)
    n_out = out0.exec_info.shape[0]
    out_cols0 = state_to_cols(out0)
    empty_row = jax.tree_util.tree_map(lambda x: x[:1], out_cols0)
    if init is None:
        # single empty template row; every reset gathers row 0
        init_cols = empty_row
        reset_row_tm = jnp.zeros(seg_end_tm.shape, jnp.int32)
    else:
        if reset_row_tm is None:
            raise ValueError("init requires reset_row_tm")
        init_cols = jax.tree_util.tree_map(
            lambda a, e: jnp.concatenate([a, e], axis=0),
            state_to_cols(init), empty_row,
        )
    # one sentinel row past the end absorbs non-flush lanes' writes
    out_mat0 = jnp.concatenate(
        [cols_to_mat(out_cols0),
         jnp.zeros((1, cols_to_mat(out_cols0).shape[1]), jnp.int32)],
        axis=0,
    )
    # hoisted out of the scan: the per-step flush gate and scatter index
    # as vectorized [T]-shaped precomputes (a per-step jnp.any reduction
    # inside the loop measurably dominates the flush cost on CPU)
    idx_tm = jnp.where(seg_end_tm, out_row_tm, n_out).astype(jnp.int32)
    any_tm = jnp.any(seg_end_tm, axis=1)

    def body(carry, xs):
        st, out = carry
        ev, seg, idx, flush_now, rrow = xs
        st = replay_step_cols(st, ev, types=types)

        def flush(args):
            st, out = args
            # idx is host-derived, always within [0, n_out] (sentinel)
            out = out.at[idx].set(
                cols_to_mat(st), mode="promise_in_bounds"
            )
            st = jax.tree_util.tree_map(
                lambda s, ini: jnp.where(
                    _lane_mask(seg, s), ini[rrow], s
                ),
                st, init_cols,
            )
            return st, out

        st, out = lax.cond(flush_now, flush, lambda args: args, (st, out))
        return (st, out), None

    (st, out), _ = lax.scan(
        body, (state_to_cols(state), out_mat0),
        (events_tm, seg_end_tm, idx_tm, any_tm, reset_row_tm),
        unroll=unroll,
    )
    return cols_to_state(st), mat_to_state(out[:n_out], caps)


replay_scan_packed_jit = jax.jit(
    replay_scan_packed, donate_argnums=(0, 1),
    static_argnames=("unroll", "types"),
)


def replay_packed_lanes(
    packed: PackedLanes, specialize: bool = True,
    initial: Optional[S.StateTensors] = None,
) -> S.StateTensors:
    """Replay a lane-packed batch; returns numpy state with one row per
    history, in input order (``packed.side`` indexes it directly).

    ``initial``: [n_histories] per-history initial carries (checkpoint
    resume) — defaults to ``packed.initial`` (set by
    ``pack_lanes(resume=...)``); each history's segment then seeds from
    its row instead of ``empty_state``, bit-identically to replaying
    the full history from scratch.

    On TPU, lanes packed with ``seg_align`` a multiple of the Pallas
    time block ride the chunked VMEM-resident kernel
    (ops/replay_pallas.py replay_scan_pallas_packed); everywhere else —
    and for unaligned packings — the XLA scan handles arbitrary segment
    boundaries."""
    from .replay_pallas import on_tpu, replay_scan_pallas_packed

    caps = packed.caps
    if initial is None:
        initial = packed.initial
    n_pad = round_scan_len(packed.n_histories)
    out0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(n_pad, caps)
    )
    if initial is None:
        state0 = jax.tree_util.tree_map(
            jnp.asarray, S.empty_state(packed.lanes, caps)
        )
        init_j = None
        reset = None
    else:
        state0 = jax.tree_util.tree_map(
            jnp.asarray, packed.lane_state0(initial)
        )
        init_j = jax.tree_util.tree_map(jnp.asarray, initial)
        reset = packed.reset_rows()
    types = type_signature(packed.present_types) if specialize else None
    if on_tpu() and packed.seg_align % 8 == 0:
        _, out = replay_scan_pallas_packed(
            state0, out0, jnp.asarray(packed.teb()),
            jnp.asarray(packed.seg_end), jnp.asarray(packed.out_row),
            caps, tb=packed.seg_align,
            init=init_j,
            reset_row=None if reset is None else jnp.asarray(reset),
        )
    else:
        ev_tm, seg_tm, row_tm = packed.time_major()
        kwargs = {}
        if init_j is not None:
            kwargs = dict(
                init=init_j,
                reset_row_tm=jnp.asarray(
                    np.ascontiguousarray(reset.T)
                ),
            )
        _, out = replay_scan_packed_jit(
            state0, out0, jnp.asarray(ev_tm), jnp.asarray(seg_tm),
            jnp.asarray(row_tm), types=types, **kwargs,
        )
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x)[: packed.n_histories], out
    )


def replay_packed(
    packed,
    initial: Optional[S.StateTensors] = None,
) -> S.StateTensors:
    """Replay a packed batch on the default device; returns numpy state.

    Accepts :class:`PackedHistories` (one history per lane) or
    :class:`PackedLanes` (ragged lane packing; rows come back per
    history). On TPU the PackedHistories path rides the Pallas
    VMEM-resident kernel through a field-major layout made on the
    device + host presence masks; elsewhere it rides the sequential XLA
    scan, its batch dimension padded to the geometric shape grid
    (``round_scan_len``) so a storm of arbitrary batch sizes compiles a
    bounded set of executables. Both are bit-identical to the host
    oracle (tests/test_replay_differential.py).

    A trace entry point (``Tracer.entry``): the ``replay_packed`` span is
    a child of the caller's span, else a root at the tracer's sample
    rate."""
    span = TRACER.entry("replay_packed", service="replay")
    with span:
        if isinstance(packed, PackedLanes):
            if span:
                span.set_tag("histories", packed.n_histories)
                span.set_tag("events", packed.total_events)
            # initial: [n_histories] per-history resume carries
            # (checkpoint rows); defaults to packed.initial from
            # pack_lanes(resume=...)
            return replay_packed_lanes(packed, initial=initial)
        if span:
            span.set_tag("histories", packed.batch)
            span.set_tag("events", int(packed.lengths.sum()))
        return _replay_histories(packed, initial)


def _replay_histories(packed, initial) -> S.StateTensors:
    """replay_packed of a PackedHistories. Its spans: the transfers to
    the device (``replay.h2d``: the state, then the events), the layout
    of the kernel's operands (``replay.layout``: the events, then on TPU
    their device layout and the presence masks, built once the events'
    transfer is issued, so that the two can overlap; tagged ``bytes``
    laid out on the host and ``device_bytes`` laid out on the device),
    the kernel call (``replay.launch``) and the fetch of the final state
    (``replay.fetch``: the wait for the kernel, then the copy back).

    On TPU the events travel batch-major, as the packer holds them, and
    ``teb_of_rows`` lays them out field-major on the device: one pass
    over HBM costs less than a host scatter of the same bytes."""
    if initial is None:
        initial = packed.initial
    state = initial if initial is not None else S.empty_state(packed.batch, packed.caps)
    state = to_device(state)
    if packed.batch == 0:
        return jax.tree_util.tree_map(np.asarray, state)
    from .replay_pallas import BT, fit_tile, on_tpu, replay_scan_pallas_teb

    b = bp = packed.batch
    tpu = on_tpu()
    with TRACER.span("replay.layout") as sp:
        if tpu:
            # the packer's [B, T, EV_N] events as one [B, T·EV_N] matrix:
            # a view, whose minor dimension is whole lanes of 128 on the
            # device, where EV_N alone would pad 8x
            events = packed.events.reshape(b, -1)
        else:
            # the XLA batch dimension pads to the geometric shape grid
            bp = round_scan_len(b)
            events = packed.time_major()                   # [T, B, EV_N]
            if bp > b:
                pad = np.zeros((events.shape[0], bp - b, S.EV_N), np.int32)
                pad[:, :, S.EV_TYPE] = -1
                events = np.concatenate([events, pad], axis=1)
                state = jax.tree_util.tree_map(
                    lambda x, p: jnp.concatenate(
                        [x, jnp.asarray(p)], axis=0
                    ),
                    state,
                    S.empty_state(bp - b, packed.caps),
                )
        if sp:
            sp.set_tag("bytes", 0 if tpu else int(events.nbytes))
            sp.set_tag("device_bytes", 0)
    events = to_device(events)
    if tpu:
        # smallest whole tile covering the batch (small rebuild batches
        # shouldn't pad to the full throughput tile), narrowed where a
        # wide state needs it — the host masks are built for that tile
        bt, _ = fit_tile(packed.caps, min(BT, ((b + 1023) // 1024) * 1024),
                         ev_bytes=S.EV_N * events.dtype.itemsize)
        with TRACER.span("replay.layout") as sp:
            events = teb_of_rows(events)
            presence = packed.presence(bt)
            if sp:
                sp.set_tag("device_bytes", int(events.nbytes))
                sp.set_tag("bytes", 0 if presence is None
                           else int(presence.nbytes))
    with TRACER.span("replay.launch") as sp:
        if tpu:
            final = replay_scan_pallas_teb(
                state, events, packed.caps,
                interpret=False, bt=bt, presence=presence,
            )
        else:
            final = replay_scan_jit(state, events)
        if bp > b:
            final = jax.tree_util.tree_map(lambda x: x[:b], final)
        if sp:
            sp.set_tag("events", int(packed.lengths.sum()))
            if not tpu:  # the Pallas kernel tags its own
                sp.set_tag("cells", bp * packed.events.shape[1])
    with TRACER.span("replay.fetch") as sp:
        out = jax.tree_util.tree_map(np.asarray, final)
        if sp:
            sp.set_tag("bytes", sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(out)))
    return out


@jax.jit
def teb_of_rows(rows):
    """The Pallas teb kernel's operand [T, EV_N, B] (what
    ``PackedHistories.teb()`` builds on the host) from the packer's
    batch-major events seen as one [B, T·EV_N] matrix: its transpose,
    the major dimension split. One program a (B, T)."""
    b, n = rows.shape
    return rows.T.reshape(n // S.EV_N, S.EV_N, b)


@functools.partial(jax.jit, static_argnums=1)
def first_rows(tree, n: int):
    """The first ``n`` rows of every leaf of a pytree, as one program:
    sliced eagerly, each leaf would be an executable of its own to
    compile for every new shape and to dispatch on every call."""
    return jax.tree_util.tree_map(lambda x: x[:n], tree)


def to_device(host, span: str = "replay.h2d", parent=None):
    """A pytree of host arrays on the default device, under a span
    (child of ``parent`` or of the thread's current span) that counts
    its bytes."""
    with TRACER.span(span, parent=parent) as sp:
        if sp:
            sp.set_tag("bytes", sum(
                int(x.nbytes) for x in jax.tree_util.tree_leaves(host)))
        return jax.tree_util.tree_map(jnp.asarray, host)

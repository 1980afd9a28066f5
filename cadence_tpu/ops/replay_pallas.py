"""Pallas TPU replay kernel — VMEM-resident state scan.

Why this exists: the XLA ``lax.scan`` kernel (ops/replay.py) round-trips
the full state carry through HBM several times per step (measured
~160us/step at B=8192 on v5e — ~10x the single-carry HBM cost), because
the step body compiles to multiple fusions. This kernel keeps the
entire mutable state of a batch tile resident in VMEM for the whole
scan and streams only event blocks from HBM, eliminating the carry
traffic altogether.

Design:

- **Row layout**: all state tensors of a batch tile are packed into one
  int32 ``[R, BT]`` matrix — batch is the lane (minor) dimension, so
  every row update is a fully-utilized 128-lane VPU op. R enumerates
  exec-info columns, version-history slots, then the flattened slot
  tables (see RowMap).
- **Grid** ``(B/BT, T/TB)`` with time as the inner sequential dimension;
  the output state block's index map ignores t, so Pallas keeps it in
  VMEM across the whole time axis (accumulator pattern) and flushes it
  once per batch tile.
- **Predication**: every event-type group and every slot's update is
  gated on a scalar presence bit — a tile only pays for the event types
  (and slots) actually present at that timestep. Real replication
  storms are type-homogeneous across lanes at most steps, so this skips
  most of the transition table most of the time; the worst (fully
  mixed) case degrades to the branchless cost, never above it.
- **Any capacity**: the slot update is one loop body over 32-slot
  blocks at a dynamic row offset, so its traced size is the same at
  every slot-table width, and the batch tile shrinks (``fit_tile``)
  until a wide state still fits the VMEM the kernel asks for.

Semantics are identical to ops/replay.py (the oracle's, i.e. the
reference's stateBuilder.applyEvents,
/root/reference/service/history/stateBuilder.go:112-613);
tests/test_replay_pallas.py asserts bit-for-bit state parity against
the XLA kernel, which is itself differential-tested against the host
oracle.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cadence_tpu.core.enums import (
    CloseStatus, EventType as E, WorkflowState,
    WORKFLOW_CLOSE_STATUS, decision_attempt_increment,
)
from cadence_tpu.core.ids import EMPTY_EVENT_ID, EMPTY_VERSION
from cadence_tpu.utils.tracing import TRACER

from . import schema as S


def on_tpu() -> bool:
    """Whether the default device is a TPU: the one question that picks
    every replay path. There the Pallas kernels run compiled; elsewhere
    the XLA scans run, and a Pallas call is interpreted. Tests steer a
    TPU branch onto the CPU by patching this function."""
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class RowMap:
    """Static row offsets of each state tensor inside the [R, B] matrix."""

    caps: S.Capacities
    exec0: int = 0

    @property
    def vh0(self) -> int:  # vh_items rows: vh0 + i*2 + {0: event_id, 1: version}
        return self.exec0 + S.X_N

    @property
    def vhlen(self) -> int:
        return self.vh0 + 2 * self.caps.max_version_items

    @property
    def act0(self) -> int:
        return self.vhlen + 1

    @property
    def tim0(self) -> int:
        return self.act0 + self.caps.max_activities * S.AC_N

    @property
    def chd0(self) -> int:
        return self.tim0 + self.caps.max_timers * S.TI_N

    @property
    def rc0(self) -> int:
        return self.chd0 + self.caps.max_children * S.CH_N

    @property
    def sg0(self) -> int:
        return self.rc0 + self.caps.max_request_cancels * S.RC_N

    @property
    def rows(self) -> int:
        return self.sg0 + self.caps.max_signals_ext * S.SG_N

    @property
    def rows_padded(self) -> int:
        return ((self.rows + 7) // 8) * 8


def state_to_rows(state: S.StateTensors, rm: RowMap):
    """StateTensors -> [R, B] int32 (jnp), batch minor."""
    b = state.exec_info.shape[0]
    parts = [
        jnp.transpose(state.exec_info),                       # [X_N, B]
        jnp.transpose(state.vh_items.reshape(b, -1)),         # [2V, B]
        state.vh_len[None, :],                                # [1, B]
        jnp.transpose(state.activities.reshape(b, -1)),
        jnp.transpose(state.timers.reshape(b, -1)),
        jnp.transpose(state.children.reshape(b, -1)),
        jnp.transpose(state.cancels.reshape(b, -1)),
        jnp.transpose(state.signals.reshape(b, -1)),
    ]
    rows = jnp.concatenate(parts, axis=0).astype(jnp.int32)
    pad = rm.rows_padded - rm.rows
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    return rows


def rows_to_state(rows, rm: RowMap) -> S.StateTensors:
    caps = rm.caps
    b = rows.shape[1]

    def take(r0, n, shape):
        return jnp.transpose(rows[r0 : r0 + n]).reshape(shape)

    return S.StateTensors(
        exec_info=take(rm.exec0, S.X_N, (b, S.X_N)),
        vh_items=take(rm.vh0, 2 * caps.max_version_items,
                      (b, caps.max_version_items, 2)),
        vh_len=rows[rm.vhlen],
        activities=take(rm.act0, caps.max_activities * S.AC_N,
                        (b, caps.max_activities, S.AC_N)),
        timers=take(rm.tim0, caps.max_timers * S.TI_N,
                    (b, caps.max_timers, S.TI_N)),
        children=take(rm.chd0, caps.max_children * S.CH_N,
                      (b, caps.max_children, S.CH_N)),
        cancels=take(rm.rc0, caps.max_request_cancels * S.RC_N,
                     (b, caps.max_request_cancels, S.RC_N)),
        signals=take(rm.sg0, caps.max_signals_ext * S.SG_N,
                     (b, caps.max_signals_ext, S.SG_N)),
    )


def _kernel(presence_ref, base_ref, ev_ref, init_ref, st, *, rm: RowMap,
            tb: int, narrow: bool = False, wide_cols: tuple = ()):
    """One (batch-tile, time-block) grid step.

    The batch tile is shaped (SL, 128) with SL a multiple of 8 — whole
    int32 VPU tiles — so every row update runs at full sublane x lane
    utilization (a flat [BT] row would occupy 1 of 8 sublanes). A
    pre-PR-1 v5e run found the kernel bound by streaming the event
    blocks from HBM, not by the step body (an empty step body took the
    same wall time as the full FSM at B=65536), so SL mainly trades
    VMEM for fewer grid steps; bt=8192 (SL=64) was best there. Neither
    is re-measured yet (PERF.md).

    presence_ref: [1, TB, W] SMEM — per-step scalar gates for this
             tile: words 0-1 are the event-type bitmask (bit e of word
             e//32 set iff some lane has type e), word 2 + k is the
             slot-presence bitmask of slots 32k..32k+31 (bit s%32 set
             iff some lane's event touches slot s of any table), and
             ``W = presence_words(caps)`` pads to at least 4. Precomputed
             in parallel by XLA outside the kernel, so the sequential
             loop gates each type's (and slot block's, and slot's)
             update on a SCALAR bit test instead of a cross-lane
             ``jnp.any`` reduction.
    ev_ref:  [TB, EV_N, 1, SL, 128] — the time block's events
    init_ref:[R, 1, SL, 128] — initial state block (only read at t==0)
    st:      [R, 1, SL, 128] — output state block, VMEM-resident across t
    """
    caps = rm.caps
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _():
        # eight rows at a time: the copy's code does not grow with R
        def copy(k, c):
            rows = pl.ds(pl.multiple_of(k * 8, 8), 8)
            st[rows] = init_ref[rows]
            return c

        lax.fori_loop(0, rm.rows_padded // 8, copy, 0)

    def rd(r):
        return st[r, 0]

    def wr(r, mask, val):
        st[r, 0] = jnp.where(mask, val, st[r, 0])

    def step(i, carry):
        w0 = presence_ref[0, i, 0]
        w1 = presence_ref[0, i, 1]

        def present(*types):
            """Scalar: any lane in this tile has one of these types."""
            out = None
            for t in types:
                t = int(t)
                bit = ((w0 if t < 32 else w1) >> (t % 32)) & 1
                out = bit if out is None else out | bit
            return out != 0

        ev = ev_ref[i]  # [EV_N(phys), 1, SL, 128]
        if narrow:
            # int16 stream (narrow_events_teb): affine columns
            # reconstruct as stored16 + base[c]; wide columns as
            # (lo16 & 0xffff) | hi16 << 16 — exact int32 either way.
            # The reconstruction ALU is VPU noise against the stream
            # the kernel is bound by (module docstring / step-body note)
            phys, _ = _phys_map(wide_cols)

            def fld(c):
                p = phys[c]
                if c in wide_cols:
                    lo16 = ev[p, 0].astype(jnp.int32) & 0xFFFF
                    return lo16 | (ev[p + 1, 0].astype(jnp.int32) << 16)
                return ev[p, 0].astype(jnp.int32) + base_ref[0, c]
        else:
            def fld(c):
                return ev[c, 0]

        et = fld(S.EV_TYPE)
        valid = et >= 0

        ev_id = fld(S.EV_ID)
        version = fld(S.EV_VERSION)
        ts = fld(S.EV_TS)
        batch_first = fld(S.EV_BATCH_FIRST)
        slot = fld(S.EV_SLOT)
        a0, a1 = fld(S.EV_A0), fld(S.EV_A1)
        a2, a3 = fld(S.EV_A2), fld(S.EV_A3)
        a4, a5 = fld(S.EV_A4), fld(S.EV_A5)
        a6, a7 = fld(S.EV_A6), fld(S.EV_A7)

        X = rm.exec0

        def m(*types):
            out = et == int(types[0])
            for t in types[1:]:
                out = out | (et == int(t))
            return valid & out

        # ---- preamble (stateBuilder.go:134-155)
        wr(X + S.X_LAST_EVENT_TASK_ID, valid, fld(S.EV_TASK_ID))
        wr(X + S.X_CUR_VERSION, valid, version)
        wr(X + S.X_NEXT_EVENT_ID, valid, ev_id + 1)
        wr(X + S.X_LAST_FIRST_EVENT_ID, valid, batch_first)

        # ---- version-history AddOrUpdateItem
        cap_v = caps.max_version_items
        vh_len = rd(rm.vhlen)
        last_idx = jnp.maximum(vh_len - 1, 0)
        # clamped read of the last materialized slot (see replay.py:
        # overflowed vh_len must compare against slot cap_v-1, not fall
        # through to the zero init); write_idx keeps the raw last_idx so
        # same-version writes past capacity still match no slot
        read_idx = jnp.minimum(last_idx, cap_v - 1)
        last_ver = jnp.zeros_like(vh_len)
        for i_v in range(cap_v):
            last_ver = jnp.where(read_idx == i_v, rd(rm.vh0 + 2 * i_v + 1),
                                 last_ver)
        same = (vh_len > 0) & (last_ver == version)
        write_idx = jnp.where(same, last_idx,
                              jnp.minimum(vh_len, cap_v - 1))
        for i_v in range(cap_v):
            wmask = valid & (write_idx == i_v)
            wr(rm.vh0 + 2 * i_v, wmask, ev_id)
            wr(rm.vh0 + 2 * i_v + 1, wmask, version)
        wr(rm.vhlen, valid & ~same, vh_len + 1)

        # ---- workflow lifecycle
        @pl.when(present(E.WorkflowExecutionStarted))
        def _():
            m_start = m(E.WorkflowExecutionStarted)
            wr(X + S.X_STATE, m_start, int(WorkflowState.Created))
            wr(X + S.X_CLOSE_STATUS, m_start, int(CloseStatus.NONE))
            wr(X + S.X_LAST_PROCESSED_EVENT, m_start, EMPTY_EVENT_ID)
            wr(X + S.X_START_TS, m_start, ts)
            wr(X + S.X_WORKFLOW_TIMEOUT, m_start, a0)
            wr(X + S.X_DECISION_TIMEOUT_VALUE, m_start, a1)
            wr(X + S.X_ATTEMPT, m_start, a2)
            wr(X + S.X_HAS_RETRY_POLICY, m_start, a3)
            wr(X + S.X_WF_EXPIRATION_TS, m_start, a4)
            wr(X + S.X_PARENT_INITIATED_ID, m_start, a7)
            wr(X + S.X_DEC_SCHEDULE_ID, m_start, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_STARTED_ID, m_start, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_VERSION, m_start, EMPTY_VERSION)
            for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT,
                        S.X_DEC_SCHEDULED_TS, S.X_DEC_STARTED_TS,
                        S.X_DEC_ORIGINAL_SCHEDULED_TS):
                wr(X + col, m_start, 0)

        @pl.when(present(*(t for t, _ in WORKFLOW_CLOSE_STATUS)))
        def _():
            close_status = sum(
                m(t) * int(cs) for t, cs in WORKFLOW_CLOSE_STATUS
            )
            m_close = close_status > 0
            wr(X + S.X_STATE, m_close, int(WorkflowState.Completed))
            wr(X + S.X_CLOSE_STATUS, m_close, close_status)
            wr(X + S.X_COMPLETION_EVENT_BATCH_ID, m_close, batch_first)

        @pl.when(present(E.WorkflowExecutionCancelRequested))
        def _():
            m_creq = m(E.WorkflowExecutionCancelRequested)
            wr(X + S.X_CANCEL_REQUESTED, m_creq, 1)

        @pl.when(present(E.WorkflowExecutionSignaled))
        def _():
            m_sig = m(E.WorkflowExecutionSignaled)
            wr(X + S.X_SIGNAL_COUNT, m_sig, rd(X + S.X_SIGNAL_COUNT) + 1)

        # ---- decision sub-FSM
        @pl.when(present(E.DecisionTaskScheduled))
        def _():
            m_dsch = m(E.DecisionTaskScheduled)
            wr(X + S.X_DEC_VERSION, m_dsch, version)
            wr(X + S.X_DEC_SCHEDULE_ID, m_dsch, ev_id)
            wr(X + S.X_DEC_STARTED_ID, m_dsch, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_TIMEOUT, m_dsch, a0)
            wr(X + S.X_DEC_ATTEMPT, m_dsch, a1)
            wr(X + S.X_DEC_SCHEDULED_TS, m_dsch, ts)
            wr(X + S.X_DEC_ORIGINAL_SCHEDULED_TS, m_dsch, ts)
            wr(X + S.X_DEC_STARTED_TS, m_dsch, 0)

        @pl.when(present(E.DecisionTaskStarted))
        def _():
            m_dsta = m(E.DecisionTaskStarted)
            wr(X + S.X_STATE,
               m_dsta & (rd(X + S.X_STATE) == int(WorkflowState.Created)),
               int(WorkflowState.Running))
            wr(X + S.X_DEC_VERSION, m_dsta, version)
            wr(X + S.X_DEC_STARTED_ID, m_dsta, ev_id)
            wr(X + S.X_DEC_ATTEMPT, m_dsta, 0)
            wr(X + S.X_DEC_STARTED_TS, m_dsta, ts)

        @pl.when(present(E.DecisionTaskCompleted))
        def _():
            m_dcom = m(E.DecisionTaskCompleted)
            wr(X + S.X_DEC_VERSION, m_dcom, EMPTY_VERSION)
            wr(X + S.X_DEC_SCHEDULE_ID, m_dcom, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_STARTED_ID, m_dcom, EMPTY_EVENT_ID)
            for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT,
                        S.X_DEC_SCHEDULED_TS, S.X_DEC_STARTED_TS):
                wr(X + col, m_dcom, 0)
            wr(X + S.X_LAST_PROCESSED_EVENT, m_dcom, a0)

        @pl.when(present(E.DecisionTaskTimedOut, E.DecisionTaskFailed))
        def _():
            m_dto = m(E.DecisionTaskTimedOut)
            m_dfail = m(E.DecisionTaskFailed)
            increment = decision_attempt_increment(m_dfail, m_dto, a0)
            no_increment = (m_dto | m_dfail) & ~increment
            new_attempt = rd(X + S.X_DEC_ATTEMPT) + 1
            wr(X + S.X_DEC_VERSION, increment, rd(X + S.X_CUR_VERSION))
            wr(X + S.X_DEC_SCHEDULE_ID, increment, batch_first)
            wr(X + S.X_DEC_STARTED_ID, increment, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_TIMEOUT, increment,
               rd(X + S.X_DECISION_TIMEOUT_VALUE))
            wr(X + S.X_DEC_ATTEMPT, increment, new_attempt)
            wr(X + S.X_DEC_SCHEDULED_TS, increment, ts)
            wr(X + S.X_DEC_STARTED_TS, increment, 0)
            wr(X + S.X_DEC_ORIGINAL_SCHEDULED_TS, increment, 0)

            wr(X + S.X_DEC_VERSION, no_increment, EMPTY_VERSION)
            wr(X + S.X_DEC_SCHEDULE_ID, no_increment, EMPTY_EVENT_ID)
            wr(X + S.X_DEC_STARTED_ID, no_increment, EMPTY_EVENT_ID)
            for col in (S.X_DEC_TIMEOUT, S.X_DEC_ATTEMPT,
                        S.X_DEC_SCHEDULED_TS, S.X_DEC_STARTED_TS,
                        S.X_DEC_ORIGINAL_SCHEDULED_TS):
                wr(X + col, no_increment, 0)

        # ---- slot-table helper: per-slot predicated updates
        def for_slots(types, cap, fn):
            """``fn(s, mask)`` for each slot ``s`` < ``cap`` that some
            lane's event touches. One loop body at every capacity: the
            32-slot blocks go by gated on their presence word, each
            block's slots on their bit, and ``fn`` writes rows at a
            dynamic offset on the state's leading (untiled) dimension.
            The bits alias across slot tables — a false positive only
            runs the masked writes with an all-false mask, a no-op."""
            n_words = -(-cap // 32)
            per_word = min(cap, 32)
            guard = n_words > 1 and cap % 32

            @pl.when(present(*types))
            def _():
                base_mask = m(*types)

                def block(w):
                    word = presence_ref[0, i, 2 + w]

                    def one(k, c):
                        s_i = w * 32 + k
                        hit = ((word >> k) & 1) != 0
                        if guard:  # the last block runs past cap
                            hit = hit & (s_i < cap)

                        @pl.when(hit)
                        def _():
                            fn(s_i, base_mask & (slot == s_i))
                        return c

                    @pl.when(word != 0)
                    def _():
                        lax.fori_loop(0, per_word, one, 0)

                if n_words == 1:
                    block(0)
                else:
                    lax.fori_loop(
                        0, n_words, lambda w, c: (block(w), c)[1], 0)

        # ---- pending activities
        A = rm.act0

        def act_sched(s_i, mask_s):
            r = A + s_i * S.AC_N
            exp_interval = jnp.where((a5 > 0) & (a6 > a2), a6, a2)
            vals = {
                S.AC_OCC: 1, S.AC_VERSION: version,
                S.AC_SCHEDULE_ID: ev_id,
                S.AC_SCHEDULED_BATCH_ID: batch_first,
                S.AC_SCHEDULED_TS: ts, S.AC_STARTED_ID: EMPTY_EVENT_ID,
                S.AC_STARTED_TS: 0, S.AC_ID_HASH: a0,
                S.AC_SCH_TO_START: a1, S.AC_SCH_TO_CLOSE: a2,
                S.AC_START_TO_CLOSE: a3, S.AC_HEARTBEAT: a4,
                S.AC_CANCEL_REQUESTED: 0,
                S.AC_CANCEL_REQUEST_ID: EMPTY_EVENT_ID,
                S.AC_ATTEMPT: 0, S.AC_HAS_RETRY: a5,
                S.AC_EXPIRATION_TS: ts + exp_interval,
                S.AC_LAST_HB_TS: 0, S.AC_TIMER_STATUS: 0,
            }
            for col in range(S.AC_N):
                wr(r + col, mask_s, vals[col])

        for_slots((E.ActivityTaskScheduled,), caps.max_activities,
                  act_sched)

        def act_start(s_i, mask_s):
            r = A + s_i * S.AC_N
            wr(r + S.AC_VERSION, mask_s, version)
            wr(r + S.AC_STARTED_ID, mask_s, ev_id)
            wr(r + S.AC_STARTED_TS, mask_s, ts)
            wr(r + S.AC_LAST_HB_TS, mask_s, ts)
            wr(r + S.AC_ATTEMPT, mask_s, a1)

        for_slots((E.ActivityTaskStarted,), caps.max_activities,
                  act_start)

        def act_close(s_i, mask_s):
            r = A + s_i * S.AC_N
            for col in range(S.AC_N):
                wr(r + col, mask_s, 0)

        for_slots(
            (E.ActivityTaskCompleted, E.ActivityTaskFailed,
             E.ActivityTaskTimedOut, E.ActivityTaskCanceled),
            caps.max_activities, act_close,
        )

        def act_creq(s_i, mask_s):
            r = A + s_i * S.AC_N
            wr(r + S.AC_VERSION, mask_s, version)
            wr(r + S.AC_CANCEL_REQUESTED, mask_s, 1)
            wr(r + S.AC_CANCEL_REQUEST_ID, mask_s, ev_id)

        for_slots((E.ActivityTaskCancelRequested,), caps.max_activities,
                  act_creq)

        # ---- pending timers
        T_ = rm.tim0

        def tim_start(s_i, mask_s):
            r = T_ + s_i * S.TI_N
            wr(r + S.TI_OCC, mask_s, 1)
            wr(r + S.TI_VERSION, mask_s, version)
            wr(r + S.TI_STARTED_ID, mask_s, ev_id)
            wr(r + S.TI_ID_HASH, mask_s, a0)
            wr(r + S.TI_EXPIRY_TS, mask_s, ts + a1)
            wr(r + S.TI_STATUS, mask_s, 0)

        for_slots((E.TimerStarted,), caps.max_timers, tim_start)

        def tim_close(s_i, mask_s):
            r = T_ + s_i * S.TI_N
            for col in range(S.TI_N):
                wr(r + col, mask_s, 0)

        for_slots((E.TimerFired, E.TimerCanceled), caps.max_timers,
                  tim_close)

        # ---- pending children
        C_ = rm.chd0

        def chd_init(s_i, mask_s):
            r = C_ + s_i * S.CH_N
            vals = {
                S.CH_OCC: 1, S.CH_VERSION: version,
                S.CH_INITIATED_ID: ev_id,
                S.CH_INITIATED_BATCH_ID: batch_first,
                S.CH_STARTED_ID: EMPTY_EVENT_ID, S.CH_WF_ID_HASH: a0,
                S.CH_RUN_ID_HASH: 0, S.CH_POLICY: a1,
            }
            for col in range(S.CH_N):
                wr(r + col, mask_s, vals[col])

        for_slots((E.StartChildWorkflowExecutionInitiated,),
                  caps.max_children, chd_init)

        def chd_start(s_i, mask_s):
            r = C_ + s_i * S.CH_N
            wr(r + S.CH_STARTED_ID, mask_s, ev_id)
            wr(r + S.CH_RUN_ID_HASH, mask_s, a1)

        for_slots((E.ChildWorkflowExecutionStarted,), caps.max_children,
                  chd_start)

        def chd_close(s_i, mask_s):
            r = C_ + s_i * S.CH_N
            for col in range(S.CH_N):
                wr(r + col, mask_s, 0)

        for_slots(
            (E.StartChildWorkflowExecutionFailed,
             E.ChildWorkflowExecutionCompleted,
             E.ChildWorkflowExecutionFailed,
             E.ChildWorkflowExecutionCanceled,
             E.ChildWorkflowExecutionTimedOut,
             E.ChildWorkflowExecutionTerminated),
            caps.max_children, chd_close,
        )

        # ---- pending external cancels / signals
        def rc_init(s_i, mask_s):
            r = rm.rc0 + s_i * S.RC_N
            wr(r + 0, mask_s, 1)
            wr(r + 1, mask_s, version)
            wr(r + 2, mask_s, ev_id)
            wr(r + 3, mask_s, batch_first)

        for_slots((E.RequestCancelExternalWorkflowExecutionInitiated,),
                  caps.max_request_cancels, rc_init)

        def rc_close(s_i, mask_s):
            r = rm.rc0 + s_i * S.RC_N
            for col in range(S.RC_N):
                wr(r + col, mask_s, 0)

        for_slots(
            (E.RequestCancelExternalWorkflowExecutionFailed,
             E.ExternalWorkflowExecutionCancelRequested),
            caps.max_request_cancels, rc_close,
        )

        def sg_init(s_i, mask_s):
            r = rm.sg0 + s_i * S.SG_N
            wr(r + 0, mask_s, 1)
            wr(r + 1, mask_s, version)
            wr(r + 2, mask_s, ev_id)
            wr(r + 3, mask_s, batch_first)

        for_slots((E.SignalExternalWorkflowExecutionInitiated,),
                  caps.max_signals_ext, sg_init)

        def sg_close(s_i, mask_s):
            r = rm.sg0 + s_i * S.SG_N
            for col in range(S.SG_N):
                wr(r + col, mask_s, 0)

        for_slots(
            (E.SignalExternalWorkflowExecutionFailed,
             E.ExternalWorkflowExecutionSignaled),
            caps.max_signals_ext, sg_close,
        )
        return carry

    lax.fori_loop(0, tb, step, 0)


BT = 4096  # default batch tile = one (32, 128) int32 block per row

# the scoped VMEM the replay kernel asks for (v5e has 128 MiB), and what
# fit_tile leaves of it to the compiler's own temporaries
VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_SPARE = 12 * 1024 * 1024


def presence_words(caps: S.Capacities) -> int:
    """Words of the kernel's per-step presence block: two of event-type
    bits, one per 32 slots of the widest slot table, at least 4 (the
    host packer's masks, ``PackedHistories.presence``)."""
    widest = max(caps.max_activities, caps.max_timers, caps.max_children,
                 caps.max_request_cancels, caps.max_signals_ext)
    return 2 + max(2, -(-widest // 32))


def fit_tile(caps: S.Capacities, bt: int = BT, tb: int = 16,
             ev_bytes: int = S.EV_N * 4):
    """(tile, state_buffers): the widest batch tile, ``bt`` or ``bt``
    halved down to 1,024 lanes, whose VMEM blocks fit the kernel's
    limit — the [R, tile] state in and out, double-buffered while that
    fits at some tile and else single-buffered, and ``tb`` steps of
    ``ev_bytes`` per lane of events, double-buffered. The default caps
    keep a 4,096-lane tile, double-buffered; a wide slot table trades
    lanes (the storm's batches fill a few dozen of them) for rows.
    ``ev_bytes`` is the physical event columns times their itemsize."""
    rows = RowMap(caps).rows_padded
    for buffers in (2, 1):
        t = bt
        while t >= 1024 and t % 1024 == 0:
            need = 2 * buffers * rows * t * 4 + 2 * tb * ev_bytes * t
            if need <= VMEM_LIMIT - _VMEM_SPARE:
                return t, buffers
            t //= 2
    raise ValueError(
        f"a state of {rows} rows does not fit {VMEM_LIMIT} bytes of VMEM "
        f"at any batch tile")


def _phys_map(wide_cols):
    """Logical column -> physical int16 column start; wide columns
    occupy two physical columns (lo16, hi16)."""
    phys = {}
    p = 0
    for c in range(S.EV_N):
        phys[c] = p
        p += 2 if c in wide_cols else 1
    return phys, p


def narrow_events_teb(events_teb, force_wide=()):
    """Narrow an int32 [T, EV_N, B] event tensor to an int16 stream.

    The kernel is bound by streaming the event tensor from HBM (the
    empty step body measures the same wall time as the full FSM —
    ``_kernel``), so shrinking the stream's bytes is the per-tile
    throughput lever. Each column whose value span fits int16 is stored
    affine (``ev - base[c]``, base = column midrange); a wide column
    (hash-valued attributes, raw timestamps) is stored EXACTLY as two
    int16 halves (low 16 bits, high 16 bits). The kernel reconstructs
    exact int32 values either way, so the state output is bit-identical
    to the int32 path. Typical mix: 1-3 wide columns of 16 -> ~45-50%
    of the original stream bytes.

    ``force_wide``: columns stored wide regardless of this tensor's
    span. Repeat callers (the serving dispatcher) pass their running
    union so the static wide set — a jit/Mosaic specialization key —
    grows monotonically instead of flapping per batch, which would
    recompile the kernel mid-storm.

    Returns (ev16 [T, P, B] int16, base [EV_N] int32, wide_cols tuple),
    or None when EV_TYPE/EV_SLOT would be wide (they gate presence
    masks; enum-bounded in practice) — callers keep the int32 path,
    correctness never depends on narrowing.
    """
    ev = np.asarray(events_teb)
    lo = ev.min(axis=(0, 2)).astype(np.int64)
    hi = ev.max(axis=(0, 2)).astype(np.int64)
    wide_cols = tuple(sorted(set(
        int(c) for c in range(S.EV_N) if hi[c] - lo[c] > 65000
    ) | set(int(c) for c in force_wide)))
    if S.EV_TYPE in wide_cols or S.EV_SLOT in wide_cols:
        return None
    base64 = ((lo + hi) // 2)
    base64[list(wide_cols)] = 0
    phys, P = _phys_map(wide_cols)
    T, _, B = ev.shape
    out = np.empty((T, P, B), np.int16)
    # no widening staging needed: the wide lo-half is exactly the
    # two's-complement int16 truncation, and the affine subtraction
    # cannot overflow int32 (|col - base| <= ~32.5k by construction)
    for c in range(S.EV_N):
        p = phys[c]
        col = ev[:, c, :]
        if c in wide_cols:
            out[:, p, :] = col.astype(np.int16)          # low 16 bits
            out[:, p + 1, :] = (col >> 16).astype(np.int16)
        else:
            out[:, p, :] = (col - np.int32(base64[c])).astype(np.int16)
    return out, base64.astype(np.int32), wide_cols


def _replay_rows_pallas(events_teb, rows0, caps: S.Capacities,
                        tb: int, interpret: bool, bt: int = BT,
                        presence=None, base=None, wide_cols: tuple = ()):
    """Dispatch wrapper: concrete interpret-mode calls (the CPU parity
    path — tests and CPU serving, never the TPU hot path) go through a
    cached AOT lower/compile at XLA opt level 0. Interpret tracing +
    optimizing the emulated kernel costs tens of seconds per call and
    an eager invocation never hits the jit executable cache (fresh
    closure identity each call); runtime of the emulated kernel is
    negligible either way, so the optimizer pays for nothing."""
    if interpret and not any(
        isinstance(a, jax.core.Tracer)
        for a in (events_teb, rows0, presence, base)
    ):
        args = (jnp.asarray(events_teb), jnp.asarray(rows0),
                None if presence is None else jnp.asarray(presence),
                None if base is None else jnp.asarray(base))
        exe = _interp_rows_exec(
            caps, tb, bt, tuple(wide_cols),
            tuple(_avkey(a) for a in args))
        return exe(*args)
    return _replay_rows_pallas_jit(
        events_teb, rows0, caps, tb, interpret, bt, presence, base,
        tuple(wide_cols))


def _avkey(x):
    return None if x is None else (tuple(x.shape), x.dtype.name)


@functools.lru_cache(maxsize=64)
def _interp_rows_exec(caps, tb, bt, wide_cols, avkey):
    avals = [
        None if k is None else jax.ShapeDtypeStruct(k[0], k[1])
        for k in avkey
    ]
    low = _replay_rows_pallas_jit.lower(
        avals[0], avals[1], caps, tb, True, bt, avals[2], avals[3],
        wide_cols)
    return low.compile({"xla_backend_optimization_level": 0})


@functools.partial(jax.jit,
                   static_argnames=("caps", "tb", "interpret", "bt",
                                    "wide_cols"))
def _replay_rows_pallas_jit(events_teb, rows0, caps: S.Capacities,
                            tb: int, interpret: bool, bt: int = BT,
                            presence=None, base=None,
                            wide_cols: tuple = ()):
    """events_teb: [T, EV_N, B] int32 — or the int16 narrow stream from
    ``narrow_events_teb`` (physical layout, with ``base`` [EV_N] int32
    and the static ``wide_cols`` tuple); rows0: [R, B]. Returns [R, B].

    B must be a multiple of ``bt``; each batch tile is viewed as
    (bt//128, 128). ``tb * EV_N * bt * 4`` bytes of events are VMEM-
    resident per grid step (double-buffered by Pallas) — keep it under
    ~4MB (tb=16 at bt=4096).
    """
    if bt % 1024:
        raise ValueError(
            f"bt={bt} must be a multiple of 1024: each batch tile is viewed "
            "as (bt//128, 128) and bt//128 must be a multiple of 8 (whole "
            "int32 VPU tiles, the kernel's layout assumption)")
    narrow = events_teb.dtype == jnp.int16
    if narrow and base is None:
        raise ValueError("int16 events need their affine base vector")
    rm = RowMap(caps)
    sl = bt // 128
    T, ev_n, B = events_teb.shape
    R = rm.rows_padded
    n_bt = B // bt
    n_words = presence_words(caps)
    _, buffers = fit_tile(caps, bt, tb, ev_n * events_teb.dtype.itemsize)
    ev5 = events_teb.reshape(T, ev_n, n_bt, sl, 128)
    rows5 = rows0.reshape(R, n_bt, sl, 128)
    if base is None:
        base = jnp.zeros((ev_n,), jnp.int32)
    base2 = jnp.asarray(base, jnp.int32)[None, :]

    if presence is None:
        # per-(step, tile) event-type presence bitmask, computed in
        # parallel here so the kernel's sequential loop reads scalars
        # from SMEM. Callers that pack host-side pass it precomputed
        # (PackedHistories.presence) — the XLA reduction over the full
        # event tensor is a measurable share of replay time.
        phys, _ = _phys_map(wide_cols) if narrow else ({c: c for c in
                                                        range(S.EV_N)}, 0)
        et = ev5[:, phys[S.EV_TYPE]].astype(jnp.int32)
        slot_v = ev5[:, phys[S.EV_SLOT]].astype(jnp.int32)
        if narrow:
            et = et + base2[0, S.EV_TYPE]
            slot_v = slot_v + base2[0, S.EV_SLOT]
        et_valid = et >= 0
        word = jnp.where(et_valid, et // 32, 0)
        bit = jnp.where(et_valid, jnp.left_shift(1, et % 32), 0)
        slot_ok = et_valid & (slot_v >= 0)
        slot_word = jnp.where(slot_ok, slot_v // 32, -1)
        slot_bit = jnp.where(slot_ok, jnp.left_shift(1, slot_v % 32), 0)

        def any_bits(bits):
            return lax.reduce(bits, jnp.int32(0), lax.bitwise_or, (2, 3))

        words = [any_bits(jnp.where(et_valid & (word == w), bit, 0))
                 for w in (0, 1)]
        words += [any_bits(jnp.where(slot_word == w, slot_bit, 0))
                  for w in range(n_words - 2)]
        presence = jnp.stack(words, axis=-1).astype(jnp.int32)
        presence = jnp.transpose(presence, (1, 0, 2))  # [n_bt, T, W]

    # a state block too large to double-buffer is fetched and written
    # back once per batch tile (fit_tile)
    state_mode = None if buffers == 2 else pl.Buffered(1)
    grid = (n_bt, T // tb)
    out = pl.pallas_call(
        functools.partial(_kernel, rm=rm, tb=tb, narrow=narrow,
                          wide_cols=wide_cols),
        out_shape=jax.ShapeDtypeStruct((R, n_bt, sl, 128), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tb, n_words), lambda b, t: (b, t, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, S.EV_N), lambda b, t: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tb, ev_n, 1, sl, 128),
                         lambda b, t: (t, 0, b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((R, 1, sl, 128), lambda b, t: (0, b, 0, 0),
                         pipeline_mode=state_mode,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((R, 1, sl, 128), lambda b, t: (0, b, 0, 0),
                               pipeline_mode=state_mode,
                               memory_space=pltpu.VMEM),
        # double-buffered blocks (events x2, init x2, out x2) exceed the
        # 16MiB default scoped-vmem budget once n_bt > 1; v5e has 128MiB
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(presence, base2, ev5, rows5)
    return out.reshape(R, B)


def replay_scan_pallas_teb(
    state: S.StateTensors,
    events_teb,
    caps: S.Capacities,
    tb: int = 16,
    interpret: bool | None = None,
    bt: int = BT,
    presence=None,
    base=None,
    wide_cols: tuple = (),
) -> S.StateTensors:
    """Replay on the Pallas kernel from the field-major event layout.

    events_teb: [T, EV_N, B] (``PackedHistories.teb()``) — the kernel's
    native operand layout; no device-side transpose happens here, which
    matters: at large B transposing the event tensor costs more HBM
    traffic than the entire replay scan. May be int16 with ``base``
    [EV_N] int32 (the affine narrow stream from ``narrow_events_teb`` —
    halves the HBM traffic the kernel is bound by). Pads B to a
    multiple of ``bt`` (invalid events + empty state) and T to a
    multiple of ``tb`` (invalid events are no-ops). ``bt`` is the widest
    tile; a wide state gets a narrower one (``fit_tile``), and host
    ``presence`` masks built for another tile or word count are left
    for the kernel to compute.
    """
    if interpret is None:
        interpret = not on_tpu()
    events_teb = jnp.asarray(events_teb)
    narrow = events_teb.dtype == jnp.int16
    T, ev_n, B = events_teb.shape
    rm = RowMap(caps)
    bt, _ = fit_tile(caps, bt, tb, ev_n * events_teb.dtype.itemsize)
    b_pad = (-B) % bt
    t_pad = (-T) % tb
    span = TRACER.current()
    if span is not None:  # event cells the kernel streams, padding in
        span.set_tag("cells", (B + b_pad) * (T + t_pad))
        span.set_tag("state_rows", rm.rows_padded)

    if t_pad or b_pad:
        if narrow:
            # padding must reconstruct EV_TYPE == -1 through the base;
            # wide columns pad as 0 halves (reconstruct 0, and invalid
            # rows never read past the type anyway)
            phys, _ = _phys_map(wide_cols)
            pad_type = jnp.int16(-1 - int(np.asarray(base)[S.EV_TYPE]))
            fill = jnp.zeros((t_pad + T, ev_n, B + b_pad), jnp.int16)
            fill = fill.at[:, phys[S.EV_TYPE], :].set(pad_type)
        else:
            fill = jnp.zeros((t_pad + T, ev_n, B + b_pad), jnp.int32)
            fill = fill.at[:, S.EV_TYPE, :].set(-1)
        events_teb = fill.at[:T, :, :B].set(events_teb)

    if presence is not None:
        presence = jnp.asarray(presence)
        if b_pad or presence.shape[::2] != (B // bt,
                                            presence_words(caps)):
            # host masks don't cover the padded tiles, or were built
            # for another tile or slot width
            presence = None
        elif t_pad:
            presence = jnp.pad(presence, ((0, 0), (0, t_pad), (0, 0)))

    rows0 = state_to_rows(state, rm)
    if b_pad:
        pad_state = S.empty_state(b_pad, caps)
        pad_state = jax.tree_util.tree_map(jnp.asarray, pad_state)
        rows0 = jnp.concatenate(
            [rows0, state_to_rows(pad_state, rm)], axis=1
        )

    rows = _replay_rows_pallas(events_teb, rows0, caps, tb, interpret, bt,
                               presence, base, wide_cols=tuple(wide_cols))
    return rows_to_state(rows[:, :B], rm)


def replay_scan_pallas_packed(
    state: S.StateTensors,
    out0: S.StateTensors,
    events_teb,
    seg_end,
    out_row,
    caps: S.Capacities,
    tb: int = 16,
    interpret: bool | None = None,
    bt: int = BT,
    base=None,
    wide_cols: tuple = (),
    init: S.StateTensors | None = None,
    reset_row=None,
):
    """Lane-packed replay on the Pallas kernel (mirror of
    ops.replay.replay_scan_packed).

    The VMEM-resident kernel has no cross-lane scatter, so segment
    flush/reset happens *between* time blocks: histories must be packed
    with ``seg_align`` a multiple of ``tb`` (pack_lanes(seg_align=tb)),
    which pins every segment boundary to a block-final step. The scan
    then alternates: kernel advances one tb-step block with the lane
    tile in VMEM → XLA scatters flagged lanes' state columns into their
    output rows and resets them to empty. Relative to the unpacked
    kernel this flushes state per block instead of once per batch tile —
    the price of emitting mid-scan snapshots — while the event stream
    (the bound) is unchanged.

    ``events_teb``: [T, EV_N, L]; ``seg_end``/``out_row``: [L, T];
    ``out0``: [n_out] empty_state buffer (same contract as the XLA
    packed scan). May be the int16 narrow stream from
    ``narrow_events_teb`` (pass its ``base`` [EV_N] int32 and static
    ``wide_cols``) — exact int32 reconstruction in-kernel, bit-identical
    output, about half the event-stream bytes the kernel is bound by.

    ``init``/``reset_row``: checkpoint resume (same contract as
    ops.replay.replay_scan_packed) — ``init`` is the [n_init] initial
    carries and ``reset_row`` [L, T] indexes it at segment-end steps
    (sentinel ``n_init`` = the appended empty row); ``state`` should
    then be ``PackedLanes.lane_state0()``. Segment boundaries are
    tb-aligned, so the between-block flush/reset needs only the
    block-final column of ``reset_row``. ``bt`` is the widest lane
    tile; a wide state gets a narrower one (``fit_tile``).
    Returns (final_lane_state, out).
    """
    if interpret is None:
        interpret = not on_tpu()
    events_teb = jnp.asarray(events_teb)
    narrow = events_teb.dtype == jnp.int16
    if narrow and base is None:
        raise ValueError("int16 events need their affine base vector")
    T, ev_n, L = events_teb.shape
    if T % tb:
        raise ValueError(f"packed scan length {T} not a multiple of tb={tb}")
    try:  # concrete inputs only — tracers skip the host-side check
        seg_np = np.asarray(seg_end)
    except Exception:
        seg_np = None
    if seg_np is not None:
        interior = seg_np.reshape(L, T // tb, tb)[:, :, : tb - 1]
        if interior.any():
            raise ValueError(
                "segment boundaries must be tb-aligned for the Pallas "
                "packed path — pack with pack_lanes(seg_align=tb)"
            )
    rm = RowMap(caps)
    bt, _ = fit_tile(caps, bt, tb, ev_n * events_teb.dtype.itemsize)
    b_pad = (-L) % bt
    span = TRACER.current()
    if span is not None:  # event cells the kernel streams, padding in
        span.set_tag("cells", (L + b_pad) * T)
        span.set_tag("state_rows", rm.rows_padded)
    if init is not None and reset_row is None:
        raise ValueError("init requires reset_row")
    # base normalized to a concrete vector: the kernel only reads it on
    # the narrow path, and zeros reproduce the None default bit-for-bit
    base_arr = (np.zeros((ev_n,), np.int32) if base is None
                else base)
    args = (state, out0, events_teb, seg_end, out_row, base_arr, init,
            reset_row)
    statics = dict(caps=caps, tb=tb, bt=bt, interpret=interpret,
                   wide_cols=tuple(wide_cols))
    leaves, tree = jax.tree_util.tree_flatten(args)
    if interpret and not any(isinstance(a, jax.core.Tracer)
                             for a in leaves):
        exe = _interp_packed_exec(
            tuple(sorted(statics.items())), tree,
            tuple(_avkey(jnp.asarray(a)) for a in leaves))
        return exe(*args)
    return _packed_replay(*args, **statics)


@functools.partial(jax.jit,
                   static_argnames=("caps", "tb", "bt", "interpret",
                                    "wide_cols"))
def _packed_replay(state, out0, events_teb, seg_end, out_row, base, init,
                   reset_row, *, caps, tb, bt, interpret, wide_cols):
    """The device half of ``replay_scan_pallas_packed`` as one program a
    shape: the lane padding to whole tiles, the row layouts, the block
    scan and the unpacking. Run eagerly, each of its ~45 small
    operations would be an executable of its own: a backend compile
    apiece for every new shape of a storm, a dispatch apiece on every
    launch."""
    rm = RowMap(caps)
    T, ev_n, L = events_teb.shape
    b_pad = (-L) % bt
    base = jnp.asarray(base, jnp.int32)
    # one empty_state column: the padding lanes' state and the reset
    # template
    empty_col = state_to_rows(S.empty_state(1, caps), rm)
    rows0 = state_to_rows(state, rm)
    seg_end = jnp.asarray(seg_end)
    out_row = jnp.asarray(out_row, jnp.int32)
    if b_pad:
        # padding lanes read EV_TYPE == -1 (through the base on the
        # narrow stream: EV_TYPE is never a wide column, so it is the
        # first physical column)
        pad_type = (-1 - base[S.EV_TYPE]).astype(events_teb.dtype)
        fill = jnp.zeros((T, ev_n, b_pad), events_teb.dtype)
        fill = fill.at[:, S.EV_TYPE, :].set(pad_type)
        events_teb = jnp.concatenate([events_teb, fill], axis=2)
        rows0 = jnp.concatenate(
            [rows0, jnp.broadcast_to(empty_col, (rm.rows_padded, b_pad))],
            axis=1)
        seg_end = jnp.concatenate(
            [seg_end, jnp.zeros((b_pad, T), seg_end.dtype)], axis=0)
        out_row = jnp.concatenate(
            [out_row, jnp.zeros((b_pad, T), jnp.int32)], axis=0)
    lb = L + b_pad
    nb = T // tb
    out_rows0 = state_to_rows(out0, rm)
    if init is None:
        # single empty template column; every reset gathers column 0
        init_rows = empty_col
        reset_b = jnp.zeros((nb, lb), jnp.int32)
    else:
        n_init = init.exec_info.shape[0]
        init_rows = jnp.concatenate([state_to_rows(init, rm), empty_col],
                                    axis=1)
        rr = jnp.asarray(reset_row, jnp.int32)
        if b_pad:
            rr = jnp.concatenate(
                [rr, jnp.full((b_pad, T), n_init, jnp.int32)], axis=0)
        reset_b = jnp.transpose(rr[:, tb - 1 :: tb])  # [nb, lb]
    ev_blocks = events_teb.reshape(nb, tb, ev_n, lb)
    seg_b = jnp.transpose(seg_end[:, tb - 1 :: tb])  # [nb, lb]
    row_b = jnp.transpose(out_row[:, tb - 1 :: tb])
    n_out = out_rows0.shape[1]

    def body(carry, xs):
        rows, out = carry
        evb, seg, orow, rrow = xs
        rows = _replay_rows_pallas(
            evb, rows, caps, tb, interpret, bt, base=base,
            wide_cols=wide_cols,
        )

        def flush(args):
            rows, out = args
            idx = jnp.where(seg, orow, n_out)
            out = out.at[:, idx].set(rows, mode="drop")
            rows = jnp.where(seg[None, :], init_rows[:, rrow], rows)
            return rows, out

        rows, out = lax.cond(
            jnp.any(seg), flush, lambda args: args, (rows, out)
        )
        return (rows, out), None

    (rows, out), _ = lax.scan(
        body, (rows0, out_rows0), (ev_blocks, seg_b, row_b, reset_b)
    )
    return rows_to_state(rows[:, :L], rm), rows_to_state(out, rm)


@functools.lru_cache(maxsize=64)
def _interp_packed_exec(statics, tree, avkey):
    """Concrete interpret-mode calls (tests, CPU serving) compile once a
    shape at XLA opt level 0, as ``_interp_rows_exec`` does."""
    avals = jax.tree_util.tree_unflatten(
        tree, [jax.ShapeDtypeStruct(k[0], k[1]) for k in avkey])
    low = _packed_replay.lower(*avals, **dict(statics))
    return low.compile({"xla_backend_optimization_level": 0})


def replay_scan_pallas(
    state: S.StateTensors,
    events_tm,
    caps: S.Capacities,
    tb: int = 16,
    interpret: bool | None = None,
    bt: int = BT,
) -> S.StateTensors:
    """Drop-in equivalent of ops.replay.replay_scan on the Pallas kernel.

    events_tm: [T, B, EV_N] (the packer's time-major layout). Transposes
    on device to the kernel's field-major layout — callers that can pack
    field-major directly should use ``replay_scan_pallas_teb`` and skip
    that cost.
    """
    events_teb = jnp.transpose(jnp.asarray(events_tm), (0, 2, 1))
    return replay_scan_pallas_teb(
        state, events_teb, caps, tb=tb, interpret=interpret, bt=bt,
    )

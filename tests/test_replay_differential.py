"""Differential tests: TPU replay kernel vs host oracle, field for field.

The contract (SURVEY.md §7.2): pack histories → scan on device → unpack →
identical canonical snapshot to replaying the same batches through
``StateBuilder.apply_events`` host-side.
"""

import functools

import numpy as np
import pytest

from cadence_tpu.core import history_factory as F
from cadence_tpu.core.enums import ParentClosePolicy, TimeoutType
from cadence_tpu.core.mutable_state import MutableState, SECOND
from cadence_tpu.core.state_builder import StateBuilder
from cadence_tpu.core.version_history import VersionHistories
from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import (
    PackOverflowError,
    pack_histories,
    pack_lanes,
    pack_workflow,
    round_scan_len,
)
from cadence_tpu.ops.replay import replay_packed, type_signature
from cadence_tpu.ops.unpack import (
    mutable_state_to_snapshot,
    split_lane_snapshots,
    state_row_to_snapshot,
)

T0 = 1_700_000_000 * SECOND
V = 10


def oracle_replay(batches, domain_id="dom", workflow_id="wf", run_id="run"):
    ms = MutableState(domain_id=domain_id)
    ms.version_histories = VersionHistories.new_empty()
    sb = StateBuilder(ms, id_generator=lambda: "fixed")
    for batch in batches:
        new_run = None
        sb.apply_events(domain_id, "req", workflow_id, run_id, list(batch), new_run)
    return ms


KERNELS = ["scan", "pallas_teb", "pallas_packed"]

# the Pallas kernels run interpreted, every batch padded to one scan
# length and one tile, so that each traces once
PALLAS_CAPS = S.Capacities(max_events=32)
PALLAS_BT, PALLAS_TB = 1024, 8


def _pallas_teb(histories):
    """The Pallas teb kernel on the operands ``_replay_histories``
    builds on a TPU: the packer's batch-major rows laid out by
    ``teb_of_rows``, and the host's presence masks."""
    import jax
    import jax.numpy as jnp

    from cadence_tpu.ops.replay import teb_of_rows
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_teb

    packed = pack_histories(histories, caps=PALLAS_CAPS,
                            pad_batch_to=PALLAS_BT)
    presence = packed.presence(PALLAS_BT)
    assert presence is not None
    final = replay_scan_pallas_teb(
        jax.tree_util.tree_map(
            jnp.asarray, S.empty_state(packed.batch, PALLAS_CAPS)),
        teb_of_rows(jnp.asarray(packed.events.reshape(packed.batch, -1))),
        PALLAS_CAPS, tb=PALLAS_TB, interpret=True, bt=PALLAS_BT,
        presence=presence)
    final = jax.tree_util.tree_map(np.asarray, final)
    return [state_row_to_snapshot(final, i, packed.epoch_s)
            for i in range(len(histories))]


def _pallas_packed(histories, resume=None):
    """The Pallas packed kernel on ``pack_lanes(..., seg_align=tb)``,
    the lanes' time axis padded with invalid steps to the one scan
    length."""
    import jax
    import jax.numpy as jnp

    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    lanes = pack_lanes(histories, caps=PALLAS_CAPS, seg_align=PALLAS_TB)
    T, L = PALLAS_CAPS.max_events, lanes.lanes
    t = lanes.scan_len
    assert t <= T
    events = np.zeros((T, S.EV_N, L), np.int32)
    events[:, S.EV_TYPE] = -1
    events[:t] = lanes.teb()
    seg_end = np.zeros((L, T), bool)
    seg_end[:, :t] = lanes.seg_end
    out_row = np.zeros((L, T), np.int32)
    out_row[:, :t] = lanes.out_row
    on_device = functools.partial(jax.tree_util.tree_map, jnp.asarray)
    _, out = replay_scan_pallas_packed(
        on_device(S.empty_state(L, PALLAS_CAPS)),
        on_device(S.empty_state(round_scan_len(len(histories)),
                                PALLAS_CAPS)),
        jnp.asarray(events), jnp.asarray(seg_end), jnp.asarray(out_row),
        PALLAS_CAPS, tb=PALLAS_TB, interpret=True, bt=PALLAS_BT)
    return split_lane_snapshots(
        lanes, jax.tree_util.tree_map(np.asarray, out))


def kernel_snapshots(histories, kernel="scan"):
    """Each history's snapshot after a replay through ``kernel``:
    ``scan`` is ``replay_packed`` off the TPU (the XLA scan), the two
    Pallas kernels are what the TPU runs."""
    if kernel == "pallas_teb":
        return _pallas_teb(histories)
    if kernel == "pallas_packed":
        return _pallas_packed(histories)
    packed = pack_histories(histories)
    final = replay_packed(packed)
    return [state_row_to_snapshot(final, i, packed.epoch_s)
            for i in range(len(histories))]


def assert_parity(batches_per_workflow, kernel="scan"):
    """Replay every workflow both ways and compare snapshots."""
    histories = [
        (f"wf-{i}", f"run-{i}", batches)
        for i, batches in enumerate(batches_per_workflow)
    ]
    got = kernel_snapshots(histories, kernel)
    for i, (_, _, batches) in enumerate(histories):
        kernel_snap = got[i]
        oracle_snap = mutable_state_to_snapshot(
            oracle_replay(batches, workflow_id=f"wf-{i}", run_id=f"run-{i}")
        )
        assert kernel_snap == oracle_snap, (
            f"workflow {i} diverged on {kernel}:\nkernel={kernel_snap}"
            f"\noracle={oracle_snap}"
        )


def echo_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t, task_list="tl", workflow_type="echo")],
        [F.decision_task_scheduled(2, V, t + SECOND)],
        [F.decision_task_started(3, V, t + 2 * SECOND, scheduled_event_id=2)],
        [
            F.decision_task_completed(4, V, t + 3 * SECOND, scheduled_event_id=2,
                                      started_event_id=3),
            F.activity_task_scheduled(5, V, t + 3 * SECOND, activity_id="a1",
                                      heartbeat_timeout_seconds=3),
        ],
        [F.activity_task_started(6, V, t + 4 * SECOND, scheduled_event_id=5)],
        [F.activity_task_completed(7, V, t + 5 * SECOND, scheduled_event_id=5,
                                   started_event_id=6),
         F.decision_task_scheduled(8, V, t + 5 * SECOND)],
        [F.decision_task_started(9, V, t + 6 * SECOND, scheduled_event_id=8)],
        [
            F.decision_task_completed(10, V, t + 7 * SECOND, scheduled_event_id=8,
                                      started_event_id=9),
            F.workflow_execution_completed(11, V, t + 7 * SECOND,
                                           decision_task_completed_event_id=10),
        ],
    ]


def timer_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_started(3, V, t, scheduled_event_id=2)],
        [
            F.decision_task_completed(4, V, t + SECOND, scheduled_event_id=2,
                                      started_event_id=3),
            F.timer_started(5, V, t + SECOND, timer_id="t1",
                            start_to_fire_timeout_seconds=30),
            F.timer_started(6, V, t + SECOND, timer_id="t2",
                            start_to_fire_timeout_seconds=10),
        ],
        [F.timer_fired(7, V, t + 11 * SECOND, timer_id="t2", started_event_id=6),
         F.decision_task_scheduled(8, V, t + 11 * SECOND)],
        [F.decision_task_started(9, V, t + 12 * SECOND, scheduled_event_id=8)],
        [
            F.decision_task_completed(10, V, t + 13 * SECOND, scheduled_event_id=8,
                                      started_event_id=9),
            F.timer_canceled(11, V, t + 13 * SECOND, timer_id="t1",
                             started_event_id=5,
                             decision_task_completed_event_id=10),
        ],
    ]


def signal_cancel_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.workflow_execution_signaled(2, V, t + SECOND, signal_name="s1")],
        [F.workflow_execution_signaled(3, V, t + SECOND, signal_name="s2")],
        [F.workflow_execution_cancel_requested(4, V, t + 2 * SECOND)],
        [F.decision_task_scheduled(5, V, t + 2 * SECOND)],
        [F.decision_task_started(6, V, t + 3 * SECOND, scheduled_event_id=5)],
        [
            F.decision_task_completed(7, V, t + 4 * SECOND, scheduled_event_id=5,
                                      started_event_id=6),
            F.workflow_execution_canceled(8, V, t + 4 * SECOND,
                                          decision_task_completed_event_id=7),
        ],
    ]


def decision_failure_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_started(3, V, t + SECOND, scheduled_event_id=2)],
        [F.decision_task_timed_out(4, V, t + 20 * SECOND, scheduled_event_id=2,
                                   started_event_id=3)],
        # transient decision now pending (attempt=1, schedule_id from batch)
        [F.decision_task_scheduled(5, V, t + 21 * SECOND, attempt=1)],
        [F.decision_task_started(6, V, t + 22 * SECOND, scheduled_event_id=5)],
        [F.decision_task_failed(7, V, t + 23 * SECOND, scheduled_event_id=5,
                                started_event_id=6)],
    ]


def sticky_timeout_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_timed_out(
            3, V, t + 5 * SECOND, scheduled_event_id=2,
            timeout_type=TimeoutType.ScheduleToStart)],
    ]


def child_external_batches(t=T0):
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_started(3, V, t, scheduled_event_id=2)],
        [
            F.decision_task_completed(4, V, t + SECOND, scheduled_event_id=2,
                                      started_event_id=3),
            F.start_child_initiated(5, V, t + SECOND, domain="dom",
                                    workflow_id="child-1",
                                    parent_close_policy=ParentClosePolicy.RequestCancel,
                                    decision_task_completed_event_id=4),
            F.request_cancel_external_initiated(6, V, t + SECOND, domain="dom",
                                                workflow_id="other-wf",
                                                decision_task_completed_event_id=4),
            F.signal_external_initiated(7, V, t + SECOND, domain="dom",
                                        workflow_id="other-wf",
                                        decision_task_completed_event_id=4),
        ],
        [F.child_execution_started(8, V, t + 2 * SECOND, initiated_event_id=5,
                                   workflow_id="child-1", run_id="crun-1")],
        [F.external_workflow_execution_cancel_requested(
            9, V, t + 2 * SECOND, initiated_event_id=6)],
        [F.external_workflow_execution_signaled(
            10, V, t + 3 * SECOND, initiated_event_id=7)],
        [F.child_execution_completed(11, V, t + 4 * SECOND, initiated_event_id=5,
                                     started_event_id=8)],
        # second decision fans out three more children + one external
        # cancel so every child-close kind (failed / timed-out /
        # terminated) and the failed-cancel resolution are on the
        # transition surface the static checker says the kernel handles
        [F.decision_task_scheduled(12, V, t + 5 * SECOND)],
        [F.decision_task_started(13, V, t + 5 * SECOND, scheduled_event_id=12)],
        [
            F.decision_task_completed(14, V, t + 6 * SECOND, scheduled_event_id=12,
                                      started_event_id=13),
            F.start_child_initiated(15, V, t + 6 * SECOND, domain="dom",
                                    workflow_id="child-2",
                                    decision_task_completed_event_id=14),
            F.start_child_initiated(16, V, t + 6 * SECOND, domain="dom",
                                    workflow_id="child-3",
                                    decision_task_completed_event_id=14),
            F.start_child_initiated(17, V, t + 6 * SECOND, domain="dom",
                                    workflow_id="child-4",
                                    decision_task_completed_event_id=14),
            F.request_cancel_external_initiated(18, V, t + 6 * SECOND,
                                                domain="dom",
                                                workflow_id="gone-wf",
                                                decision_task_completed_event_id=14),
        ],
        [F.child_execution_started(19, V, t + 7 * SECOND, initiated_event_id=15,
                                   workflow_id="child-2", run_id="crun-2")],
        [F.child_execution_failed(20, V, t + 8 * SECOND, initiated_event_id=15,
                                  started_event_id=19)],
        [F.child_execution_started(21, V, t + 8 * SECOND, initiated_event_id=16,
                                   workflow_id="child-3", run_id="crun-3")],
        [F.child_execution_timed_out(22, V, t + 9 * SECOND, initiated_event_id=16,
                                     started_event_id=21)],
        [F.child_execution_started(23, V, t + 9 * SECOND, initiated_event_id=17,
                                   workflow_id="child-4", run_id="crun-4")],
        [F.child_execution_terminated(24, V, t + 10 * SECOND, initiated_event_id=17,
                                      started_event_id=23)],
        [F.request_cancel_external_failed(25, V, t + 10 * SECOND,
                                          initiated_event_id=18)],
    ]


def continued_as_new_batches(t=T0):
    """First run of a continued-as-new chain. NOT in ALL_SCENARIOS:
    the oracle needs the new run's history threaded through
    apply_events, which the shared assert_parity helper doesn't do —
    TestTransitionCoverage replays it through its own parity check."""
    return [
        [F.workflow_execution_started(1, V, t, task_list="tl",
                                      workflow_type="loop")],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_started(3, V, t + SECOND, scheduled_event_id=2)],
        [
            F.decision_task_completed(4, V, t + 2 * SECOND,
                                      scheduled_event_id=2,
                                      started_event_id=3),
            F.workflow_execution_continued_as_new(
                5, V, t + 2 * SECOND, new_execution_run_id="run-next",
                decision_task_completed_event_id=4),
        ],
    ]


def activity_storm_batches(t=T0):
    """Interleaved activity lifecycles incl. cancel-request and timeout."""
    return [
        [F.workflow_execution_started(1, V, t)],
        [F.decision_task_scheduled(2, V, t)],
        [F.decision_task_started(3, V, t, scheduled_event_id=2)],
        [
            F.decision_task_completed(4, V, t, scheduled_event_id=2,
                                      started_event_id=3),
            F.activity_task_scheduled(5, V, t, activity_id="a1"),
            F.activity_task_scheduled(6, V, t, activity_id="a2",
                                      schedule_to_start_timeout_seconds=5),
            F.activity_task_scheduled(7, V, t, activity_id="a3",
                                      heartbeat_timeout_seconds=2),
            F.activity_task_cancel_requested(8, V, t, activity_id="a2",
                                             decision_task_completed_event_id=4),
        ],
        [F.activity_task_started(9, V, t + SECOND, scheduled_event_id=5)],
        [F.activity_task_started(10, V, t + SECOND, scheduled_event_id=7)],
        [F.activity_task_failed(11, V, t + 2 * SECOND, scheduled_event_id=5,
                                started_event_id=9, reason="boom")],
        [F.activity_task_timed_out(12, V, t + 6 * SECOND, scheduled_event_id=6,
                                   started_event_id=-23,
                                   timeout_type=TimeoutType.ScheduleToStart)],
        [F.activity_task_canceled(13, V, t + 6 * SECOND, scheduled_event_id=7,
                                  started_event_id=10)],
        # a1 slot is free again: schedule a new activity reusing the id
        [F.decision_task_scheduled(14, V, t + 6 * SECOND)],
        [F.decision_task_started(15, V, t + 7 * SECOND, scheduled_event_id=14)],
        [
            F.decision_task_completed(16, V, t + 8 * SECOND, scheduled_event_id=14,
                                      started_event_id=15),
            F.activity_task_scheduled(17, V, t + 8 * SECOND, activity_id="a1"),
        ],
    ]


def version_bump_batches(t=T0):
    """Failover mid-history: version changes across batches (NDC)."""
    return [
        [F.workflow_execution_started(1, 10, t)],
        [F.decision_task_scheduled(2, 10, t)],
        [F.decision_task_started(3, 10, t, scheduled_event_id=2)],
        [F.decision_task_timed_out(4, 21, t + 30 * SECOND, scheduled_event_id=2,
                                   started_event_id=3)],
        [F.decision_task_scheduled(5, 21, t + 31 * SECOND, attempt=1)],
        [F.decision_task_started(6, 21, t + 32 * SECOND, scheduled_event_id=5)],
        [
            F.decision_task_completed(7, 21, t + 33 * SECOND, scheduled_event_id=5,
                                      started_event_id=6),
            F.workflow_execution_completed(8, 21, t + 33 * SECOND,
                                           decision_task_completed_event_id=7),
        ],
    ]


ALL_SCENARIOS = [
    echo_batches,
    timer_batches,
    signal_cancel_batches,
    decision_failure_batches,
    sticky_timeout_batches,
    child_external_batches,
    activity_storm_batches,
    version_bump_batches,
]


class TestKernelOracleParity:
    @pytest.mark.parametrize("kernel", KERNELS)
    @pytest.mark.parametrize("scenario", ALL_SCENARIOS, ids=lambda f: f.__name__)
    def test_single(self, scenario, kernel):
        assert_parity([scenario()], kernel)

    @pytest.mark.parametrize("kernel", KERNELS)
    def test_mixed_batch(self, kernel):
        """All scenarios in one padded, ragged device batch."""
        assert_parity([fn() for fn in ALL_SCENARIOS], kernel)

    def test_batch_padding(self):
        histories = [("wf", "run", echo_batches())]
        packed = pack_histories(histories, pad_batch_to=8)
        assert packed.batch == 8
        final = replay_packed(packed)
        snap = state_row_to_snapshot(final, 0, packed.epoch_s)
        assert snap == mutable_state_to_snapshot(oracle_replay(echo_batches()))
        # padded rows stay pristine
        pad = state_row_to_snapshot(final, 7, packed.epoch_s)
        assert pad["activities"] == {} and pad["version_history"] == []
        assert pad["exec"]["state"] == 0


class TestLanePacking:
    """Ragged lane packing (ops/pack.pack_lanes): K whole histories
    back-to-back per scan lane must be byte-identical to replaying each
    history in its own lane, and to the host oracle."""

    CAPS = S.Capacities(max_events=64)

    def _fuzz(self, n, seed=11):
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        fz = HistoryFuzzer(seed=seed, caps=self.CAPS)
        return [
            (f"wf-{i}", f"run-{i}",
             fz.generate(target_events=6 + (i * 7) % 40))
            for i in range(n)
        ]

    @pytest.mark.parametrize("seg_align", [1, 8])
    def test_fuzzed_lane_packed_matches_unpacked_and_oracle(self, seg_align):
        hs = self._fuzz(17)
        lanes = pack_lanes(
            hs, caps=self.CAPS, target_lane_len=96, seg_align=seg_align
        )
        assert lanes.lanes < len(hs), "packer must share lanes"
        final = replay_packed(lanes)

        ref = replay_packed(pack_histories(hs, caps=self.CAPS))
        # byte identity, field for field, history for history
        for name in ("exec_info", "activities", "timers", "children",
                     "cancels", "signals", "vh_items", "vh_len"):
            np.testing.assert_array_equal(
                np.asarray(getattr(final, name))[: len(hs)],
                np.asarray(getattr(ref, name))[: len(hs)],
                err_msg=f"lane-packed {name} != per-lane replay "
                        f"(seg_align={seg_align})",
            )
        # and the host oracle, via the lane segment side tables
        snaps = split_lane_snapshots(lanes, final)
        for i, (wf, run, batches) in enumerate(hs):
            oracle = mutable_state_to_snapshot(
                oracle_replay(batches, workflow_id=wf, run_id=run)
            )
            assert snaps[i] == oracle, f"history {i} diverged from oracle"

    def test_scenarios_lane_packed(self):
        hs = [
            (f"wf-{i}", f"run-{i}", fn())
            for i, fn in enumerate(ALL_SCENARIOS)
        ]
        lanes = pack_lanes(hs, target_lane_len=128)
        final = replay_packed(lanes)
        for i, (wf, run, batches) in enumerate(hs):
            got = state_row_to_snapshot(final, i, lanes.epoch_s)
            want = mutable_state_to_snapshot(
                oracle_replay(batches, workflow_id=wf, run_id=run)
            )
            assert got == want, ALL_SCENARIOS[i].__name__

    def test_type_specialized_scan_is_bit_identical(self):
        """The static type-set specialization must not change results."""
        from cadence_tpu.ops.replay import replay_packed_lanes

        hs = self._fuzz(9, seed=4)
        lanes = pack_lanes(hs, caps=self.CAPS, target_lane_len=96)
        spec = replay_packed_lanes(lanes, specialize=True)
        full = replay_packed_lanes(lanes, specialize=False)
        for name in ("exec_info", "activities", "timers", "children",
                     "cancels", "signals", "vh_items", "vh_len"):
            np.testing.assert_array_equal(
                np.asarray(getattr(spec, name)),
                np.asarray(getattr(full, name)),
                err_msg=f"type specialization changed {name}",
            )
        # the signature covers every present type that drives a
        # transition block (pass-through types — markers, upserts — have
        # no block to gate and may drop out)
        from cadence_tpu.ops.replay import _type_groups

        grouped = {int(t) for g in _type_groups() for t in g}
        sig = set(type_signature(lanes.present_types))
        assert (set(lanes.present_types) & grouped) <= sig

    def test_one_history_per_lane_fallback(self):
        """When no two histories fit a lane (target below any pair sum),
        packing degenerates to pack_histories density: one history per
        lane — the lane capacity never stretches past the longest
        single history."""
        hs = [
            (f"wf-{i}", f"run-{i}", timer_batches())
            for i in range(5)
        ]
        lanes = pack_lanes(hs, caps=self.CAPS, target_lane_len=1)
        assert lanes.n_histories == 5
        assert all(len(segs) <= 1 for segs in lanes.lane_segments)
        final = replay_packed(lanes)
        for i, (wf, run, batches) in enumerate(hs):
            got = state_row_to_snapshot(final, i, lanes.epoch_s)
            want = mutable_state_to_snapshot(
                oracle_replay(batches, workflow_id=wf, run_id=run)
            )
            assert got == want

    def test_round_scan_len_grid(self):
        assert [round_scan_len(n) for n in (1, 8, 9, 13, 17, 25, 769, 1000)] \
            == [8, 8, 12, 16, 24, 32, 1024, 1024]
        # monotone, bounded overhead (adjacent grid ratio ≤ 1.5)
        for n in range(1, 3000, 37):
            g = round_scan_len(n)
            assert g >= n and (n <= 8 or g < n * 1.5)


class TestPackValidation:
    def test_overflow_raises(self):
        t = T0
        caps = S.Capacities(max_activities=2)
        batches = [
            [F.workflow_execution_started(1, V, t)],
            [
                F.activity_task_scheduled(2, V, t, activity_id="a1"),
                F.activity_task_scheduled(3, V, t, activity_id="a2"),
                F.activity_task_scheduled(4, V, t, activity_id="a3"),
            ],
        ]
        with pytest.raises(PackOverflowError):
            pack_workflow(batches, caps)

    def test_orphan_event_raises(self):
        t = T0
        batches = [
            [F.workflow_execution_started(1, V, t)],
            [F.activity_task_completed(2, V, t, scheduled_event_id=99,
                                       started_event_id=98)],
        ]
        with pytest.raises(Exception):
            pack_workflow(batches, S.Capacities())

    def test_slot_reuse_is_deterministic(self):
        t = T0
        batches = [
            [F.workflow_execution_started(1, V, t)],
            [F.activity_task_scheduled(2, V, t, activity_id="a1"),
             F.activity_task_scheduled(3, V, t, activity_id="a2")],
            [F.activity_task_completed(4, V, t, scheduled_event_id=2,
                                       started_event_id=-23)],
            [F.activity_task_scheduled(5, V, t, activity_id="a3")],
        ]
        arr, side = pack_workflow(batches, S.Capacities())
        # a3 reuses slot 0 (lowest free)
        assert side.activity_ids == {0: "a3", 1: "a2"}


class TestTransitionCoverage:
    """Close the loop between the static transition surface
    (cadence_tpu/analysis --emit-matrix) and the dynamic suites: every
    event type the kernel claims to handle must actually occur in the
    histories these tests generate, or the differential fuzz only
    *samples* the surface the checker *covers*."""

    @pytest.mark.parametrize("kernel", KERNELS)
    def test_continued_as_new_parity(self, kernel):
        """CaN is kernel-handled but needs new-run history on the
        oracle side, so it gets its own parity check (the shared
        assert_parity helper can't thread the new run through)."""
        batches = continued_as_new_batches()
        ms = MutableState(domain_id="dom")
        ms.version_histories = VersionHistories.new_empty()
        sb = StateBuilder(ms, id_generator=lambda: "fixed")
        new_run = [F.workflow_execution_started(
            1, V, T0 + 2 * SECOND, task_list="tl", workflow_type="loop")]
        for batch in batches[:-1]:
            sb.apply_events("dom", "req", "wf-can", "run-can", list(batch))
        sb.apply_events(
            "dom", "req", "wf-can", "run-can", list(batches[-1]), new_run
        )
        (got,) = kernel_snapshots([("wf-can", "run-can", batches)], kernel)
        want = mutable_state_to_snapshot(ms)
        assert got == want

    def test_generated_mix_covers_kernel_surface(self):
        from cadence_tpu.analysis.transition_surface import (
            kernel_handled_types,
        )
        from cadence_tpu.core.enums import EventType
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        seen = set()
        for seed in (1, 2, 3):
            fz = HistoryFuzzer(seed=seed)
            for i in range(25):
                for batch in fz.generate(target_events=10 + (i * 7) % 50):
                    for ev in batch:
                        seen.add(int(ev.event_type))
        for fn in ALL_SCENARIOS + [continued_as_new_batches]:
            for batch in fn():
                for ev in batch:
                    seen.add(int(ev.event_type))
        handled = kernel_handled_types()
        missing = sorted(EventType(t).name for t in handled - seen)
        assert not missing, (
            "kernel-handled event types never generated by the "
            f"differential suites: {missing} — extend the fuzzer or a "
            "scenario so the dynamic tests exercise the whole surface"
        )

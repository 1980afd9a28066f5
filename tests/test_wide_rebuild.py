"""Fan-out histories wider than the default slot tables rebuild on the
device: ``rebuild_many`` measures each history's slot-table peaks,
buckets it by width and depth (``ops.dispatch.buckets``) and replays
each bucket at its own ``Capacities``, on every kernel path, with the
host oracle's answers and no host fallback.
"""

from __future__ import annotations

import dataclasses
import random

import numpy as np
import pytest

from benchmark import compare as C
from cadence_tpu.checkpoint import CheckpointManager, MemoryCheckpointStore
from cadence_tpu.core import history_factory as F
from cadence_tpu.core.enums import EventType
from cadence_tpu.ops import schema as S
from cadence_tpu.ops.dispatch import buckets, depth_buckets, history_depth
from cadence_tpu.ops.grid import round_scan_len
from cadence_tpu.ops.pack import (
    SLOT_TABLES,
    WIDEST,
    PackOverflowError,
    bucket_caps,
    pack_workflow,
    slot_peaks,
)
from cadence_tpu.ops.replay_pallas import RowMap, fit_tile, presence_words
from cadence_tpu.runtime.persistence.memory import create_memory_bundle
from cadence_tpu.runtime.replication.rebuilder import (
    RebuildRequest,
    StateRebuilder,
)
from cadence_tpu.testing.event_generator import HistoryFuzzer
from cadence_tpu.utils.metrics import Scope
from cadence_tpu.utils.tracing import TRACER

SECOND = 1_000_000_000
T0 = 1_700_000_000 * SECOND


class _Ids:
    def __init__(self) -> None:
        self.eid = 0
        self.t = T0

    def next(self) -> int:
        self.eid += 1
        return self.eid

    def tick(self) -> int:
        self.t += SECOND
        return self.t


def _decision(ids, v=1):
    sch = ids.next()
    out = [[F.decision_task_scheduled(sch, v, ids.t)]]
    sta = ids.next()
    out.append([F.decision_task_started(sta, v, ids.tick(),
                                        scheduled_event_id=sch)])
    out.append([F.decision_task_completed(
        ids.next(), v, ids.tick(), scheduled_event_id=sch,
        started_event_id=sta)])
    return out


def fanout(kind: str, width: int, pending: int, seed: int = 0):
    """A parent whose one decision opens ``width`` activities, timers or
    children in one batch; all but ``pending`` of them close, in a
    seeded order, with a fan-in decision every few closes."""
    rng = random.Random(f"{kind}:{width}:{seed}")
    ids = _Ids()
    out = [[F.workflow_execution_started(
        ids.next(), 1, ids.t, task_list="tl", workflow_type="fan",
        execution_start_to_close_timeout_seconds=3600,
        task_start_to_close_timeout_seconds=10)]]
    out += _decision(ids)
    dtc = out[-1][0].event_id
    opened = []
    for k in range(width):
        eid = ids.next()
        opened.append(eid)
        if kind == "activity":
            ev = F.activity_task_scheduled(
                eid, 1, ids.t, activity_id=f"a{k}",
                decision_task_completed_event_id=dtc)
        elif kind == "timer":
            ev = F.timer_started(
                eid, 1, ids.t, timer_id=f"t{k}",
                start_to_fire_timeout_seconds=60 + k,
                decision_task_completed_event_id=dtc)
        else:
            ev = F.start_child_initiated(
                eid, 1, ids.t, domain="dom", workflow_id=f"c{k}",
                decision_task_completed_event_id=dtc)
        out[-1].append(ev)
    order = list(range(width))
    rng.shuffle(order)
    for n, k in enumerate(order[: width - pending], start=1):
        if kind == "activity":
            sta = ids.next()
            out.append([F.activity_task_started(
                sta, 1, ids.tick(), scheduled_event_id=opened[k])])
            out.append([F.activity_task_completed(
                ids.next(), 1, ids.tick(), scheduled_event_id=opened[k],
                started_event_id=sta)])
        elif kind == "timer":
            out.append([F.timer_fired(
                ids.next(), 1, ids.tick(), timer_id=f"t{k}",
                started_event_id=opened[k])])
        else:
            sta = ids.next()
            out.append([F.child_execution_started(
                sta, 1, ids.tick(), initiated_event_id=opened[k],
                domain="dom", workflow_id=f"c{k}", run_id=f"cr{k}")])
            out.append([F.child_execution_completed(
                ids.next(), 1, ids.tick(), initiated_event_id=opened[k],
                started_event_id=sta)])
        if n % 5 == 0:
            out += _decision(ids)
    if kind == "activity":  # a few of the pending ones have started
        for k in order[width - pending:][: pending // 2]:
            out.append([F.activity_task_started(
                ids.next(), 1, ids.tick(), scheduled_event_id=opened[k])])
    return out


# (kind, width, pending): 33, 40 and 70 pending activities, 17-24
# pending timers and children — each wider than its default table
WIDE = [("activity", 36, 33), ("activity", 52, 40), ("activity", 70, 70),
        ("timer", 24, 17), ("timer", 30, 24),
        ("child", 20, 17), ("child", 24, 24)]


@pytest.fixture(autouse=True)
def _quiet_tracer():
    TRACER.configure(sample_rate=0.0)
    TRACER.clear()
    yield
    TRACER.clear()


@pytest.fixture()
def store():
    bundle = create_memory_bundle()
    yield bundle.history
    bundle.close()


def _request(history, i, batches):
    branch = history.new_history_branch(tree_id=f"run-{i}")
    for txn, batch in enumerate(batches, start=1):
        history.append_history_nodes(branch, batch, transaction_id=txn)
    return RebuildRequest(domain_id="dom", workflow_id=f"wf-{i}",
                          run_id=f"run-{i}",
                          branch_token=branch.to_json().encode())


def _canon(ms, transfer, timer):
    """Field by field, as the benchmark's comparison reads a rebuild."""
    return C.canon_state(ms), C.canon_tasks(transfer, timer)


def _counting(rb):
    """Count the requests the rebuilder hands to its host oracle."""
    host = rb.rebuild
    calls = []

    def counted(req):
        calls.append(req.run_id)
        return host(req)

    rb.rebuild = counted
    return calls, host


@pytest.fixture(params=["xla", "pallas"])
def kernel_path(request):
    """rebuild_many's dispatcher on the CPU: the XLA packed scan, or the
    Pallas packed kernel (its TPU branch) in interpret mode at small
    tiles."""
    if request.param == "pallas":
        request.getfixturevalue("tpu_branch_on_cpu")
    return request.param


@pytest.mark.parametrize("kind,width,pending", WIDE)
def test_wide_fanout_rebuilds_on_device_like_the_host(
        store, kernel_path, kind, width, pending):
    batches = fanout(kind, width, pending)
    reqs = [_request(store, 0, batches),
            _request(store, 1, fanout(kind, width, pending, seed=1))]
    rb = StateRebuilder(store, lane_len=128)
    calls, host = _counting(rb)
    with TRACER.trace("caller", sampled=True) as root:
        out = rb.rebuild_many(reqs)
    assert calls == []  # no host fallback
    for got, r in zip(out, reqs):
        assert _canon(*got) == _canon(*host(r))
    (top,) = [s for s in TRACER.spans()
              if s.trace_id == root.trace_id and s.name == "rebuild_many"]
    assert top.tags["wide_histories"] == 2
    assert top.tags["host_fallbacks"] == 0


def test_recorded_peaks_equal_the_slots_pack_workflow_allocates():
    """``slot_peaks``, measured from the event types alone, is the
    packer's own peak: the highest slot it allocates, the capacity a
    history packs at exactly, and one slot below it overflows."""
    fuzzer = HistoryFuzzer(seed=3)
    hists = [fuzzer.generate(target_events=n) for n in (30, 120, 400)]
    hists += [fanout(k, w, p) for k, w, p in WIDE]
    table_of = {}
    for t, tables in (
            (0, (EventType.ActivityTaskScheduled,)),
            (1, (EventType.TimerStarted,)),
            (2, (EventType.StartChildWorkflowExecutionInitiated,)),
            (3, (EventType.RequestCancelExternalWorkflowExecutionInitiated,)),
            (4, (EventType.SignalExternalWorkflowExecutionInitiated,))):
        for et in tables:
            table_of[int(et)] = t
    wide = S.Capacities(**{f: WIDEST for f in SLOT_TABLES})
    for batches in hists:
        peaks = slot_peaks(batches)
        arr, _ = pack_workflow(batches, wide)
        used = [0] * len(SLOT_TABLES)
        for row in arr:
            t = table_of.get(int(row[S.EV_TYPE]))
            if t is not None:
                used[t] = max(used[t], int(row[S.EV_SLOT]) + 1)
        assert peaks == tuple(used)
        exact = dataclasses.replace(wide, **dict(zip(SLOT_TABLES, peaks)))
        np.testing.assert_array_equal(pack_workflow(batches, exact)[0], arr)
        for field, p in zip(SLOT_TABLES, peaks):
            if p:
                with pytest.raises(PackOverflowError):
                    pack_workflow(batches,
                                  dataclasses.replace(exact, **{field: p - 1}))
        # the same rows at the history's own bucket: slots do not
        # depend on the capacity they were packed at
        arr2, _ = pack_workflow(batches, bucket_caps(peaks))
        np.testing.assert_array_equal(arr, arr2)


def test_a_history_inside_the_default_caps_gets_exactly_them():
    fuzzer = HistoryFuzzer(seed=9)
    hs = [(f"wf-{i}", f"run-{i}", fuzzer.generate(target_events=60))
          for i in range(6)]
    for batches in (h[2] for h in hs):
        assert bucket_caps(slot_peaks(batches)) == S.Capacities()
    assert all(caps == S.Capacities() for _, _, caps in buckets(hs))
    # the kernel keeps its tile and buffering at the default caps
    assert fit_tile(S.Capacities()) == (4096, 2)
    assert presence_words(S.Capacities()) == 4


def test_wide_buckets_group_by_width_alone():
    """Histories at the default caps group as ``depth_buckets`` groups
    them; each wider bucket is one group whatever its depths, narrowest
    first, after the default ones."""
    fuzzer = HistoryFuzzer(seed=21)
    # (width, pending) by position: 33-48 wide lands in the 48-slot
    # bucket, 70 in the 96-slot one; 36 and 47 differ in depth class
    wide_specs = {1: (70, 5), 3: (36, 36), 5: (47, 33), 7: (70, 5),
                  9: (40, 34), 11: (44, 40)}
    hs = []
    for i in range(12):
        if i in wide_specs:
            batches = fanout("activity", *wide_specs[i], seed=i)
        else:
            batches = fuzzer.generate(target_events=20 + 35 * i)
        hs.append((f"wf-{i}", f"run-{i}", batches))
    got = buckets(hs)
    narrow = [i for i in range(len(hs)) if i not in wide_specs]
    want = [tuple(narrow[j] for j in idxs)
            for idxs, _ in depth_buckets([hs[i] for i in narrow])]
    assert len(want) > 1
    assert [idxs for idxs, _, _ in got[:len(want)]] == want
    assert all(caps == S.Capacities() for _, _, caps in got[:len(want)])
    wide = got[len(want):]
    assert [caps.max_activities for _, _, caps in wide] == [48, 96]
    assert [idxs for idxs, _, _ in wide] == [(3, 5, 9, 11), (1, 7)]
    depths = {round_scan_len(history_depth(hs[i][2])) for i in (3, 5)}
    assert len(depths) == 2  # one bucket over two depth classes
    assert all(h is hs[i] for idxs, group, _ in got
               for i, h in zip(idxs, group))


def test_bucket_caps_round_each_table_on_the_grid():
    caps = bucket_caps((33, 17, 0, 9, 0))
    assert (caps.max_activities, caps.max_timers, caps.max_children,
            caps.max_request_cancels, caps.max_signals_ext) == (
                48, 24, 16, 12, 8)
    assert bucket_caps((300, 0, 0, 0, 0)).max_activities == 384
    assert caps.max_events == 1024 and caps.max_version_items == 8
    # the widest bucket still fits the kernel's VMEM, on a narrower tile
    widest = S.Capacities(max_activities=WIDEST)
    assert fit_tile(widest) == (1024, 1)
    assert RowMap(widest).rows_padded == 7632
    assert presence_words(widest) == 2 + WIDEST // 32


def test_mixed_narrow_and_wide_batch_answers_in_submission_order(store):
    fuzzer = HistoryFuzzer(seed=17)
    hists = []
    for i in range(8):
        if i % 2:
            hists.append(fanout("activity", 34 + 9 * i, 33 + i, seed=i))
        else:
            hists.append(fuzzer.generate(target_events=20 + 15 * i))
    reqs = [_request(store, i, b) for i, b in enumerate(hists)]
    scope = Scope()
    rb = StateRebuilder(store, lane_len=128, metrics=scope)
    calls, host = _counting(rb)
    out = rb.rebuild_many(reqs)
    assert calls == []
    for got, r in zip(out, reqs):
        assert got[0].execution_info.run_id == r.run_id
        assert _canon(*got) == _canon(*host(r))
    assert scope.registry.counter_value("wide_histories") == 4


def test_wide_history_with_checkpoints_rebuilds_on_the_device(store):
    """A wide bucket writes no checkpoint (lookups are at the default
    caps), so the next rebuild of a wide history misses and replays whole
    on the device; so does a default-caps checkpoint whose suffix
    outgrows the default tables."""
    wide = fanout("activity", 45, 40)
    prefix = fanout("activity", 12, 10)
    reqs = [_request(store, 0, wide), _request(store, 1, prefix)]
    scope = Scope()
    ckpts = MemoryCheckpointStore()
    rb = StateRebuilder(store, lane_len=128, metrics=scope,
                        checkpoints=CheckpointManager(ckpts))
    calls, host = _counting(rb)
    rb.rebuild_many(reqs)
    assert scope.registry.counter_value("checkpoint_miss") == 2
    assert ckpts.count_checkpoints() == 1  # run-1's, none for run-0
    # run-1 grows past the default tables after its checkpoint
    ids_t = prefix[-1][-1].timestamp
    eid = sum(len(b) for b in prefix)
    grown = []
    for k in range(30):
        eid += 1
        grown.append(F.activity_task_scheduled(
            eid, 1, ids_t, activity_id=f"more{k}"))
    branch = reqs[1].branch_token
    from cadence_tpu.runtime.persistence.records import BranchToken
    store.append_history_nodes(BranchToken.from_json(branch.decode()),
                               grown, transaction_id=len(prefix) + 1)
    out = rb.rebuild_many(reqs)
    assert calls == []
    for got, r in zip(out, reqs):
        assert _canon(*got) == _canon(*host(r))
    assert len(out[1][0].pending_activities) == 40
    # run-0 has no checkpoint; run-1's hit degrades to a full replay
    # once its suffix is measured
    counter = scope.registry.counter_value
    assert counter("checkpoint_invalidated") == 0
    assert counter("checkpoint_miss") == 4
    assert counter("checkpoint_hit") == 0


def test_a_width_above_the_widest_bucket_falls_back_alone(store):
    too_wide = fanout("activity", WIDEST + 1, WIDEST + 1)
    fuzzer = HistoryFuzzer(seed=5)
    reqs = [_request(store, 0, fuzzer.generate(target_events=40)),
            _request(store, 1, too_wide),
            _request(store, 2, fanout("activity", 40, 35))]
    rb = StateRebuilder(store, lane_len=1024)
    calls, host = _counting(rb)
    with TRACER.trace("caller", sampled=True) as root:
        out = rb.rebuild_many(reqs)
    assert calls == ["run-1"]
    for got, r in zip(out, reqs):
        assert _canon(*got) == _canon(*host(r))
    (top,) = [s for s in TRACER.spans()
              if s.trace_id == root.trace_id and s.name == "rebuild_many"]
    assert top.tags["host_fallbacks"] == 1
    assert top.tags["device_histories"] == 2
    assert top.tags["wide_histories"] == 1

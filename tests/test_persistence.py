"""Cross-backend persistence conformance suite.

One suite, N backends — the reference's persistence-tests pattern
(/root/reference/common/persistence/persistence-tests/): every test runs
against both the memory and sqlite bundles via the fixture param."""

import json

import pytest

from cadence_tpu.core import history_factory as F
from cadence_tpu.core.events import encode_batch
from cadence_tpu.core.enums import TimerTaskType, TransferTaskType
from cadence_tpu.core.tasks import ReplicationTask, TimerTask, TransferTask
from cadence_tpu.runtime.persistence import (
    ConditionFailedError,
    CreateWorkflowMode,
    DomainAlreadyExistsError,
    DomainConfig,
    DomainInfo,
    DomainRecord,
    DomainReplicationConfig,
    EntityNotExistsError,
    ShardInfo,
    ShardOwnershipLostError,
    TaskInfo,
    TaskListLeaseLostError,
    TaskType,
    VisibilityRecord,
    WorkflowAlreadyStartedError,
    WorkflowSnapshot,
    create_memory_bundle,
    create_sqlite_bundle,
)
from cadence_tpu.testing.event_generator import HistoryFuzzer

SHARD = 1
RANGE = 1


@pytest.fixture(params=["memory", "sqlite"])
def bundle(request, tmp_path):
    if request.param == "memory":
        b = create_memory_bundle()
    else:
        b = create_sqlite_bundle(str(tmp_path / "store.db"))
    b.shard.create_shard(ShardInfo(shard_id=SHARD, range_id=RANGE))
    yield b
    b.close()


def make_snapshot(
    wf="wf1", run="run1", domain="dom", next_event_id=3, state=1,
    close_status=0, request_id="req1", tasks=False, last_write_version=0,
):
    snap = {
        "exec": {"state": state, "close_status": close_status},
        "request_id": request_id,
    }
    return WorkflowSnapshot(
        domain_id=domain,
        workflow_id=wf,
        run_id=run,
        snapshot=snap,
        next_event_id=next_event_id,
        last_write_version=last_write_version,
        transfer_tasks=(
            [
                TransferTask(
                    task_type=TransferTaskType.DecisionTask,
                    domain_id=domain, workflow_id=wf, run_id=run,
                    task_id=100, task_list="tl", schedule_id=2,
                )
            ]
            if tasks
            else []
        ),
        timer_tasks=(
            [
                TimerTask(
                    task_type=TimerTaskType.WorkflowTimeout,
                    visibility_timestamp=5000, domain_id=domain,
                    workflow_id=wf, run_id=run, task_id=101,
                )
            ]
            if tasks
            else []
        ),
    )


# -- shard ---------------------------------------------------------------


def test_shard_crud(bundle):
    info = bundle.shard.get_shard(SHARD)
    assert info.range_id == RANGE
    info.range_id = 2
    bundle.shard.update_shard(info, previous_range_id=RANGE)
    assert bundle.shard.get_shard(SHARD).range_id == 2
    # stale update fenced
    info.range_id = 3
    with pytest.raises(ShardOwnershipLostError):
        bundle.shard.update_shard(info, previous_range_id=RANGE)
    with pytest.raises(EntityNotExistsError):
        bundle.shard.get_shard(99)


# -- executions ----------------------------------------------------------


def test_create_get_update_execution(bundle):
    ex = bundle.execution
    snap = make_snapshot(tasks=True)
    ex.create_workflow_execution(SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, snap)

    got = ex.get_workflow_execution(SHARD, "dom", "wf1", "run1")
    assert got.next_event_id == 3
    assert got.snapshot["exec"]["state"] == 1

    cur = ex.get_current_execution(SHARD, "dom", "wf1")
    assert cur.run_id == "run1" and cur.state == 1

    # brand-new again fails with started error carrying run id
    with pytest.raises(WorkflowAlreadyStartedError) as ei:
        ex.create_workflow_execution(
            SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, make_snapshot()
        )
    assert ei.value.run_id == "run1"

    # conditional update: wrong condition fails
    mut = make_snapshot(next_event_id=5)
    with pytest.raises(ConditionFailedError):
        ex.update_workflow_execution(SHARD, RANGE, 99, mut)
    ex.update_workflow_execution(SHARD, RANGE, 3, mut)
    assert ex.get_workflow_execution(SHARD, "dom", "wf1", "run1").next_event_id == 5

    # fenced by newer range_id
    info = bundle.shard.get_shard(SHARD)
    info.range_id = 10
    bundle.shard.update_shard(info, previous_range_id=RANGE)
    with pytest.raises(ShardOwnershipLostError):
        ex.update_workflow_execution(SHARD, RANGE, 5, make_snapshot(next_event_id=7))


def test_workflow_id_reuse(bundle):
    ex = bundle.execution
    ex.create_workflow_execution(
        SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, make_snapshot()
    )
    # reuse while running -> already started
    with pytest.raises(WorkflowAlreadyStartedError):
        ex.create_workflow_execution(
            SHARD, RANGE, CreateWorkflowMode.WORKFLOW_ID_REUSE,
            make_snapshot(run="run2"), prev_run_id="run1",
        )
    # close it, then reuse works
    ex.update_workflow_execution(
        SHARD, RANGE, 3, make_snapshot(next_event_id=4, state=2, close_status=1)
    )
    ex.create_workflow_execution(
        SHARD, RANGE, CreateWorkflowMode.WORKFLOW_ID_REUSE,
        make_snapshot(run="run2"), prev_run_id="run1",
    )
    assert ex.get_current_execution(SHARD, "dom", "wf1").run_id == "run2"


def test_continue_as_new_atomic(bundle):
    ex = bundle.execution
    ex.create_workflow_execution(
        SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, make_snapshot()
    )
    old = make_snapshot(next_event_id=6, state=2, close_status=5)
    new = make_snapshot(run="run2", next_event_id=3)
    ex.update_workflow_execution(
        SHARD, RANGE, 3, old, new_snapshot=new,
        new_mode=CreateWorkflowMode.CONTINUE_AS_NEW,
    )
    assert ex.get_current_execution(SHARD, "dom", "wf1").run_id == "run2"
    # both concrete runs exist
    assert ex.get_workflow_execution(SHARD, "dom", "wf1", "run1").next_event_id == 6
    assert ex.get_workflow_execution(SHARD, "dom", "wf1", "run2").next_event_id == 3


def test_transfer_timer_queues(bundle):
    ex = bundle.execution
    ex.create_workflow_execution(
        SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, make_snapshot(tasks=True)
    )
    tasks = ex.get_transfer_tasks(SHARD, 0, 10_000, 10)
    assert len(tasks) == 1 and tasks[0].task_id == 100
    assert tasks[0].task_type == TransferTaskType.DecisionTask
    ex.complete_transfer_task(SHARD, 100)
    assert ex.get_transfer_tasks(SHARD, 0, 10_000, 10) == []

    timers = ex.get_timer_tasks(SHARD, 0, 10_000, 10)
    assert len(timers) == 1 and timers[0].visibility_timestamp == 5000
    # window below the timer sees nothing
    assert ex.get_timer_tasks(SHARD, 0, 5000, 10) == []
    ex.complete_timer_task(SHARD, 5000, 101)
    assert ex.get_timer_tasks(SHARD, 0, 10_000, 10) == []


def test_replication_queue(bundle):
    ex = bundle.execution
    snap = make_snapshot()
    snap.replication_tasks = [
        ReplicationTask(
            domain_id="dom", workflow_id="wf1", run_id="run1", task_id=7,
            first_event_id=1, next_event_id=3, version=10,
            branch_token=b"\x01\x02",
        )
    ]
    ex.create_workflow_execution(SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, snap)
    tasks = ex.get_replication_tasks(SHARD, 0, 10)
    assert len(tasks) == 1 and tasks[0].branch_token == b"\x01\x02"
    ex.complete_replication_task(SHARD, 7)
    assert ex.get_replication_tasks(SHARD, 0, 10) == []


def test_delete_execution(bundle):
    ex = bundle.execution
    ex.create_workflow_execution(
        SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, make_snapshot()
    )
    ex.delete_current_workflow_execution(SHARD, "dom", "wf1", "run1")
    with pytest.raises(EntityNotExistsError):
        ex.get_current_execution(SHARD, "dom", "wf1")
    ex.delete_workflow_execution(SHARD, "dom", "wf1", "run1")
    with pytest.raises(EntityNotExistsError):
        ex.get_workflow_execution(SHARD, "dom", "wf1", "run1")


# -- history tree --------------------------------------------------------


def _events(first_id, n, v=10, t=1_700_000_000_000_000_000):
    return [
        F.marker_recorded(first_id + i, v, t, decision_task_completed_event_id=1)
        for i in range(n)
    ]


def test_history_append_read(bundle):
    h = bundle.history
    branch = h.new_history_branch("tree1")
    h.append_history_nodes(branch, _events(1, 3), transaction_id=1)
    h.append_history_nodes(branch, _events(4, 2), transaction_id=2)
    batches, token = h.read_history_branch(branch, 1, 10_000)
    assert token == 0
    assert [b[0].event_id for b in batches] == [1, 4]
    # paginated
    batches, token = h.read_history_branch(branch, 1, 10_000, page_size=1)
    assert len(batches) == 1 and token == 4
    batches, token = h.read_history_branch(
        branch, 1, 10_000, page_size=1, next_token=token
    )
    assert batches[0][0].event_id == 4 and token == 0


def test_history_txn_id_wins(bundle):
    h = bundle.history
    branch = h.new_history_branch("tree1")
    h.append_history_nodes(branch, _events(1, 2, v=10), transaction_id=5)
    # lower transaction id loses
    h.append_history_nodes(branch, _events(1, 3, v=20), transaction_id=3)
    batches, _ = h.read_history_branch(branch, 1, 100)
    assert len(batches[0]) == 2 and batches[0][0].version == 10
    # higher wins
    h.append_history_nodes(branch, _events(1, 3, v=30), transaction_id=9)
    batches, _ = h.read_history_branch(branch, 1, 100)
    assert len(batches[0]) == 3 and batches[0][0].version == 30


def test_history_fork(bundle):
    h = bundle.history
    main = h.new_history_branch("tree1")
    h.append_history_nodes(main, _events(1, 3), transaction_id=1)
    h.append_history_nodes(main, _events(4, 3), transaction_id=2)
    h.append_history_nodes(main, _events(7, 3), transaction_id=3)

    fork = h.fork_history_branch(main, fork_node_id=7)
    # fork sees ancestor nodes below 7 only
    batches, _ = h.read_history_branch(fork, 1, 10_000)
    assert [b[0].event_id for b in batches] == [1, 4]
    # write to the fork; main is unaffected
    h.append_history_nodes(fork, _events(7, 2, v=99), transaction_id=4)
    fork_batches, _ = h.read_history_branch(fork, 1, 10_000)
    assert [b[0].event_id for b in fork_batches] == [1, 4, 7]
    assert fork_batches[-1][0].version == 99
    main_batches, _ = h.read_history_branch(main, 1, 10_000)
    assert main_batches[-1][0].version == 10

    assert len(h.get_history_tree("tree1")) == 2
    h.delete_history_branch(fork)
    assert len(h.get_history_tree("tree1")) == 1


def _mixed_history():
    """Fuzzed batches of several depths, then batches whose attributes
    hold bytes nested in dicts and lists: more than two 256-node pages."""
    fuzzer = HistoryFuzzer(seed=5)
    batches, eid = [], 1
    while len(batches) < 600:
        for batch in fuzzer.generate(target_events=120):
            shift = eid - batch[0].event_id
            for e in batch:
                e.event_id += shift
            eid = batch[-1].event_id + 1
            batches.append(batch)
    for i in range(40):
        marker = F.marker_recorded(
            eid, 10, 1_700_000_000_000_000_000, marker_name="__bytes__",
            details=b"\x00\xff")
        marker.attributes["details"] = [b"\x00\xff", {"k": b"v"}]
        marker.attributes["header"] = {"fields": {"trace": b"t%d" % i}}
        batches.append([marker])
        eid += 1
    return batches


def _set_node_blob(h, branch, node_id, blob):
    """Overwrite one stored node's bytes (backend-peeking)."""
    if hasattr(h, "_nodes"):  # memory backend
        nodes = h._nodes[(branch.tree_id, branch.branch_id)]
        nodes[node_id] = (nodes[node_id][0], blob)
        return
    with h.db.txn() as c:  # sqlite backend
        c.execute(
            "UPDATE history_nodes SET blob=? WHERE tree_id=? AND "
            "branch_id=? AND node_id=?",
            (blob, branch.tree_id, branch.branch_id, node_id),
        )


def test_history_pages_round_trip(bundle):
    h = bundle.history
    branch = h.new_history_branch("tree-pages")
    written = _mixed_history()
    for txn, batch in enumerate(written, start=1):
        h.append_history_nodes(branch, batch, transaction_id=txn)
    got, tokens, token = [], [], 0
    while True:
        page, token = h.read_history_branch(
            branch, 1, 1 << 60, page_size=256, next_token=token)
        got += page
        tokens.append(token)
        if not token:
            break
    assert got == written
    # a page's token is the node id (first event id) of the next page
    ids = [b[0].event_id for b in written]
    assert tokens == [ids[256], ids[512], 0]
    assert isinstance(got[-1][0].attributes["details"][0], bytes)

    # a corrupt blob in a page raises as decoding it alone always has
    _set_node_blob(h, branch, ids[300], encode_batch(written[300])[:-9])
    with pytest.raises(json.JSONDecodeError):
        h.read_history_branch(branch, 1, 1 << 60, page_size=256,
                              next_token=ids[256])
    # the page before it still reads
    page, token = h.read_history_branch(branch, 1, 1 << 60, page_size=256)
    assert page == written[:256] and token == ids[256]


def _tree_node_count(h, tree_id):
    """Raw node count per tree (backend-peeking: orphan-leak assertions)."""
    if hasattr(h, "_nodes"):  # memory backend
        return sum(
            len(v) for k, v in h._nodes.items() if k[0] == tree_id
        )
    with h.db.txn() as c:  # sqlite backend
        return c.execute(
            "SELECT COUNT(*) FROM history_nodes WHERE tree_id=?",
            (tree_id,),
        ).fetchone()[0]


def test_delete_last_descendant_reclaims_ancestor_nodes(bundle):
    # ADVICE r4: deleting a forked-from branch retains its shared prefix
    # for descendants, but once the LAST descendant goes those retained
    # nodes must be swept too — they were orphaned forever (no
    # history_branches row, invisible to the scavenger).
    h = bundle.history
    main = h.new_history_branch("tree-orph")
    h.append_history_nodes(main, _events(1, 3), transaction_id=1)
    h.append_history_nodes(main, _events(4, 3), transaction_id=2)
    h.append_history_nodes(main, _events(7, 3), transaction_id=3)
    fork = h.fork_history_branch(main, fork_node_id=7)
    h.append_history_nodes(fork, _events(7, 2, v=99), transaction_id=4)

    h.delete_history_branch(main)
    # shared prefix survives for the fork; main's own tail is gone
    assert _tree_node_count(h, "tree-orph") > 0
    batches, _ = h.read_history_branch(fork, 1, 10_000)
    assert [b[0].event_id for b in batches] == [1, 4, 7]

    h.delete_history_branch(fork)
    assert h.get_history_tree("tree-orph") == []
    assert _tree_node_count(h, "tree-orph") == 0


# -- matching tasks ------------------------------------------------------


def test_task_list_lease_and_tasks(bundle):
    tm = bundle.task
    info = tm.lease_task_list("dom", "tl", TaskType.DECISION)
    assert info.range_id == 1
    info2 = tm.lease_task_list("dom", "tl", TaskType.DECISION)
    assert info2.range_id == 2
    # the old lease can no longer write
    with pytest.raises(TaskListLeaseLostError):
        tm.create_tasks(info, [TaskInfo("dom", "wf1", "run1", 1, 2)])
    tm.create_tasks(
        info2,
        [
            TaskInfo("dom", "wf1", "run1", 1, 2),
            TaskInfo("dom", "wf2", "run2", 2, 2),
        ],
    )
    tasks = tm.get_tasks("dom", "tl", TaskType.DECISION, 0, 100, 10)
    assert [t.task_id for t in tasks] == [1, 2]
    tm.complete_task("dom", "tl", TaskType.DECISION, 1)
    assert len(tm.get_tasks("dom", "tl", TaskType.DECISION, 0, 100, 10)) == 1
    assert tm.complete_tasks_less_than("dom", "tl", TaskType.DECISION, 100) == 1

    info2.ack_level = 2
    tm.update_task_list(info2)
    lists = tm.list_task_lists()
    assert len(lists) == 1 and lists[0].ack_level == 2
    tm.delete_task_list("dom", "tl", TaskType.DECISION, info2.range_id)
    assert tm.list_task_lists() == []


# -- domains -------------------------------------------------------------


def _domain(name="dom1"):
    return DomainRecord(
        info=DomainInfo(id="", name=name, description="d"),
        config=DomainConfig(retention_days=3),
        replication_config=DomainReplicationConfig(),
    )


def test_domain_crud(bundle):
    md = bundle.metadata
    did = md.create_domain(_domain())
    with pytest.raises(DomainAlreadyExistsError):
        md.create_domain(_domain())
    rec = md.get_domain(name="dom1")
    assert rec.info.id == did and rec.config.retention_days == 3
    assert md.get_domain(id=did).info.name == "dom1"

    v0 = rec.notification_version
    rec.config.retention_days = 9
    md.update_domain(rec)
    rec2 = md.get_domain(id=did)
    assert rec2.config.retention_days == 9
    assert rec2.notification_version > v0
    assert md.get_metadata_version() >= 2

    assert len(md.list_domains()) == 1
    md.delete_domain(name="dom1")
    with pytest.raises(EntityNotExistsError):
        md.get_domain(name="dom1")


# -- visibility ----------------------------------------------------------


def test_visibility_lifecycle(bundle):
    vis = bundle.visibility
    for i in range(3):
        vis.record_workflow_execution_started(
            VisibilityRecord(
                domain_id="dom", workflow_id=f"wf{i}", run_id=f"run{i}",
                workflow_type="echo", start_time=1000 + i,
            )
        )
    open_recs, _ = vis.list_open_workflow_executions("dom")
    assert len(open_recs) == 3
    assert open_recs[0].workflow_id == "wf2"  # start_time desc

    vis.record_workflow_execution_closed(
        VisibilityRecord(
            domain_id="dom", workflow_id="wf1", run_id="run1",
            workflow_type="echo", start_time=1001, close_time=2000,
            close_status=0, history_length=10,
        )
    )
    open_recs, _ = vis.list_open_workflow_executions("dom")
    assert len(open_recs) == 2
    closed, _ = vis.list_closed_workflow_executions("dom")
    assert len(closed) == 1 and closed[0].history_length == 10
    closed, _ = vis.list_closed_workflow_executions("dom", close_status=0)
    assert len(closed) == 1
    closed, _ = vis.list_closed_workflow_executions("dom", close_status=1)
    assert closed == []

    got = vis.get_closed_workflow_execution("dom", "wf1", "")
    assert got.run_id == "run1"
    assert vis.count_workflow_executions("dom") == 3
    assert vis.count_workflow_executions("dom", open_only=True) == 2

    by_id, _ = vis.list_open_workflow_executions("dom", workflow_id="wf0")
    assert len(by_id) == 1

    vis.delete_workflow_execution("dom", "wf1", "run1")
    with pytest.raises(EntityNotExistsError):
        vis.get_closed_workflow_execution("dom", "wf1", "run1")


def test_visibility_pagination(bundle):
    vis = bundle.visibility
    for i in range(5):
        vis.record_workflow_execution_started(
            VisibilityRecord(
                domain_id="dom", workflow_id=f"wf{i}", run_id=f"r{i}",
                workflow_type="echo", start_time=i,
            )
        )
    page1, token = vis.list_open_workflow_executions("dom", page_size=2)
    assert len(page1) == 2 and token
    page2, token = vis.list_open_workflow_executions(
        "dom", page_size=2, next_token=token
    )
    assert len(page2) == 2 and token
    page3, token = vis.list_open_workflow_executions(
        "dom", page_size=2, next_token=token
    )
    assert len(page3) == 1 and token == 0
    ids = [r.workflow_id for r in page1 + page2 + page3]
    assert ids == ["wf4", "wf3", "wf2", "wf1", "wf0"]


class TestHistoryTrees:
    def test_list_history_trees_both_backends(self, bundle):
        """The scavenger's scan surface must exist on every backend —
        sqlite silently lacked it and orphaned trees accumulated."""
        h = bundle.history
        b1 = h.new_history_branch(tree_id="tree-a")
        b2 = h.new_history_branch(tree_id="tree-b")
        trees = dict(h.list_history_trees())
        assert set(trees) >= {"tree-a", "tree-b"}
        assert any(t.branch_id == b1.branch_id for t in trees["tree-a"])
        h.delete_history_branch(b2)
        trees = dict(h.list_history_trees())
        assert "tree-b" not in trees

    def test_missing_shard_row_fences_writes(self, bundle):
        """A write against a shard with no shard record must fence
        (EntityNotExists), not bypass range checking."""
        import pytest as _pytest

        from cadence_tpu.runtime.persistence.errors import (
            EntityNotExistsError,
        )
        from cadence_tpu.runtime.persistence.records import (
            WorkflowSnapshot,
        )

        snap = WorkflowSnapshot(
            domain_id="d", workflow_id="w", run_id="r",
            snapshot={"execution_info": {}}, next_event_id=2,
        )
        with _pytest.raises(EntityNotExistsError):
            bundle.execution.create_workflow_execution(
                9999, 1, 0, snap
            )


class TestReshardState:
    """Singleton routing-epoch row (elastic resharding write-ahead
    record) — LWT semantics identical on every backend."""

    def test_absent_reads_none_and_writes_from_epoch_zero(self, bundle):
        assert bundle.shard.get_reshard_state() is None
        bundle.shard.set_reshard_state(1, '{"m": 1}', previous_epoch=0)
        assert bundle.shard.get_reshard_state() == (1, '{"m": 1}')

    def test_epoch_lwt_rejects_stale_writer(self, bundle):
        bundle.shard.set_reshard_state(1, "a", previous_epoch=0)
        with pytest.raises(ConditionFailedError):
            bundle.shard.set_reshard_state(2, "b", previous_epoch=0)
        # in-place update under the SAME epoch (plan state transitions)
        bundle.shard.set_reshard_state(1, "a2", previous_epoch=1)
        bundle.shard.set_reshard_state(2, "b", previous_epoch=1)
        assert bundle.shard.get_reshard_state() == (2, "b")


class TestReplicationProgress:
    """Consumer-side replication cursor/mode rows (adaptive
    geo-replication) — versioned LWT semantics identical on every
    backend, keyed (shard, remote cluster)."""

    def test_absent_reads_none_and_writes_from_version_zero(self, bundle):
        assert bundle.shard.get_replication_progress(1, "active") is None
        bundle.shard.set_replication_progress(
            1, "active", '{"applied_through": 7}', previous_version=0
        )
        assert bundle.shard.get_replication_progress(1, "active") == (
            1, '{"applied_through": 7}'
        )

    def test_version_lwt_rejects_stale_writer(self, bundle):
        bundle.shard.set_replication_progress(1, "active", "a", 0)
        with pytest.raises(ConditionFailedError):
            bundle.shard.set_replication_progress(1, "active", "b", 0)
        bundle.shard.set_replication_progress(1, "active", "b", 1)
        assert bundle.shard.get_replication_progress(1, "active") == (
            2, "b"
        )

    def test_rows_keyed_per_shard_and_cluster(self, bundle):
        bundle.shard.set_replication_progress(1, "active", "s1a", 0)
        bundle.shard.set_replication_progress(2, "active", "s2a", 0)
        bundle.shard.set_replication_progress(1, "other", "s1o", 0)
        assert bundle.shard.get_replication_progress(1, "active") == (
            1, "s1a"
        )
        assert bundle.shard.get_replication_progress(2, "active") == (
            1, "s2a"
        )
        assert bundle.shard.get_replication_progress(1, "other") == (
            1, "s1o"
        )

    def test_torn_write_retry_reads_landed_blob_as_success(self, bundle):
        """The reshard_state discipline: a torn write LANDS while the
        ack is lost; the caller's retry re-reads, sees exactly the blob
        it meant to write at the bumped version, and treats the write
        as durable (processor._persist_progress)."""
        from cadence_tpu.testing.faults import FaultRule, FaultSchedule
        from cadence_tpu.runtime.persistence.decorators import wrap_bundle

        sched = FaultSchedule(seed=7, rules=[
            FaultRule(site="persistence.shard",
                      method="set_replication_progress",
                      probability=1.0, max_faults=1,
                      action="torn_write", error="TimeoutError"),
        ])
        wrapped = wrap_bundle(bundle, faults=sched)
        blob = '{"applied_through": 42, "mode": "snapshot"}'
        with pytest.raises(TimeoutError):
            wrapped.shard.set_replication_progress(1, "active", blob, 0)
        # the write landed; a blind retry with the stale version fences
        with pytest.raises(ConditionFailedError):
            wrapped.shard.set_replication_progress(1, "active", blob, 0)
        # ... and the re-read shows the landed blob — retry succeeds by
        # recognizing its own write, never double-bumping the version
        assert wrapped.shard.get_replication_progress(1, "active") == (
            1, blob
        )


class TestReshardMove:
    """reshard_extract / reshard_install: the handoff's row mover —
    atomic, watermark-aware, and exactly-once on task identity."""

    TARGET = 7

    def _seed(self, bundle, wf="wf-move", run="run1"):
        bundle.shard.create_shard(
            ShardInfo(shard_id=self.TARGET, range_id=5)
        )
        snap = make_snapshot(wf=wf, run=run, tasks=True)
        bundle.execution.create_workflow_execution(
            SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, snap
        )
        return snap

    def test_extract_install_roundtrip_moves_everything(self, bundle):
        self._seed(bundle)
        ext = bundle.execution.reshard_extract(
            SHARD, ["wf-move"], transfer_watermark=0,
            timer_watermark=(0, 0), delete=True,
        )
        assert len(ext["executions"]) == 1
        assert len(ext["currents"]) == 1
        assert len(ext["transfer"]) == 1 and len(ext["timers"]) == 1
        # gone from the source
        with pytest.raises(EntityNotExistsError):
            bundle.execution.get_workflow_execution(
                SHARD, "dom", "wf-move", "run1"
            )
        assert bundle.execution.get_transfer_tasks(SHARD, 0, 1 << 60, 10) == []

        ids = iter(range(1000, 1010))
        bundle.execution.reshard_install(
            self.TARGET, 5, ext, lambda: next(ids)
        )
        resp = bundle.execution.get_workflow_execution(
            self.TARGET, "dom", "wf-move", "run1"
        )
        assert resp.next_event_id == 3
        cur = bundle.execution.get_current_execution(
            self.TARGET, "dom", "wf-move"
        )
        assert cur.run_id == "run1"
        moved = bundle.execution.get_transfer_tasks(
            self.TARGET, 0, 1 << 60, 10
        )
        # re-minted ids from the target's sequencer; same task identity
        assert [t.task_id for t in moved] == [1000]
        assert moved[0].workflow_id == "wf-move"
        timers = bundle.execution.get_timer_tasks(
            self.TARGET, 0, 1 << 62, 10
        )
        assert len(timers) == 1 and timers[0].task_id == 1001

    def test_copy_then_purge_is_crash_safe(self, bundle):
        """delete=False extract is a pure read; purge removes exactly
        the named rows (idempotent) — the coordinator's copy-then-purge
        move never has a window with the rows on NEITHER shard."""
        self._seed(bundle)
        ext = bundle.execution.reshard_extract(
            SHARD, ["wf-move"], transfer_watermark=0,
            timer_watermark=(0, 0),
        )
        assert len(ext["executions"]) == 1
        # source intact after the read
        bundle.execution.get_workflow_execution(
            SHARD, "dom", "wf-move", "run1"
        )
        ids = iter(range(2000, 2010))
        bundle.execution.reshard_install(
            self.TARGET, 5, ext, lambda: next(ids)
        )
        # both copies exist (the crash window); purge resolves it
        bundle.execution.reshard_purge(SHARD, ext)
        with pytest.raises(EntityNotExistsError):
            bundle.execution.get_workflow_execution(
                SHARD, "dom", "wf-move", "run1"
            )
        assert bundle.execution.get_transfer_tasks(
            SHARD, 0, 1 << 60, 10
        ) == []
        bundle.execution.reshard_purge(SHARD, ext)  # idempotent
        bundle.execution.get_workflow_execution(
            self.TARGET, "dom", "wf-move", "run1"
        )

    def test_watermarks_leave_completed_tasks_behind(self, bundle):
        self._seed(bundle)
        # transfer task id is 100 (make_snapshot): a watermark at/above
        # it means the task was durably completed — it must NOT move
        ext = bundle.execution.reshard_extract(
            SHARD, ["wf-move"], transfer_watermark=100,
            timer_watermark=(1 << 62, 0),
        )
        assert ext["transfer"] == [] and ext["timers"] == []
        assert len(ext["executions"]) == 1

    def test_unlisted_workflows_stay(self, bundle):
        self._seed(bundle)
        other = make_snapshot(wf="wf-stay", run="run2", tasks=True)
        bundle.execution.create_workflow_execution(
            SHARD, RANGE, CreateWorkflowMode.BRAND_NEW, other
        )
        ext = bundle.execution.reshard_extract(
            SHARD, ["wf-move"], transfer_watermark=0,
            timer_watermark=(0, 0), delete=True,
        )
        assert {e["workflow_id"] for e in ext["executions"]} == {"wf-move"}
        # wf-stay untouched, tasks included
        bundle.execution.get_workflow_execution(
            SHARD, "dom", "wf-stay", "run2"
        )
        remaining = bundle.execution.get_transfer_tasks(
            SHARD, 0, 1 << 60, 10
        )
        assert {t.workflow_id for t in remaining} == {"wf-stay"}

    def test_install_fenced_by_target_range(self, bundle):
        self._seed(bundle)
        ext = bundle.execution.reshard_extract(
            SHARD, ["wf-move"], transfer_watermark=0,
            timer_watermark=(0, 0), delete=True,
        )
        with pytest.raises(ShardOwnershipLostError):
            bundle.execution.reshard_install(
                self.TARGET, 4, ext, lambda: 1  # stale range_id
            )
        # all-or-nothing: nothing landed on the fenced target
        assert bundle.execution.list_concrete_executions(self.TARGET) == []

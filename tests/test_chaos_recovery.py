"""Chaos recovery suite: recovery invariants under injected faults.

The fault-schedule-driven validation backbone (testing/faults.py):
every test drives real components — the full frontend→matching→history
stack or a live queue processor — against a seeded FaultSchedule and
asserts a recovery invariant, not just "no crash":

  * differential replay: a workflow driven to completion while
    persistence throws on a double-digit percentage of writes must
    produce BYTE-IDENTICAL history to a fault-free run;
  * shard-ownership-lost mid-stream must not lose or duplicate queue
    tasks (ack-watermark + exactly-once-completion assertions);
  * park-on-exhaustion followed by fault clearing must drain the
    backlog to zero;
  * the decorator stack (fault client innermost, metrics, rate limit)
    surfaces PersistenceBusyError untranslated and counts injected
    faults like real backend errors.

Determinism: histories are reproducible because the harness freezes
the clock (FakeTimeSource) and pins the matching poll nonce; the fault
sequence is reproducible because the schedule is seeded. CHAOS_SEED
overrides the seed (scripts/run_chaos.sh sweeps it).
"""

from __future__ import annotations

import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from cadence_tpu.client import HistoryClient, MatchingClient
from cadence_tpu.cluster import ClusterMetadata
from cadence_tpu.frontend import DomainHandler, WorkflowHandler
from cadence_tpu.matching import MatchingEngine
from cadence_tpu.runtime.domains import DomainCache
from cadence_tpu.runtime.membership import single_host_monitor
from cadence_tpu.runtime.persistence.decorators import (
    MetricsClient,
    PersistenceBusyError,
    RateLimitedClient,
    wrap_bundle,
)
from cadence_tpu.runtime.persistence.errors import PersistenceError
from cadence_tpu.runtime.persistence.memory import create_memory_bundle
from cadence_tpu.runtime.queues.ack import QueueAckManager
from cadence_tpu.runtime.queues.base import QueueProcessorBase
from cadence_tpu.runtime.service import HistoryService
from cadence_tpu.runtime.api import StartWorkflowRequest
from cadence_tpu.testing.faults import (
    FaultInjectionClient,
    FaultRule,
    FaultSchedule,
)
from cadence_tpu.utils.clock import FakeTimeSource
from cadence_tpu.utils.metrics import Scope
from cadence_tpu.worker import Worker

pytestmark = pytest.mark.chaos

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "1234"))
DOMAIN = "chaos-dom"
TL = "chaos-tl"


# ---------------------------------------------------------------------------
# deterministic full-stack harness
# ---------------------------------------------------------------------------


class ChaosBox:
    """Frontend→matching→history with a frozen clock and a pinned poll
    nonce, optionally fault-injected — two runs of the same workload
    produce byte-identical histories unless a fault breaks recovery.

    ``hosts`` > 1 builds an in-process multi-host cluster: one
    HistoryService per host over the SAME bundle, each with its own
    monitor whose history ring lists every host (the reshard chaos
    family kills hosts mid-handoff)."""

    def __init__(self, faults=None, num_shards=1, hosts=1, effects=False,
                 sanitize=False, queue_parallel=0):
        from cadence_tpu.runtime.membership import Monitor

        self.metrics = Scope()
        # queue_parallel > 0: ONE shared conflict-keyed wave executor
        # across every host's transfer/timer pumps (the queues.
        # parallelism gate), built from the live footprint table
        self.queue_executor = None
        if queue_parallel:
            from cadence_tpu.runtime.queues.parallel import (
                ParallelQueueExecutor,
            )

            self.queue_executor = ParallelQueueExecutor(
                parallelism=queue_parallel, metrics=self.metrics
            )
        self.persistence = create_memory_bundle()
        if faults is not None or effects or sanitize:
            self.persistence = wrap_bundle(
                self.persistence, metrics=self.metrics, faults=faults,
                effects=effects, sanitize=sanitize,
            )
        self.domain_handler = DomainHandler(
            self.persistence.metadata, ClusterMetadata()
        )
        self.domains = DomainCache(self.persistence.metadata)
        self.clock = FakeTimeSource()
        host_ids = [f"chaos-host-{i}" for i in range(hosts)]
        self.services = []
        controllers = {}
        for ident in host_ids:
            if hosts == 1:
                monitor = single_host_monitor(ident)
            else:
                monitor = Monitor(self_identity=ident)
                for service in Monitor.SERVICES:
                    monitor.resolver(service).set_hosts(list(host_ids))
            svc = HistoryService(
                num_shards, self.persistence, self.domains, monitor,
                time_source=self.clock,
                metrics=self.metrics, faults=faults,
                queue_executor=self.queue_executor,
            )
            self.services.append(svc)
            controllers[ident] = svc.controller
        self.history = self.services[0]
        hc = HistoryClient(controllers)
        self.history_client = hc
        self.matching = MatchingEngine(
            self.persistence.task, hc,
            poll_request_id_fn=(
                lambda info: f"rid-{info.workflow_id}-{info.schedule_id}"
            ),
        )
        mc = MatchingClient(self.matching)
        for svc in self.services:
            svc.wire(mc, hc)
            svc.start()
        self.frontend = WorkflowHandler(
            self.domain_handler, self.domains, hc, mc
        )
        self.domain_handler.register_domain(DOMAIN)

    def coordinator(self, **kwargs):
        from cadence_tpu.runtime.resharding import ReshardCoordinator

        return ReshardCoordinator(
            self.persistence,
            [svc.controller for svc in self.services],
            metrics=self.metrics, **kwargs,
        )

    def kill_host(self, index):
        """Hard-kill one host: its engines stop and every surviving
        ring evicts it (what the failure detector does on probe
        misses)."""
        dead = self.services[index]
        ident = dead.monitor.self_identity
        self.services = [
            s for i, s in enumerate(self.services) if i != index
        ]
        dead.stop()
        self.history_client.remove_host(ident)
        for svc in self.services:
            svc.monitor.leave("history", ident)
        return dead

    def stop(self):
        for svc in self.services:
            svc.stop()
        self.matching.shutdown()


def _chained_doubler(ctx, input):
    a = yield ctx.schedule_activity("double", input)
    b = yield ctx.schedule_activity("double", a)
    return b


def _drive_workflows(box, workflow_ids, timeout_s=30.0):
    """Run the doubler workflow to completion for every id; returns the
    canonical JSON serialization of each history."""
    w = Worker(box.frontend, DOMAIN, TL, identity="chaos-worker",
               sticky=False)
    w.register_workflow("chaos-wf", _chained_doubler)
    w.register_activity("double", lambda inp: inp * 2)
    w.start()
    try:
        histories = []
        for wid in workflow_ids:
            run_id = box.frontend.start_workflow_execution(
                StartWorkflowRequest(
                    domain=DOMAIN, workflow_id=wid,
                    workflow_type="chaos-wf", task_list=TL, input=b"x",
                    request_id=f"req-{wid}",
                    execution_start_to_close_timeout_seconds=60,
                )
            )
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                d = box.frontend.describe_workflow_execution(
                    DOMAIN, wid, run_id
                )
                if not d.is_running:
                    break
                time.sleep(0.02)
            else:
                raise AssertionError(f"workflow {wid} did not complete")
            events, _ = box.frontend.get_workflow_execution_history(
                DOMAIN, wid, run_id
            )
            histories.append(json.dumps(
                [e.to_dict() for e in events], sort_keys=True, default=repr
            ))
        return histories
    finally:
        w.stop()


def _write_fault_schedule(seed):
    """≥10% write-fault pressure on the paths the system hardens:
    optimistic-concurrency failures on the main execution write
    (Update_History_Loop retries), hard errors on queue-task completion
    (logged, never blocks the ack), and torn writes on the same
    (write lands, response lost — the idempotency reality)."""
    return FaultSchedule(seed=seed, rules=[
        FaultRule(site="persistence.execution",
                  method="update_workflow_execution",
                  probability=0.15, error="ConditionFailedError"),
        FaultRule(site="persistence.execution",
                  method="complete_transfer_task",
                  probability=0.2, error="PersistenceError"),
        FaultRule(site="persistence.shard", method="update_shard",
                  probability=0.2, action="torn_write",
                  error="TimeoutError"),
    ])


class TestDifferentialReplay:
    def test_history_byte_identical_under_write_faults(self):
        """Core recovery invariant: a seeded fault storm on >10% of the
        main persistence writes must not change a single byte of any
        driven workflow's final history."""
        wids = ["wf-1", "wf-2", "wf-3"]

        clean_box = ChaosBox()
        try:
            clean = _drive_workflows(clean_box, wids)
        finally:
            clean_box.stop()

        sched = _write_fault_schedule(CHAOS_SEED)
        chaos_box = ChaosBox(faults=sched)
        try:
            faulted = _drive_workflows(chaos_box, wids)
        finally:
            chaos_box.stop()

        # the storm actually happened (the whole point of the test)
        update = next(
            s for s in sched.snapshot()
            if s["method"] == "update_workflow_execution"
        )
        assert update["injected"] > 0, sched.snapshot()
        assert update["injected"] / max(1, update["matched"]) >= 0.05
        assert sched.injected_total() >= 5, sched.snapshot()

        for wid, a, b in zip(wids, clean, faulted):
            assert a == b, f"history for {wid} diverged under faults"

    def test_clean_runs_reproducible(self):
        """Sanity floor for the differential check: two fault-free runs
        of the harness are byte-identical (frozen clock, pinned poll
        nonce) — without this the test above proves nothing."""
        box1, box2 = ChaosBox(), ChaosBox()
        try:
            h1 = _drive_workflows(box1, ["wf-1"])
            h2 = _drive_workflows(box2, ["wf-1"])
        finally:
            box1.stop()
            box2.stop()
        assert h1 == h2


# ---------------------------------------------------------------------------
# queue-task integrity under shard-ownership loss
# ---------------------------------------------------------------------------


class _TaskStore:
    """Minimal ordered task queue for a bare QueueProcessorBase."""

    def __init__(self, n):
        self.tasks = [
            SimpleNamespace(task_id=i + 1, task_type=0) for i in range(n)
        ]

    def read(self, level, batch_size):
        return [t for t in self.tasks if t.task_id > level][:batch_size]


def _run_queue_until_drained(store, faults, timeout_s=15.0,
                             exhausted_retry_delay_s=0.1):
    processed = []
    completed = []
    lock = threading.Lock()

    def process(task):
        with lock:
            processed.append(task.task_id)

    def complete(task):
        with lock:
            completed.append(task.task_id)

    ack = QueueAckManager(0)
    proc = QueueProcessorBase(
        name="chaos", ack=ack,
        read_batch=store.read,
        process_task=process,
        complete_task=complete,
        task_key=lambda t: t.task_id,
        worker_count=4, batch_size=16,
        faults=faults,
        exhausted_retry_delay_s=exhausted_retry_delay_s,
        shard_id=3,
    )
    proc.start()
    try:
        last = store.tasks[-1].task_id
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            proc.notify()
            if ack.update_ack_level() >= last:
                break
            time.sleep(0.02)
        return processed, completed, ack
    finally:
        proc.stop()


class TestShardOwnershipLostIntegrity:
    def test_no_task_lost_or_double_completed(self):
        """ShardOwnershipLostError on ~30% of task executions: every
        task must still execute, complete exactly once, and the ack
        watermark must sweep the full range — an errored task is never
        acked away (lost) and a retried task is never completed twice
        (duplicated). The rule is shard-pinned to the processor's shard,
        proving the queue plane threads its shard id to the schedule."""
        store = _TaskStore(40)
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="queue.chaos", shard_id=3, probability=0.3,
                      error="ShardOwnershipLostError"),
        ])
        processed, completed, ack = _run_queue_until_drained(store, sched)

        all_ids = {t.task_id for t in store.tasks}
        assert set(processed) >= all_ids, "task lost (never executed)"
        assert sorted(completed) == sorted(all_ids), (
            "completion must be exactly-once per task"
        )
        assert ack.ack_level == store.tasks[-1].task_id
        assert ack.outstanding() == 0 and ack.held() == 0
        assert sched.injected_total() > 0  # the storm happened

    def test_park_on_exhaustion_then_clear_drains_to_zero(self):
        """Every attempt fails while armed → the retry budget exhausts
        and tasks park (held, wedging the ack sweep — never acked away).
        Disarming the schedule must let the parked retries fire and the
        backlog drain to zero."""
        store = _TaskStore(8)
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="queue.chaos", probability=1.0,
                      error="PersistenceError"),
        ])

        processed = []
        completed = []
        lock = threading.Lock()

        def process(task):
            with lock:
                processed.append(task.task_id)

        def complete(task):
            with lock:
                completed.append(task.task_id)

        ack = QueueAckManager(0)
        proc = QueueProcessorBase(
            name="chaos", ack=ack,
            read_batch=store.read,
            process_task=process,
            complete_task=complete,
            task_key=lambda t: t.task_id,
            worker_count=2, batch_size=16,
            faults=sched,
            exhausted_retry_delay_s=0.1,
        )
        proc.start()
        try:
            # phase 1: armed — every task must exhaust its in-line
            # budget and cycle through the park (DEFERRED→RETRY→re-run)
            # machinery without ever being acked away. Parked tasks
            # oscillate between held and re-taken, so the stable
            # invariants are: nothing completed, the ack level pinned
            # at 0, and every read task still accounted for.
            deadline = time.monotonic() + 10.0
            budget = 3 * len(store.tasks)  # one full in-line budget each
            while time.monotonic() < deadline:
                proc.notify()
                if sched.injected_total() >= budget:
                    break
                time.sleep(0.02)
            assert sched.injected_total() >= budget
            assert processed == [], "armed faults must precede the handler"
            assert ack.update_ack_level() == 0, (
                "ack level must not pass parked (unexecuted) tasks"
            )
            assert completed == []
            assert ack.outstanding() + ack.held() == len(store.tasks)

            # phase 2: fault cleared — backlog must drain to zero
            sched.disarm()
            last = store.tasks[-1].task_id
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                proc.notify()
                if ack.update_ack_level() >= last:
                    break
                time.sleep(0.02)
            assert ack.ack_level == last, (ack.ack_level, ack.held())
            assert sorted(completed) == [t.task_id for t in store.tasks]
            assert ack.outstanding() == 0 and ack.held() == 0
        finally:
            proc.stop()


# ---------------------------------------------------------------------------
# decorator stack composition
# ---------------------------------------------------------------------------


class TestDecoratorStack:
    def test_busy_error_propagates_untranslated_with_counters(self):
        """Factory order (fault innermost, metrics, rate limit): an
        injected PersistenceBusyError must surface to the caller as
        exactly that class, and the metrics client above the fault
        client must count it like a real backend error."""
        scope = Scope()
        sched = FaultSchedule(seed=CHAOS_SEED, metrics=scope, rules=[
            FaultRule(site="persistence.metadata", method="list_domains",
                      probability=1.0, max_faults=1,
                      error="PersistenceBusyError"),
        ])
        bundle = wrap_bundle(
            create_memory_bundle(), metrics=scope, max_qps=10_000.0,
            faults=sched,
        )
        # composition is factory-ordered: RateLimited(Metrics(Fault(mgr)))
        assert isinstance(bundle.metadata, RateLimitedClient)
        assert isinstance(bundle.metadata._base, MetricsClient)
        assert isinstance(bundle.metadata._base._base, FaultInjectionClient)

        with pytest.raises(PersistenceBusyError):
            bundle.metadata.list_domains()

        counters = scope.registry.snapshot()["counters"]
        assert any(
            "list_domains.errors.PersistenceBusyError" in k
            for k in counters
        ), counters
        assert any("faults_injected" in k for k in counters), counters

        # max_faults=1 spent: the next call goes through untouched
        assert bundle.metadata.list_domains() == []

    def test_disabled_schedule_installs_nothing(self):
        """Zero-cost guarantee: without a schedule the factory stack is
        exactly what it was before the chaos subsystem existed."""
        bundle = wrap_bundle(create_memory_bundle(), metrics=Scope())
        assert isinstance(bundle.metadata, MetricsClient)
        assert not isinstance(bundle.metadata._base, FaultInjectionClient)
        assert type(bundle.metadata._base).__name__ == (
            "MemoryMetadataManager"
        )


# ---------------------------------------------------------------------------
# queue-task effect witness (the dynamic half of analysis Pass 5)
# ---------------------------------------------------------------------------


class TestEffectWitness:
    """Static/dynamic bidirectional proof for the queue-effect
    footprints: Pass 5 proves AST-extracted ⊆ declared; this suite
    proves RECORDED ⊆ extracted under the ≥10% write-fault storm — the
    conflict matrix the parallel queue will trust is validated under
    execution, retries and torn writes included, not just by AST
    reading."""

    def _drive_with_recorder(self, faults=None):
        from cadence_tpu.testing.effect_witness import EffectRecorder

        rec = EffectRecorder().install()
        try:
            box = ChaosBox(faults=faults, effects=True)
            try:
                _drive_workflows(box, ["wf-1", "wf-2"])
                # the CloseExecution fan-out runs async after the
                # workflow completes: wait for the witness to see it
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if ("transfer", "CloseExecution") in rec.snapshot():
                        break
                    time.sleep(0.02)
            finally:
                box.stop()
        finally:
            rec.uninstall()
        return rec

    def test_recorded_effects_within_static_footprints(self):
        """Witness under the write-fault storm: every persistence call
        recorded during task execution must land inside BOTH the
        declared footprint table and the AST-extracted footprints (the
        stronger direction — it validates the extractor itself)."""
        from cadence_tpu.analysis import queue_effects
        from cadence_tpu.testing.effect_witness import check_witness

        sched = _write_fault_schedule(CHAOS_SEED)
        rec = self._drive_with_recorder(faults=sched)

        snap = rec.snapshot()
        assert snap, "witness recorded nothing — task scope wiring broken"
        assert ("transfer", "CloseExecution") in snap, snap
        # the storm actually hit (same floor as the differential suite)
        assert sched.injected_total() > 0, sched.snapshot()

        assert check_witness(rec) == []  # recorded ⊆ declared
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__
        )))
        extracted = {
            k: fp
            for k, (_, _, fp) in
            queue_effects.handler_footprints(repo_root).items()
            if fp is not None
        }
        assert check_witness(rec, extracted) == []  # recorded ⊆ static

    def test_witness_catches_escaping_effect(self):
        """The checker is falsifiable: a recorded write outside the
        footprint must surface as a violation (a witness that can't
        fail proves nothing)."""
        from cadence_tpu.testing.effect_witness import (
            EffectRecorder,
            check_witness,
        )

        rec = EffectRecorder()
        rec.record("transfer", "DecisionTask", "visibility",
                   "upsert_workflow_execution")
        violations = check_witness(rec)
        assert violations and "visibility" in violations[0], violations

    def test_scope_attribution_drops_unscoped_calls(self):
        """Persistence calls outside any task scope (pump machinery,
        setup) must not be attributed to a task."""
        from cadence_tpu.runtime.queues.effects import (
            record_persistence_call,
            set_recorder,
            task_effect_scope,
        )

        seen = []
        set_recorder(lambda *a: seen.append(a))
        try:
            record_persistence_call("execution", "get_transfer_tasks")
            assert seen == []
            with task_effect_scope("transfer-7", 0):
                record_persistence_call(
                    "execution", "update_workflow_execution"
                )
            record_persistence_call("shard", "update_shard")
        finally:
            set_recorder(None)
        assert seen == [
            ("transfer", "DecisionTask", "execution",
             "update_workflow_execution")
        ]


# ---------------------------------------------------------------------------
# concurrency sanitizer under the storm (CHAOS_SANITIZE=1 sweeps this)
# ---------------------------------------------------------------------------


class TestSanitizedChaos:
    """The runtime lock/race witness under the ≥10% write-fault storm —
    the regime where retries, torn-write recovery and park/drain loops
    walk lock paths a clean run never touches. Zero unwaived findings
    and full cross-validation against the static Pass 3 graph are the
    acceptance bar (ISSUE 12); the witness artifact is refreshed for
    ``--emit-lock-graph``."""

    def test_storm_zero_unwaived_findings(self):
        from cadence_tpu.testing.race_witness import (
            RaceWitness,
            check_race_witness,
            cross_validate,
        )

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))
        sched = _write_fault_schedule(CHAOS_SEED)
        w = RaceWitness().install()
        try:
            box = ChaosBox(faults=sched, sanitize=True)
            try:
                _drive_workflows(box, ["san-wf-1", "san-wf-2"])
            finally:
                box.stop()
        finally:
            w.uninstall()

        # the storm actually hit (same floor as the differential suite)
        assert sched.injected_total() > 0, sched.snapshot()
        # traffic exercised the tracked plane
        assert w.observed_edges(), "no lock edges observed under storm"

        from cadence_tpu.analysis import lock_order

        graph = lock_order.build_graph(repo_root)
        unwaived = check_race_witness(w, repo_root, graph=graph)
        assert unwaived == [], "\n".join(f.format() for f in unwaived)

        # bidirectional proof, dynamic → static direction: every
        # observed edge either exists statically or carries a waiver
        # (cross_validate findings are a subset of the checked set)
        for f in cross_validate(w, repo_root, graph=graph):
            assert f.rule == "RUNTIME-EDGE-UNKNOWN"

        # refresh the artifact input for --emit-lock-graph
        w.save(os.path.join(repo_root, "build", "lock_witness.json"))

    def test_sanitizer_preserves_differential_replay(self):
        """The instrumentation must be an observer: the same seeded
        storm produces byte-identical histories with and without the
        sanitizer installed."""
        from cadence_tpu.testing.race_witness import RaceWitness

        wids = ["san-diff-1", "san-diff-2"]
        plain_box = ChaosBox(faults=_write_fault_schedule(CHAOS_SEED))
        try:
            plain = _drive_workflows(plain_box, wids)
        finally:
            plain_box.stop()

        w = RaceWitness().install()
        try:
            box = ChaosBox(
                faults=_write_fault_schedule(CHAOS_SEED), sanitize=True
            )
            try:
                sanitized = _drive_workflows(box, wids)
            finally:
                box.stop()
        finally:
            w.uninstall()
        assert plain == sanitized


# ---------------------------------------------------------------------------
# schedule semantics
# ---------------------------------------------------------------------------


class TestFaultSchedule:
    def test_same_seed_same_fault_sequence(self):
        def sequence(seed):
            s = FaultSchedule(seed=seed, rules=[
                FaultRule(site="persistence.*", probability=0.3),
            ])
            return [
                s.plan("persistence.execution", "update", 1) is not None
                for _ in range(200)
            ]

        assert sequence(CHAOS_SEED) == sequence(CHAOS_SEED)
        assert sequence(CHAOS_SEED) != sequence(CHAOS_SEED + 1)

    def test_latency_injection_delays_the_call(self):
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.metadata", method="list_domains",
                      probability=1.0, action="latency", latency_s=0.05),
        ])
        bundle = wrap_bundle(create_memory_bundle(), faults=sched)
        t0 = time.monotonic()
        assert bundle.metadata.list_domains() == []
        assert time.monotonic() - t0 >= 0.05

    def test_torn_write_lands_then_raises(self):
        from cadence_tpu.runtime.persistence.records import (
            DomainConfig, DomainInfo, DomainRecord, DomainReplicationConfig,
        )

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.metadata", method="create_domain",
                      probability=1.0, max_faults=1, action="torn_write",
                      error="TimeoutError"),
        ])
        bundle = wrap_bundle(create_memory_bundle(), faults=sched)
        rec = DomainRecord(
            info=DomainInfo(id="d1", name="torn"),
            config=DomainConfig(),
            replication_config=DomainReplicationConfig(),
        )
        with pytest.raises(TimeoutError):
            bundle.metadata.create_domain(rec)
        # the write landed even though the caller saw a timeout
        assert bundle.metadata.get_domain(name="torn").info.id == "d1"

    def test_shard_pin_and_call_window(self):
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="q", shard_id=3, probability=1.0,
                      after_calls=2, max_faults=2),
        ])
        # wrong shard never matches
        assert sched.plan("q", "m", 7) is None
        # first two matching calls are a grace window
        assert sched.plan("q", "m", 3) is None
        assert sched.plan("q", "m", 3) is None
        # then at most max_faults fire
        assert sched.plan("q", "m", 3) is not None
        assert sched.plan("q", "m", 3) is not None
        assert sched.plan("q", "m", 3) is None

    def test_shard_pin_resolves_from_record_argument(self):
        """update_shard(info, previous_range_id) carries its shard id
        on the ShardInfo record, not as an int argument — a shard-
        pinned rule must still resolve and fire there (otherwise a
        pinned chaos run on persistence.shard is a silent no-op)."""
        class _Mgr:
            def update_shard(self, info, previous_range_id=0):
                return "ok"

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.shard", method="update_shard",
                      shard_id=3, probability=1.0,
                      error="PersistenceError"),
        ])
        client = FaultInjectionClient(_Mgr(), sched, manager="shard")
        # wrong shard passes through untouched
        assert client.update_shard(SimpleNamespace(shard_id=7)) == "ok"
        with pytest.raises(PersistenceError):
            client.update_shard(SimpleNamespace(shard_id=3))

    def test_replication_hook_fires_before_any_state_moves(self):
        """The replicator-queue hook runs before the ack/read: a fetch
        that faults must leave persistence completely untouched (the
        pull model's at-least-once contract)."""
        from cadence_tpu.runtime.replication.replicator_queue import (
            ReplicatorQueueProcessor,
        )

        class _Exploding:
            def __getattr__(self, name):
                raise AssertionError(
                    f"persistence touched ({name}) despite injected fault"
                )

        shard = SimpleNamespace(
            shard_id=0, persistence=SimpleNamespace(
                execution=_Exploding(), history=_Exploding()
            ),
            now=lambda: 0,
        )
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="replication.replicator_queue", probability=1.0,
                      error="PersistenceError"),
        ])
        rq = ReplicatorQueueProcessor(shard, faults=sched)
        with pytest.raises(PersistenceError):
            rq.get_replication_messages("remote", 0)


class TestChaosConfig:
    def test_config_builds_armed_schedule(self):
        from cadence_tpu.config import load_config_dict

        cfg = load_config_dict({"chaos": {
            "enabled": True, "seed": 42,
            "rules": [{"site": "persistence.*", "probability": 0.1}],
        }})
        sched = cfg.chaos.build_schedule()
        assert sched is not None and sched.seed == 42 and sched.armed

    def test_config_rejects_bad_rules(self):
        from cadence_tpu.config import ConfigError, load_config_dict

        with pytest.raises(ConfigError):
            load_config_dict({"chaos": {
                "enabled": True,
                "rules": [{"site": "x", "action": "explode"}],
            }})

    def test_disabled_section_builds_nothing(self):
        from cadence_tpu.config import load_config_dict

        cfg = load_config_dict({"chaos": {
            "enabled": False,
            "rules": [{"site": "persistence.*"}],
        }})
        assert cfg.chaos.build_schedule() is None


# ---------------------------------------------------------------------------
# checkpoint plane under write faults (checkpointed incremental replay)
# ---------------------------------------------------------------------------


class TestCheckpointChaos:
    """Chaos rules on ``persistence.checkpoint``: a faulted snapshot
    plane must cost only the optimization (fallback: full replay) —
    rebuild results stay byte-identical to a host rebuild no matter
    which checkpoint reads/writes fail or tear."""

    def _seeded(self, n=5):
        from cadence_tpu.runtime.replication.rebuilder import (
            RebuildRequest,
            StateRebuilder,
        )
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        bundle = create_memory_bundle()
        history = bundle.history
        fz = HistoryFuzzer(seed=CHAOS_SEED)
        reqs = []
        for i in range(n):
            batches = fz.generate(target_events=30 + 10 * (i % 3))
            branch = history.new_history_branch(tree_id=f"ck-run-{i}")
            txn = 1
            for b in batches:
                history.append_history_nodes(
                    branch, b, transaction_id=txn)
                txn += 1
            reqs.append(RebuildRequest(
                domain_id="dom", workflow_id=f"ck-wf-{i}",
                run_id=f"ck-run-{i}",
                branch_token=branch.to_json().encode(),
            ))
        host = [StateRebuilder(history).rebuild(r) for r in reqs]
        return bundle, reqs, host

    def test_checkpoint_write_faults_fall_back_to_full_replay(self):
        from cadence_tpu.checkpoint import (
            CheckpointManager,
            CheckpointPolicy,
        )
        from cadence_tpu.ops.unpack import mutable_state_to_snapshot
        from cadence_tpu.runtime.replication.rebuilder import StateRebuilder

        bundle, reqs, host = self._seeded()
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint", probability=1.0,
                      error="PersistenceError"),
        ])
        scope = Scope()
        wrapped = wrap_bundle(bundle, metrics=scope, faults=sched)
        rb = StateRebuilder(
            wrapped.history,
            checkpoints=CheckpointManager(
                wrapped.checkpoint, CheckpointPolicy(every_events=1),
            ),
            metrics=scope,
        )
        # every lookup and every write faults — results must still be
        # byte-identical to the host rebuild, twice in a row
        for _ in range(2):
            out = rb.rebuild_many(reqs)
            for (h, _, _), (o, _, _) in zip(host, out):
                assert mutable_state_to_snapshot(h) == \
                    mutable_state_to_snapshot(o)
        assert sched.injected_total() > 0, "the storm never happened"
        assert bundle.checkpoint.count_checkpoints() == 0
        assert scope.registry.counter_value("checkpoint_hit") == 0

    def test_torn_checkpoint_write_lands_and_later_resumes(self):
        """torn_write on put_checkpoint: the snapshot LANDS while the
        ack is lost — the write path swallows the error, and the next
        rebuild resumes from the landed snapshot bit-identically."""
        from cadence_tpu.checkpoint import (
            CheckpointManager,
            CheckpointPolicy,
        )
        from cadence_tpu.ops.unpack import mutable_state_to_snapshot
        from cadence_tpu.runtime.replication.rebuilder import StateRebuilder

        bundle, reqs, host = self._seeded()
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint",
                      method="put_checkpoint", probability=1.0,
                      action="torn_write", error="TimeoutError"),
        ])
        scope = Scope()
        wrapped = wrap_bundle(bundle, metrics=scope, faults=sched)
        rb = StateRebuilder(
            wrapped.history,
            checkpoints=CheckpointManager(
                wrapped.checkpoint, CheckpointPolicy(every_events=1),
            ),
            metrics=scope,
        )
        rb.rebuild_many(reqs)
        assert bundle.checkpoint.count_checkpoints() == len(reqs), (
            "torn writes must land"
        )
        warm = rb.rebuild_many(reqs)
        for (h, _, _), (w, _, _) in zip(host, warm):
            assert mutable_state_to_snapshot(h) == \
                mutable_state_to_snapshot(w)
        assert scope.registry.counter_value("checkpoint_hit") == len(reqs)

    def test_corrupted_stored_checkpoint_degrades_to_full_replay(self):
        from cadence_tpu.checkpoint import (
            CheckpointManager,
            CheckpointPolicy,
        )
        from cadence_tpu.ops.unpack import mutable_state_to_snapshot
        from cadence_tpu.runtime.replication.rebuilder import StateRebuilder

        bundle, reqs, host = self._seeded()
        scope = Scope()
        rb = StateRebuilder(
            bundle.history,
            checkpoints=CheckpointManager(
                bundle.checkpoint, CheckpointPolicy(every_events=1),
            ),
            metrics=scope,
        )
        rb.rebuild_many(reqs)
        for r in reqs:
            key = r.branch_token.decode()
            for ck in bundle.checkpoint.list_checkpoints(key):
                bundle.checkpoint._corrupt(key, ck.event_id)
        warm = rb.rebuild_many(reqs)
        for (h, _, _), (w, _, _) in zip(host, warm):
            assert mutable_state_to_snapshot(h) == \
                mutable_state_to_snapshot(w)
        assert scope.registry.counter_value("checkpoint_hit") == 0


# ---------------------------------------------------------------------------
# elastic resharding chaos family (runtime/resharding.py)
# ---------------------------------------------------------------------------


def _drive_concurrent(box, workflow_ids, mid=None, timeout_s=60.0):
    """Start every workflow, fire ``mid()`` while they are in flight,
    wait for all to complete; returns canonical history JSON per id.
    The SAME driver produces the clean baseline — concurrency is part
    of the workload, not a nondeterminism source (frozen clock, pinned
    poll nonce)."""
    w = Worker(box.frontend, DOMAIN, TL, identity="chaos-worker",
               sticky=False)
    w.register_workflow("chaos-wf", _chained_doubler)
    w.register_activity("double", lambda inp: inp * 2)
    w.start()
    try:
        runs = {}
        for wid in workflow_ids:
            runs[wid] = box.frontend.start_workflow_execution(
                StartWorkflowRequest(
                    domain=DOMAIN, workflow_id=wid,
                    workflow_type="chaos-wf", task_list=TL, input=b"x",
                    request_id=f"req-{wid}",
                    execution_start_to_close_timeout_seconds=60,
                )
            )
        if mid is not None:
            mid()
        histories = []
        deadline = time.monotonic() + timeout_s
        for wid in workflow_ids:
            while time.monotonic() < deadline:
                d = box.frontend.describe_workflow_execution(
                    DOMAIN, wid, runs[wid]
                )
                if not d.is_running:
                    break
                time.sleep(0.02)
            else:
                raise AssertionError(f"workflow {wid} did not complete")
            events, _ = box.frontend.get_workflow_execution_history(
                DOMAIN, wid, runs[wid]
            )
            histories.append(json.dumps(
                [e.to_dict() for e in events], sort_keys=True, default=repr
            ))
        return histories
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# geographic link chaos (bandwidth-adaptive replication transport)
# ---------------------------------------------------------------------------


class _GeoAdapter:
    """RemoteClusterClient over the in-process active cluster."""

    def __init__(self, svc):
        self.svc = svc

    def get_replication_messages(self, shard_id, last_retrieved_id,
                                 max_tasks=None):
        return self.svc.get_replication_messages(
            shard_id, last_retrieved_id, cluster="standby",
            max_tasks=max_tasks,
        )

    def get_workflow_history_raw(self, *a):
        return self.svc.get_workflow_history_raw(*a)

    def get_replication_backlog(self, shard_id, last_retrieved_id):
        return self.svc.get_replication_backlog(
            shard_id, last_retrieved_id
        )

    def get_replication_checkpoint(self, *a):
        return self.svc.get_replication_checkpoint(*a)


class GeoChaosBox:
    """Two deterministic in-process clusters: the ACTIVE side drives
    the doubler workload under the ChaosBox discipline (frozen clock,
    pinned poll nonce, optional write-fault storm); the STANDBY pulls
    the replication stream through an optionally degraded
    ``SimulatedLink`` with the bandwidth-adaptive transport attached.
    Replication is drained explicitly (``drain_replication``) so tests
    control exactly when the link starts carrying the backlog."""

    GEO_DOMAIN_ID = "geo-dom-0000"

    def __init__(self, faults=None, link_profile=None, adaptive=True,
                 force_mode=None, min_gap_events=4,
                 snapshot_bytes_prior=4096.0, client_wrap=None,
                 backoff_max_s=0.2):
        from cadence_tpu.cluster import (
            ClusterInformation,
            ClusterMetadata,
        )
        from cadence_tpu.runtime.domains import register_domain
        from cadence_tpu.runtime.replication import (
            AdaptiveTransport,
            HistoryRereplicator,
            ReplicationTaskFetcher,
            ReplicationTaskProcessor,
        )
        from cadence_tpu.testing.faults import chaos_link

        self.clock = FakeTimeSource()
        self.metrics = Scope()          # active-side registry
        self.standby_metrics = Scope()  # standby-side registry

        def meta(name):
            return ClusterMetadata(
                failover_version_increment=10,
                master_cluster_name="active",
                current_cluster_name=name,
                cluster_info={
                    "active": ClusterInformation(
                        initial_failover_version=1),
                    "standby": ClusterInformation(
                        initial_failover_version=2),
                },
            )

        def cluster(name, cluster_faults, scope):
            persistence = create_memory_bundle()
            if cluster_faults is not None:
                persistence = wrap_bundle(
                    persistence, metrics=scope, faults=cluster_faults
                )
            register_domain(
                persistence.metadata, DOMAIN, is_global=True,
                clusters=["active", "standby"],
                active_cluster="active",
                domain_id=self.GEO_DOMAIN_ID, failover_version=1,
            )
            domains = DomainCache(persistence.metadata)
            svc = HistoryService(
                1, persistence, domains,
                single_host_monitor(f"geo-{name}"),
                time_source=self.clock, metrics=scope,
                faults=cluster_faults, cluster_metadata=meta(name),
            )
            hc = HistoryClient(svc.controller)
            matching = MatchingEngine(
                persistence.task, hc,
                poll_request_id_fn=(
                    lambda info: f"rid-{info.workflow_id}-"
                    f"{info.schedule_id}"
                ),
            )
            svc.wire(MatchingClient(matching), hc)
            svc.start()
            return {
                "svc": svc, "hc": hc, "matching": matching,
                "persistence": persistence, "domains": domains,
            }

        self.active = cluster("active", faults, self.metrics)
        self.standby = cluster("standby", None, self.standby_metrics)
        self.frontend = WorkflowHandler(
            DomainHandler(
                self.active["persistence"].metadata, ClusterMetadata()
            ),
            self.active["domains"], self.active["hc"],
            MatchingClient(self.active["matching"]),
        )
        # small emit pages: the first fetch is the link probe, not the
        # whole hydrated backlog in one transfer
        self.active["svc"].controller.get_engine_for_shard(
            0).replicator_queue.batch_size = 4

        base = _GeoAdapter(self.active["svc"])
        self.link = None
        client = base
        if link_profile is not None:
            client = chaos_link(base, link_profile, seed=CHAOS_SEED)
            self.link = client.link
        if client_wrap is not None:
            client = client_wrap(client)
        self.client = client
        self.fetcher = ReplicationTaskFetcher("active", client)
        self.transport = None
        if adaptive:
            self.transport = AdaptiveTransport(
                client, "active", min_gap_events=min_gap_events,
                min_dwell=1,
                snapshot_bytes_prior=snapshot_bytes_prior,
                force_mode=force_mode, metrics=self.standby_metrics,
            )
        engine = self.standby["svc"].controller.get_engine_for_shard(0)
        self.standby_engine = engine
        rerepl = HistoryRereplicator(
            client, engine.ndc_replicator, transport=self.transport,
            metrics=self.standby_metrics,
        )
        self.processor = ReplicationTaskProcessor(
            engine.shard, engine.ndc_replicator, self.fetcher,
            rereplicator=rerepl, metrics=self.standby_metrics,
            transport=self.transport, backoff_max_s=backoff_max_s,
        )

    def drain_replication(self, timeout_s=60.0,
                          swallow=()) -> int:
        """process_once until quiescent; exceptions in ``swallow`` are
        retried (partition windows heal by transfer index)."""
        total = 0
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                n = self.processor.process_once()
            except swallow:
                continue
            total += n
            if n == 0:
                return total
        raise AssertionError("replication never drained")

    def active_history(self, wid, rid):
        engine = self.active["svc"].controller.get_engine(wid)
        events, _ = engine.get_workflow_execution_history(
            DOMAIN, wid, rid
        )
        return json.dumps(
            [e.to_dict() for e in events], sort_keys=True, default=repr
        )

    def standby_history(self, wid, rid):
        events, _ = self.standby_engine.get_workflow_execution_history(
            DOMAIN, wid, rid
        )
        return json.dumps(
            [e.to_dict() for e in events], sort_keys=True, default=repr
        )

    def stop(self):
        self.active["svc"].stop()
        self.active["matching"].shutdown()
        self.standby["svc"].stop()
        self.standby["matching"].shutdown()


_GEO_WIDS = [f"geo-wf-{i}" for i in range(2)]
_GEO_STORM_WIDS = [f"geo-sig-{i}" for i in range(2)]
_GEO_SIGNALS = 18
_GEO_CLEAN: dict = {}  # wid -> standby history, healthy-link baseline


def _drive_geo(box):
    """Drive the deterministic geo workload on the ACTIVE cluster
    (standby not pulling yet — the backlog accumulates): the doubler
    trio to completion under the worker, then a signal-deepened open
    cohort on a pollerless task list — deep histories whose event
    backlog dwarfs a compressed state snapshot, the shape snapshot
    shipping exists for. Returns {wid: run_id}."""
    from cadence_tpu.runtime.api import SignalRequest

    w = Worker(box.frontend, DOMAIN, TL, identity="chaos-worker",
               sticky=False)
    w.register_workflow("chaos-wf", _chained_doubler)
    w.register_activity("double", lambda inp: inp * 2)
    w.start()
    runs = {}
    try:
        for wid in _GEO_WIDS:
            runs[wid] = box.frontend.start_workflow_execution(
                StartWorkflowRequest(
                    domain=DOMAIN, workflow_id=wid,
                    workflow_type="chaos-wf", task_list=TL, input=b"x",
                    request_id=f"req-{wid}",
                    execution_start_to_close_timeout_seconds=60,
                )
            )
        deadline = time.monotonic() + 30.0
        for wid in _GEO_WIDS:
            while time.monotonic() < deadline:
                d = box.frontend.describe_workflow_execution(
                    DOMAIN, wid, runs[wid]
                )
                if not d.is_running:
                    break
                time.sleep(0.02)
            else:
                raise AssertionError(f"workflow {wid} did not complete")
    finally:
        w.stop()
    for wid in _GEO_STORM_WIDS:
        runs[wid] = box.frontend.start_workflow_execution(
            StartWorkflowRequest(
                domain=DOMAIN, workflow_id=wid,
                workflow_type="chaos-wf", task_list="geo-sig-tl",
                input=b"x", request_id=f"req-{wid}",
                execution_start_to_close_timeout_seconds=300,
            )
        )
        for k in range(_GEO_SIGNALS):
            box.frontend.signal_workflow_execution(SignalRequest(
                domain=DOMAIN, workflow_id=wid, signal_name=f"s{k}",
                input=b"x" * 96, identity="geo",
            ))
    return runs


def _geo_clean_baseline():
    """Healthy-link, fault-free run — the static baseline every link
    chaos scenario must converge byte-identically to."""
    if not _GEO_CLEAN:
        box = GeoChaosBox()
        try:
            runs = _drive_geo(box)
            box.drain_replication()
            for wid, rid in runs.items():
                standby = box.standby_history(wid, rid)
                assert standby == box.active_history(wid, rid)
                _GEO_CLEAN[wid] = standby
        finally:
            box.stop()
    return dict(_GEO_CLEAN)


class TestLinkChaos:
    """The degraded-WAN scenario family: a standby cluster behind a
    constrained/lossy link must stay live (adaptive snapshot shipping)
    and converge byte-identical to the healthy-link run once the
    workload quiesces — the geographic-SMR state-transfer adaptation's
    validation suite."""

    def test_constrained_link_write_storm_converges_byte_identical(self):
        """A seeded write-fault storm on the active side plus a link
        throttled well below the backlog's event-stream cost: the
        adaptive controller must demonstrably switch to snapshot
        shipping (mode-switch metric > 0), installs must ride the
        suffix-only resume path (events_replayed_saved > 0), and after
        the storm the standby histories must be byte-identical to the
        healthy-link baseline."""
        from cadence_tpu.testing.faults import LinkProfile

        clean = _geo_clean_baseline()

        sched = _write_fault_schedule(CHAOS_SEED)
        box = GeoChaosBox(
            faults=sched,
            link_profile=LinkProfile(
                bytes_per_s=16384.0, latency_s=0.002,
                jitter_s=0.002, max_sleep_s=0.5,
            ),
        )
        try:
            runs = _drive_geo(box)
            assert sched.injected_total() >= 5, sched.snapshot()
            box.drain_replication()
            for wid, rid in runs.items():
                got = box.standby_history(wid, rid)
                assert got == box.active_history(wid, rid), (
                    f"standby diverged from active for {wid}"
                )
                assert got == clean[wid], (
                    f"standby history for {wid} diverged from the "
                    "healthy-link run"
                )
            reg = box.standby_metrics.registry
            assert box.transport.controller.switches >= 1, (
                "the adaptive controller never switched modes"
            )
            assert reg.counter_value("replication_mode_switches") >= 1
            assert reg.counter_value(
                "replication_snapshots_shipped") >= 1
            assert reg.counter_value("events_replayed_saved") > 0, (
                "snapshot installs must ride the suffix-only resume "
                "path"
            )
            assert box.link.bytes_total > 0
        finally:
            box.stop()

    @pytest.mark.slow
    def test_partition_window_recovers_and_pump_backs_off(self):
        """Transfers inside the partition window raise; the pump's
        capped jittered exponential backoff spaces the retries, and
        once the window passes (transfer-indexed, deterministic) the
        standby converges byte-identical.

        slow-marked (still chaos-marked: every run_chaos.sh sweep runs
        it with --runslow): the backoff ladder + second cluster pair
        are wall-clock-hungry and tier-1's budget is shared; the
        ladder's unit contract stays tier-1 via
        tests/test_replication_transport.py::TestPumpBackoff."""
        from cadence_tpu.testing.faults import LinkProfile

        clean = _geo_clean_baseline()

        box = GeoChaosBox(
            link_profile=LinkProfile(partitions=((2, 10),)),
            adaptive=False, backoff_max_s=0.1,
        )
        try:
            runs = _drive_geo(box)
            # background pump so the backoff ladder (not the test
            # loop) owns the retries
            box.processor.start(interval_s=0.01)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                done = True
                for wid, rid in runs.items():
                    try:
                        if box.standby_history(wid, rid) != clean[wid]:
                            done = False
                            break
                    except Exception:
                        done = False
                        break
                if done:
                    break
                time.sleep(0.05)
            else:
                raise AssertionError(
                    "standby never converged after the partition"
                )
            assert box.link.partitioned_calls >= 1
            assert box.standby_metrics.registry.counter_value(
                "replication_pump_backoffs") >= 1, (
                "partitioned fetches must enter the backoff ladder"
            )
        finally:
            box.processor.stop()
            box.stop()

    @pytest.mark.slow
    def test_torn_snapshot_transfer_falls_back_to_event_shipping(self):
        """The link dies mid-snapshot-blob (every checkpoint transfer
        truncates): the snapshot path must fall back to event shipping
        (fallback metric counts it) and the standby still converges
        byte-identical — degraded optimization, never degraded
        correctness.

        slow-marked for tier-1 wall clock (chaos sweeps run it); the
        decode-side torn-blob rejection stays tier-1 via
        TestCheckpointWireCodec."""
        clean = _geo_clean_baseline()

        class _TornSnapshots:
            def __init__(self, base):
                self._base = base
                self.torn = 0

            def get_replication_checkpoint(self, *a):
                blob = self._base.get_replication_checkpoint(*a)
                if blob:
                    self.torn += 1
                return blob[: len(blob) // 2]

            def __getattr__(self, name):
                return getattr(self._base, name)

        wrapper = {}

        def wrap(client):
            wrapper["w"] = _TornSnapshots(client)
            return wrapper["w"]

        box = GeoChaosBox(
            force_mode="snapshot", client_wrap=wrap,
        )
        try:
            runs = _drive_geo(box)
            box.drain_replication()
            assert wrapper["w"].torn >= 1, (
                "the snapshot path was never even attempted"
            )
            reg = box.standby_metrics.registry
            assert reg.counter_value(
                "replication_snapshot_fallbacks") >= 1
            assert reg.counter_value(
                "replication_snapshots_shipped") == 0
            for wid, rid in runs.items():
                assert box.standby_history(wid, rid) == clean[wid], (
                    f"standby history for {wid} diverged after torn "
                    "snapshot fallback"
                )
        finally:
            box.stop()


_RESHARD_WIDS = [f"rs-wf-{i}" for i in range(5)]
_RESHARD_CLEAN: list = []  # per-process memo: identical workload/driver


class TestLinkChaosTracing:
    """Chaos failures made self-explaining (ISSUE 10): a sampled trace
    from a geo run under deterministic write faults records the fault
    injections as span annotations (testing/faults.py annotates the
    active span), so the trace shows where the faults landed next to
    the work they interrupted."""

    def test_sampled_trace_records_fault_annotations(self):
        from cadence_tpu.runtime.api import SignalRequest
        from cadence_tpu.utils.tracing import TRACER

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.execution",
                      method="update_workflow_execution",
                      probability=1.0, max_faults=2,
                      error="ConditionFailedError"),
        ])
        TRACER.configure(sample_rate=0.0)
        TRACER.clear()
        box = GeoChaosBox(faults=sched)
        try:
            with TRACER.trace("geo_chaos_run", sampled=True) as root:
                trace_id = root.trace_id
                box.frontend.start_workflow_execution(
                    StartWorkflowRequest(
                        domain=DOMAIN, workflow_id="geo-trace-0",
                        workflow_type="chaos-wf",
                        task_list="geo-trace-tl", input=b"x",
                        request_id="req-geo-trace-0",
                        execution_start_to_close_timeout_seconds=300,
                    )
                )
                for k in range(3):
                    box.frontend.signal_workflow_execution(SignalRequest(
                        domain=DOMAIN, workflow_id="geo-trace-0",
                        signal_name=f"s{k}", input=b"x",
                        identity="geo-trace",
                    ))
        finally:
            box.stop()
        spans = [s for s in TRACER.spans() if s.trace_id == trace_id]
        TRACER.clear()
        assert sched.injected_total() == 2, sched.snapshot()
        annotations = [a for s in spans for _, a in s.annotations]
        faults_seen = [a for a in annotations if "fault_injected" in a]
        assert len(faults_seen) == 2, annotations
        assert all(
            "site=persistence.execution" in a for a in faults_seen
        )
        # the interrupted persistence calls are error-tagged spans in
        # the SAME trace — failure and cause sit side by side
        errored = [
            s for s in spans
            if s.tags.get("error") == "ConditionFailedError"
        ]
        assert errored, [s.name for s in spans]


class TestReshardChaos:
    """The ROADMAP's reshard scenario family: split/merge executed
    mid-traffic under ≥10% injected write faults, host kill
    mid-handoff, rollback on a failed plan — with the differential
    byte-identical-replay guarantee held across every reconfiguration
    and handoff shipping checkpoints + suffixes only (asserted via the
    events_replayed_saved metric, never assumed)."""

    def _clean_histories(self):
        """Fault-free static-topology baseline, computed once per
        process (every test drives the identical workload through the
        identical concurrent driver)."""
        if not _RESHARD_CLEAN:
            box = ChaosBox(num_shards=2)
            try:
                _RESHARD_CLEAN.extend(_drive_concurrent(box, _RESHARD_WIDS))
            finally:
                box.stop()
        return list(_RESHARD_CLEAN)

    def test_sampled_trace_records_ownership_retry_spans(self):
        """The reshard failure shape made self-explaining: an
        ownership-lost write fault surfaces as a ``retry.*`` span in
        the sampled trace (client/history.py re-resolution) with the
        injection annotated at the persistence span that raised — a
        mid-handoff trace reads as fault → error → retry → success
        without log correlation."""
        from cadence_tpu.utils.tracing import TRACER

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.execution",
                      method="create_workflow_execution",
                      probability=1.0, max_faults=1,
                      error="ShardOwnershipLostError"),
        ])
        TRACER.configure(sample_rate=0.0)
        TRACER.clear()
        box = ChaosBox(faults=sched, num_shards=1)
        try:
            with TRACER.trace("reshard_chaos_run", sampled=True) as root:
                trace_id = root.trace_id
                box.frontend.start_workflow_execution(
                    StartWorkflowRequest(
                        domain=DOMAIN, workflow_id="trace-retry-0",
                        workflow_type="chaos-wf", task_list=TL,
                        input=b"x", request_id="req-trace-retry-0",
                        execution_start_to_close_timeout_seconds=60,
                    )
                )
        finally:
            box.stop()
        spans = [s for s in TRACER.spans() if s.trace_id == trace_id]
        TRACER.clear()
        assert sched.injected_total() == 1, sched.snapshot()
        retry_spans = [
            s for s in spans if s.name.startswith("retry.")
        ]
        assert retry_spans, [s.name for s in spans]
        assert retry_spans[0].name == "retry.start_workflow_execution"
        assert retry_spans[0].tags.get("error") is None  # it succeeded
        assert any(
            "ownership_lost" in a
            for _, a in retry_spans[0].annotations
        )
        annotations = [a for s in spans for _, a in s.annotations]
        assert any("fault_injected" in a for a in annotations), (
            annotations
        )

    def test_split_then_merge_under_write_faults_byte_identical(self):
        """A split AND a merge executed while the doubler workload runs
        under the standard ≥10% write-fault storm: every workflow
        completes, no queue task is lost or double-applied (a lost task
        stalls a workflow, a duplicate changes its bytes), and every
        history is byte-identical to the fault-free static-topology
        run."""
        clean = self._clean_histories()

        sched = _write_fault_schedule(CHAOS_SEED)
        box = ChaosBox(faults=sched, num_shards=2)
        plans = []

        def mid():
            coord = box.coordinator()
            plans.append(coord.split(0))
            plans.append(coord.merge(2, 0))

        try:
            chaos = _drive_concurrent(box, _RESHARD_WIDS, mid=mid)
            status = box.services[0].controller.describe()
        finally:
            box.stop()

        assert [p.state for p in plans] == ["COMMITTED", "COMMITTED"]
        assert plans[0].kind == "split" and plans[1].kind == "merge"
        assert status["reshard_epoch"] == 2
        assert sched.injected_total() >= 5, sched.snapshot()
        for wid, a, b in zip(_RESHARD_WIDS, clean, chaos):
            assert a == b, f"history for {wid} diverged across reshard"

    def test_handoff_ships_checkpoints_and_suffixes_only(self):
        """The no-full-history-shipping proof: the handoff snapshots
        every OPEN workflow leaving the split shard, and the new owner
        rehydrates them from those ReplayCheckpoints —
        events_replayed_saved covers every open moved event and zero
        suffix events re-replay on a quiesced handoff (under live
        traffic the suffix covers only post-flush writes). Closed runs
        move as rows and are never flushed (nobody replays them hot)."""
        from cadence_tpu.runtime.resharding import ShardMap

        old_map = ShardMap.initial(2)
        new_map, new_id = old_map.split(0)
        # workflow ids that the split moves 0 -> new shard
        moving_wids = []
        i = 0
        while len(moving_wids) < 3:
            wid = f"open-{i}"
            if (old_map.shard_for(wid) == 0
                    and new_map.shard_for(wid) == new_id):
                moving_wids.append(wid)
            i += 1

        box = ChaosBox(num_shards=2)
        try:
            _drive_concurrent(box, _RESHARD_WIDS)  # a closed population
            # open, in-flight workflows (no worker running: they hold a
            # scheduled decision task — the "hot" state a reshard ships)
            for wid in moving_wids:
                box.frontend.start_workflow_execution(StartWorkflowRequest(
                    domain=DOMAIN, workflow_id=wid,
                    workflow_type="chaos-wf", task_list=TL, input=b"x",
                    request_id=f"req-{wid}",
                    execution_start_to_close_timeout_seconds=300,
                ))
            coord = box.coordinator()
            plan = coord.split(0)
            assert plan.state == "COMMITTED"
            assert plan.moved_workflows >= len(moving_wids)
            assert plan.checkpoints_shipped >= len(moving_wids), (
                "every open moved workflow must ship a checkpoint"
            )
            assert plan.suffix_events_replayed == 0, (
                "quiesced handoff must replay no suffix events"
            )
            saved = box.metrics.registry.counter_value(
                "events_replayed_saved"
            )
            assert saved and saved > 0, (
                "checkpoint shipping must be observable in "
                "events_replayed_saved"
            )
        finally:
            box.stop()

    @pytest.mark.slow
    def test_host_kill_mid_handoff_traffic_recovers(self):
        """Two hosts; the one NOT running the coordinator dies right
        after the fence step (the worst window: shards quiesced, rows
        mid-move). The handoff still commits, the survivor re-acquires
        every shard including the dead host's, and the full workload
        completes byte-identically to the clean static run.

        slow-marked (still chaos-marked: every run_chaos.sh sweep runs
        it): the two-host box + kill/re-acquire churn is the family's
        most wall-clock-hungry member and tier-1's budget is shared."""
        clean = self._clean_histories()

        box = ChaosBox(num_shards=2, hosts=2)
        killed = []

        def on_step(step):
            if step == "fenced" and not killed:
                box.kill_host(1)
                killed.append(True)

        plans = []

        def mid():
            coord = box.coordinator(on_step=on_step)
            plans.append(coord.split(0))
            # the dead host is gone from the coordinator's view too
            coord.controllers = [
                s.controller for s in box.services
            ]

        try:
            chaos = _drive_concurrent(box, _RESHARD_WIDS, mid=mid)
            owned = box.services[0].controller.owned_shards()
        finally:
            box.stop()

        assert killed, "the kill hook never fired"
        assert plans[0].state == "COMMITTED"
        assert owned == [0, 1, 2], (
            "survivor must own every shard incl. the split target"
        )
        for wid, a, b in zip(_RESHARD_WIDS, clean, chaos):
            assert a == b, f"history for {wid} diverged after host kill"

    def test_failed_plan_rolls_back_then_retry_succeeds(self):
        """A write fault on the COMMIT record (the epoch LWT write)
        must roll the whole handoff back — old epoch, rows at home,
        fences lifted (no regression: rollback re-acquires under fresh
        leases) — and traffic keeps completing; a later fault-free
        retry commits."""
        from cadence_tpu.runtime.resharding import ReshardError

        # write 1 = PREPARED, 2 = FENCED, 3.. = COMMIT <- faulted past
        # the coordinator's transient-retry budget (3), so the handoff
        # must give up; the ABORT record (call 6) goes through
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.shard",
                      method="set_reshard_state",
                      after_calls=2, max_faults=3, probability=1.0,
                      error="PersistenceError"),
        ])
        box = ChaosBox(faults=sched, num_shards=2)
        outcomes = []

        def mid():
            coord = box.coordinator()
            epoch_before = coord.current_map().epoch
            range_before = box.persistence.shard.get_shard(0).range_id
            with pytest.raises(ReshardError):
                coord.split(0)
            from cadence_tpu.runtime.resharding import load_reshard_state

            _, plan = load_reshard_state(box.persistence.shard)
            outcomes.append((
                plan.state, coord.current_map().epoch, epoch_before,
                box.persistence.shard.get_shard(0).range_id, range_before,
            ))
            retry = coord.split(0)
            outcomes.append(retry.state)

        try:
            chaos = _drive_concurrent(box, _RESHARD_WIDS, mid=mid)
        finally:
            box.stop()

        (state, epoch_after, epoch_before, range_after, range_before), \
            retry_state = outcomes
        assert state == "ABORTED"
        assert epoch_after == epoch_before, "epoch must not advance"
        assert range_after > range_before, (
            "rollback must never regress the fence (lease only bumps)"
        )
        assert retry_state == "COMMITTED"
        assert sched.injected_total() == 3
        # the aborted handoff + retry cost nothing: workload intact
        for wid, a, b in zip(
            _RESHARD_WIDS, self._clean_histories(), chaos
        ):
            assert a == b, f"history for {wid} diverged after rollback"


# ---------------------------------------------------------------------------
# continuous-batching serving chaos family (serving/engine.py)
# (CHAOS_SERVE=1 sweeps this)
# ---------------------------------------------------------------------------


class TestServingChaos:
    """The resident serving engine under the write-fault storm: the
    checkpoint flush plane is ONLY an optimization — a ≥10% fault
    storm on the flush path (and total flush failure, and torn flush
    writes) must leave resident reads byte-identical to the fault-free
    baseline, because the history store stays the source of truth and
    a readmit cold-replays whatever the snapshot plane lost."""

    def _seed_serving(self, bundle, n=4):
        from cadence_tpu.ops import schema as S
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        caps = S.Capacities(max_events=256)
        out = []
        for i in range(n):
            fz = HistoryFuzzer(seed=CHAOS_SEED + 7 * i, caps=caps)
            batches = fz.generate(
                target_events=30 + 10 * (i % 3), close=False
            )
            branch = bundle.history.new_history_branch(
                tree_id=f"serve-run-{i}"
            )
            txn = 1
            for b in batches:
                bundle.history.append_history_nodes(
                    branch, b, transaction_id=txn
                )
                txn += 1
            out.append((
                f"serve-wf-{i}", f"serve-run-{i}",
                branch.to_json().encode(), batches,
            ))
        return caps, out

    def _drive(self, engine, seeded):
        """The serving choreography every arm replays identically:
        seat a prefix, append the Δ suffix, tick, evict everyone (the
        flush storm fires HERE), readmit from the store, read
        resident. Returns {(wf, run): state_row}."""
        from cadence_tpu.ops import schema as S  # noqa: F401

        for wf, run, token, batches in seeded:
            cut = max(1, len(batches) // 2)
            t = engine.admit(
                "dom", wf, run, branch_token=token,
                batches=batches[:cut],
            )
            assert t is not None
            rest = batches[cut:]
            per = max(1, len(rest) // 2) if rest else 1
            for j in range(0, len(rest), per):
                assert engine.append(t, rest[j:j + per])
        engine.tick()
        for wf, run, _, _ in seeded:
            assert engine.evict(wf, run)
        assert engine.occupancy() == 0.0
        rows = {}
        for wf, run, token, _ in seeded:
            t = engine.admit_from_store("dom", wf, run, token)
            assert t is not None
            got = engine.read(wf, run)
            assert got is not None and got.resident
            rows[(wf, run)] = got.state_row
        return rows

    @staticmethod
    def _assert_rows_equal(got, want, msg=""):
        import numpy as np

        from cadence_tpu.ops import schema as S

        for k in S.STATE_ROW_FIELDS:
            np.testing.assert_array_equal(
                got[k], want[k], err_msg=f"{msg} field {k}"
            )

    def _engine(self, bundle, caps, metrics=None):
        from cadence_tpu.checkpoint import (
            CheckpointManager,
            CheckpointPolicy,
        )
        from cadence_tpu.serving import ResidentEngine

        return ResidentEngine(
            lanes=8, caps=caps,
            checkpoints=CheckpointManager(
                bundle.checkpoint,
                CheckpointPolicy(every_events=1, keep_last=4),
            ),
            history=bundle.history, metrics=metrics,
        )

    @pytest.mark.slow
    def test_flush_fault_storm_reads_byte_identical_to_baseline(self):
        # slow-marked (two full drive arms): the CHAOS_SERVE=1 sweep
        # runs it at every seed (--runslow); tier-1 keeps the
        # single-arm total-flush-failure member below
        # fault-free baseline arm
        base_bundle = create_memory_bundle()
        caps, base_seeded = self._seed_serving(base_bundle)
        base_rows = self._drive(
            self._engine(base_bundle, caps), base_seeded
        )
        # storm arm: same deterministic histories, ≥10% of every
        # checkpoint-plane call (flush writes AND admit lookups) throws
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint", probability=0.25,
                      error="PersistenceError"),
        ])
        storm_bundle = wrap_bundle(
            create_memory_bundle(), metrics=Scope(), faults=sched
        )
        _, storm_seeded = self._seed_serving(storm_bundle)
        storm_rows = self._drive(
            self._engine(storm_bundle, caps), storm_seeded
        )
        assert sched.injected_total() > 0, "the storm never happened"
        assert base_rows.keys() == storm_rows.keys()
        for key in base_rows:
            self._assert_rows_equal(
                storm_rows[key], base_rows[key], msg=f"storm {key}"
            )

    def test_total_flush_failure_degrades_to_cold_readmit(self):
        """probability=1.0 on the flush write: every eviction loses its
        snapshot. Readmits must cold-replay from history (zero resume
        seats, zero stored checkpoints) and reads stay byte-identical
        to a cold device rebuild of the full history."""
        from cadence_tpu.ops import schema as S
        from cadence_tpu.ops.pack import pack_lanes
        from cadence_tpu.ops.replay import replay_packed_lanes

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint",
                      method="put_checkpoint", probability=1.0,
                      error="PersistenceError"),
        ])
        bundle = wrap_bundle(
            create_memory_bundle(), metrics=Scope(), faults=sched
        )
        caps, seeded = self._seed_serving(bundle)
        scope = Scope()
        engine = self._engine(bundle, caps, metrics=scope)
        rows = self._drive(engine, seeded)
        reg = scope.registry
        assert reg.counter_value("serving_flush_failures") >= len(seeded)
        assert reg.counter_value("serving_admit_resume") == 0
        assert bundle.checkpoint.count_checkpoints() == 0
        for wf, run, _, batches in seeded:
            pk = pack_lanes([(wf, run, batches)], caps=caps)
            want = S.state_row(replay_packed_lanes(pk), 0)
            self._assert_rows_equal(
                rows[(wf, run)], want, msg=f"cold {wf}"
            )

    @pytest.mark.slow
    def test_torn_flush_lands_and_readmit_resumes(self):
        """slow-marked (two full drive arms — see the storm member);
        every CHAOS_SERVE=1 sweep seed runs it via --runslow.

        torn_write on the flush: the snapshot LANDS while the ack is
        lost (the idempotency reality). The flush counts as failed, but
        the landed snapshot must seed the next admit suffix-only —
        byte-identical reads with resume seats."""
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint",
                      method="put_checkpoint", probability=1.0,
                      action="torn_write", error="TimeoutError"),
        ])
        bundle = wrap_bundle(
            create_memory_bundle(), metrics=Scope(), faults=sched
        )
        caps, seeded = self._seed_serving(bundle)
        scope = Scope()
        engine = self._engine(bundle, caps, metrics=scope)
        rows = self._drive(engine, seeded)
        reg = scope.registry
        assert bundle.checkpoint.count_checkpoints() >= len(seeded), (
            "torn flush writes must land"
        )
        assert reg.counter_value("serving_admit_resume") == len(seeded)
        # baseline arm: fault-free, same histories
        base_bundle = create_memory_bundle()
        _, base_seeded = self._seed_serving(base_bundle)
        base_rows = self._drive(
            self._engine(base_bundle, caps), base_seeded
        )
        for key in base_rows:
            self._assert_rows_equal(
                rows[key], base_rows[key], msg=f"torn {key}"
            )


class TestOverloadChaos:
    """Graceful degradation under sustained overload (ISSUE 15): the
    open-loop harness offers 2× the admitted capacity (Poisson and
    bursty storms) against the fair-admission engine with the ≥10%
    write-fault storm underneath. The bar: every domain makes progress
    (no starvation), admitted-traffic p99 stays in bound while the
    excess is shed, shed-then-retried workflows converge byte-identical
    to an uncontended baseline, retry budgets keep total offered load
    bounded, and the tick pump holds serving_staleness_ms under the
    configured staleness bound."""

    DOMAINS = ("dom-a", "dom-b", "dom-c")

    class _Clock:
        """Virtual clock shared by the harness, the limiter buckets
        and the admission quotas — deterministic overload in
        milliseconds of wall time."""

        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

        def sleep(self, dt):
            self.t += max(dt, 1e-6)

    def _loads(self, n=9, seed=None, deltas=3):
        from cadence_tpu.ops import schema as S
        from cadence_tpu.runtime.persistence.records import BranchToken
        from cadence_tpu.serving import ServeWorkload
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        caps = S.Capacities(max_events=256)
        loads = []
        for i in range(n):
            fz = HistoryFuzzer(
                seed=(seed if seed is not None else CHAOS_SEED) + 31 * i,
                caps=caps,
            )
            batches = fz.generate(
                target_events=24 + 8 * (i % 3), close=False
            )
            cut = max(1, len(batches) // 2)
            rest = batches[cut:]
            per = max(1, len(rest) // deltas) if rest else 1
            loads.append(ServeWorkload(
                domain_id=self.DOMAINS[i % len(self.DOMAINS)],
                workflow_id=f"ovl-wf-{i}", run_id=f"ovl-run-{i}",
                # a real branch token: the eviction/recycle churn then
                # flushes through the (fault-wrapped) checkpoint plane
                # — the write-fault storm's landing site
                branch_token=BranchToken(
                    tree_id=f"ovl-run-{i}", branch_id=f"ovl-br-{i}"
                ).to_json().encode(),
                prefix=batches[:cut],
                deltas=[
                    rest[j:j + per] for j in range(0, len(rest), per)
                ],
            ))
        return caps, loads

    def _engine(self, caps, clock, scope=None, lanes=4, bundle=None):
        from cadence_tpu.checkpoint import (
            CheckpointManager,
            CheckpointPolicy,
        )
        from cadence_tpu.serving import AdmissionPolicy, ResidentEngine

        kw = {}
        if bundle is not None:
            kw = dict(
                checkpoints=CheckpointManager(
                    bundle.checkpoint,
                    CheckpointPolicy(every_events=1, keep_last=2),
                ),
            )
        engine = ResidentEngine(
            lanes=lanes, caps=caps, metrics=scope, idle_ticks=2,
            admission=AdmissionPolicy(
                domain_weights={
                    "dom-a": 8.0, "dom-b": 2.0, "dom-c": 0.5,
                },
                quota_rps=200.0, quota_burst=4,
                aging_boost=1.0, starvation_recycles=6,
            ),
            **kw,
        )
        # the fair queue's quota buckets must ride the virtual clock
        engine._admit_queue._clock = clock
        return engine

    def _drive(self, kind, caps, loads, scope, bundle=None,
               capacity_frac=0.5, qps=200.0, budget=None):
        from cadence_tpu.serving import ArrivalProcess, OpenLoopHarness
        from cadence_tpu.utils.quotas import (
            MultiStageRateLimiter,
            RetryBudget,
        )

        clock = self._Clock()
        engine = self._engine(caps, clock, scope=scope, bundle=bundle)
        capacity = qps * capacity_frac
        harness = OpenLoopHarness(
            engine, loads,
            ArrivalProcess(qps=qps, kind=kind, seed=CHAOS_SEED),
            metrics=scope,
            limiter=MultiStageRateLimiter(
                global_rps=capacity, domain_rps=lambda d: capacity,
                clock=clock, global_burst=4,
            ),
            # effectively unbounded on purpose: THESE members prove
            # CONVERGENCE of shed-then-retried work (every rejection
            # re-offers until it lands, so byte-identity is meaningful
            # for every workload); the dedicated budget member below
            # proves the bounded-offered-load half with a starved
            # budget — at sustained 2x a finite budget rightfully
            # collapses and sheds the excess permanently
            retry_budget=(
                budget if budget is not None
                else RetryBudget(ratio=0.0, cap=1e9, initial=1e9)
            ),
            clock=clock, sleep=clock.sleep,
        )
        out = harness.run()
        return out, engine

    def _storm_bundle(self):
        """The ≥10% write-fault storm: every checkpoint-plane write the
        eviction/recycle churn produces can throw."""
        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.checkpoint", probability=0.2,
                      error="PersistenceError"),
        ])
        return wrap_bundle(
            create_memory_bundle(), metrics=Scope(), faults=sched
        ), sched

    def _assert_rows_match_cold(self, engine, loads, caps, msg):
        """Every workload — including every shed-then-retried one —
        must converge byte-identical to its uncontended baseline (a
        cold full-history replay). Workloads evicted by the lane churn
        re-seat one at a time (their flushed/faulted checkpoints may
        resume-seed or cold-replay; both must land the same bytes)."""
        import numpy as np

        from cadence_tpu.ops import schema as S
        from cadence_tpu.ops.pack import pack_lanes
        from cadence_tpu.ops.replay import replay_packed_lanes

        for w in loads:
            full = list(w.prefix) + [b for d in w.deltas for b in d]
            # evict first: admit dedups by key, and a lane still seated
            # from the run would answer at ITS tip instead of seating
            # the full history
            engine.evict(w.workflow_id, w.run_id)
            engine.admit(
                w.domain_id, w.workflow_id, w.run_id,
                branch_token=w.branch_token, batches=full,
            )
            got = engine.read(w.workflow_id, w.run_id)
            assert got is not None, f"{msg}: {w.workflow_id} lost"
            pk = pack_lanes(
                [(w.workflow_id, w.run_id, full)], caps=caps
            )
            want = S.state_row(replay_packed_lanes(pk), 0)
            for k in S.STATE_ROW_FIELDS:
                np.testing.assert_array_equal(
                    got.state_row[k], want[k],
                    err_msg=f"{msg} {w.workflow_id} field {k}",
                )
            engine.evict(w.workflow_id, w.run_id)

    def test_sustained_2x_poisson_degrades_gracefully(self):
        """The headline member: 2× offered load, write-fault storm on
        the flush plane, generous retry budget. Every domain completes,
        admitted p99 stays in bound, every rejection is observable, and
        every shed-then-retried workflow converges byte-identical to
        the uncontended (cold full-replay) state."""
        bundle, sched = self._storm_bundle()
        try:
            caps, loads = self._loads()
            scope = Scope()
            out, engine = self._drive(
                "poisson", caps, loads, scope, bundle=bundle
            )
            reg = scope.registry
            # the storm happened and the excess was rejected
            assert sched.injected_total() > 0, "storm never fired"
            assert reg.counter_value("serve_shed") > 0, (
                "2x load never tripped the limiter"
            )
            assert out["retries"] > 0
            # no starvation: every domain completed work
            for d in self.DOMAINS:
                assert out["domains"].get(d, {}).get("completed", 0) > 0, (
                    f"domain {d} starved: {out['domains']}"
                )
            # the generous budget converged the whole offered set
            assert out["completed"] == out["requests"], out
            assert out["shed"] == 0
            # admitted-traffic p99 in bound: shedding + retry backoff
            # keep the queueing delay bounded (virtual-clock seconds;
            # the bound is ~2 arrival windows of the retried tail)
            stats = reg.timer_stats("serve_decision")
            assert stats.count == out["requests"]
            assert stats.p99 < 2.0, (
                f"admitted p99 {stats.p99:.3f}s out of bound"
            )
            # the fair refill ran and recorded its starvation ages —
            # bounded by aging (well under the virtual run length)
            starv = reg.timer_stats("serving_admit_starvation_age_ms")
            if starv.count:
                assert starv.max_s < 2000.0
            # shed-then-retried workflows byte-identical to uncontended
            self._assert_rows_match_cold(
                engine, loads, caps, "2x-poisson"
            )
        finally:
            bundle.close()

    @pytest.mark.slow
    def test_bursty_storm_all_domains_progress(self):
        """The thundering-herd arrival shape at 2× capacity: bursts
        shed harder, but fairness still feeds every domain and the
        converged rows stay byte-identical. slow-marked: the Poisson
        member keeps the same invariants under tier-1 wall clock; the
        CHAOS_OVERLOAD=1 sweep runs this one at every seed
        (--runslow)."""
        bundle, sched = self._storm_bundle()
        try:
            caps, loads = self._loads(seed=CHAOS_SEED + 7)
            scope = Scope()
            out, engine = self._drive(
                "bursty", caps, loads, scope, bundle=bundle
            )
            reg = scope.registry
            assert reg.counter_value("serve_shed") > 0
            for d in self.DOMAINS:
                assert out["domains"].get(d, {}).get("completed", 0) > 0
            assert out["completed"] == out["requests"]
            assert reg.timer_stats("serve_decision").p99 < 3.0
            self._assert_rows_match_cold(
                engine, loads, caps, "bursty"
            )
        finally:
            bundle.close()

    def test_retry_budget_bounds_offered_load(self):
        """Deny-everything limiter + a finite, success-starved budget:
        total offered load is requests + budget — the retry storm
        cannot amplify. The exhaustion is observable."""
        from cadence_tpu.serving import ArrivalProcess, OpenLoopHarness
        from cadence_tpu.utils.quotas import RetryBudget

        class _DenyAll:
            def allow(self, domain=""):
                return False

            def retry_after_s(self, domain=""):
                return 0.02

        caps, loads = self._loads(n=3)
        clock = self._Clock()
        scope = Scope()
        engine = self._engine(caps, clock, scope=scope)
        budget = RetryBudget(ratio=0.0, cap=8.0, initial=5.0)
        harness = OpenLoopHarness(
            engine, loads, ArrivalProcess(qps=100.0, seed=CHAOS_SEED),
            metrics=scope, limiter=_DenyAll(), retry_budget=budget,
            clock=clock, sleep=clock.sleep,
        )
        out = harness.run()
        assert out["completed"] == 0
        assert out["retries"] == 5  # exactly the seeded budget
        assert out["offered"] == out["requests"] + 5
        assert out["shed"] == out["requests"]
        assert (
            scope.registry.counter_value("retry_budget_exhausted") >= 1
        )

    def test_tick_pump_bounds_staleness_under_write_storm(self):
        """Write-heavy/read-light: events reach lanes ONLY through the
        persist feed, reads never drive ticks — the pump alone must
        compose the debt. A ≥10% fault storm on the catch-up's history
        reads stretches individual cycles; the staleness p99 must stay
        under the bound anyway, and the final rows must be
        byte-identical to the store's full history.

        Determinism discipline: the workload is built from FIXED-SHAPE
        chunks (2 signals + one decision cycle = 5 events, constant
        type set), so the executable set is exactly {k chunks → one
        span-width grid bucket} — the warm phase compiles ALL of them
        up front and jit time can never masquerade as staleness."""
        import numpy as np

        from cadence_tpu.core import history_factory as F
        from cadence_tpu.ops import schema as S
        from cadence_tpu.ops.pack import pack_lanes
        from cadence_tpu.ops.replay import replay_packed_lanes
        from cadence_tpu.serving import ResidentEngine, TickPump

        caps = S.Capacities(max_events=256)
        SECOND = 1_000_000_000
        CHUNKS = 8

        def build_workload():
            """(prefix batches, chunk list); every chunk is the same
            5-event shape so any contiguous chunk span has the same
            type signature."""
            eid = [0]
            t = [1_700_000_000 * SECOND]

            def nxt():
                eid[0] += 1
                return eid[0]

            def tick():
                t[0] += SECOND
                return t[0]

            v = 10

            def cycle():
                sch = nxt()
                out = [[F.decision_task_scheduled(sch, v, t[0])]]
                sta = nxt()
                out.append([F.decision_task_started(
                    sta, v, tick(), scheduled_event_id=sch,
                )])
                out.append([F.decision_task_completed(
                    nxt(), v, tick(), scheduled_event_id=sch,
                    started_event_id=sta,
                )])
                return out

            prefix = [[F.workflow_execution_started(
                nxt(), v, t[0], task_list="tl", workflow_type="pump",
                execution_start_to_close_timeout_seconds=3600,
                task_start_to_close_timeout_seconds=10,
            )]]
            prefix += cycle()
            chunks = []
            for n in range(CHUNKS):
                c = [
                    [F.workflow_execution_signaled(
                        nxt(), v, tick(), signal_name=f"s{n}-{j}",
                    )]
                    for j in range(2)
                ]
                c += cycle()
                chunks.append(c)
            return prefix, chunks

        prefix, chunks = build_workload()
        full_batches = list(prefix) + [b for c in chunks for b in c]

        # warm phase: compile every executable the measured round can
        # touch — the seat shape, and one compose per chunk-span width
        # (a fault-stalled catch-up composes up to ALL CHUNKS chunks in
        # one step, so every k is reachable)
        warm_engine = ResidentEngine(lanes=2, caps=caps)
        for k in range(1, CHUNKS + 1):
            t = warm_engine.admit(
                "dom", f"warm-wf-{k}", f"warm-run-{k}", batches=prefix
            )
            assert t is not None
            assert warm_engine.append(
                t, [b for c in chunks[:k] for b in c]
            )
            warm_engine.tick()
            assert warm_engine.evict(f"warm-wf-{k}", f"warm-run-{k}")

        sched = FaultSchedule(seed=CHAOS_SEED, rules=[
            FaultRule(site="persistence.history",
                      method="read_history_branch", probability=0.15,
                      error="PersistenceError"),
        ])
        bundle = wrap_bundle(
            create_memory_bundle(), metrics=Scope(), faults=sched
        )
        try:
            scope = Scope()
            engine = ResidentEngine(
                lanes=4, caps=caps, history=bundle.history,
                metrics=scope,
            )
            sched.disarm()  # clean seeding; the storm hits the pump
            seeded = []
            for i in range(3):
                branch = bundle.history.new_history_branch(
                    tree_id=f"pump-run-{i}"
                )
                txn = 1
                for b in prefix:
                    bundle.history.append_history_nodes(
                        branch, b, transaction_id=txn
                    )
                    txn += 1
                t = engine.admit(
                    "dom", f"pump-wf-{i}", f"pump-run-{i}",
                    branch_token=branch.to_json().encode(),
                    batches=prefix,
                )
                assert t is not None
                seeded.append((i, branch, txn))
            sched.arm()
            txns = {i: txn for i, _, txn in seeded}
            pump = TickPump(engine, 0.01, metrics=scope).start()
            try:
                # the write-heavy phase: durable chunk writes + one
                # O(1) marker each, round-robin over the lanes — never
                # a read, never an explicit tick
                for c in range(CHUNKS):
                    for i, branch, _ in seeded:
                        for b in chunks[c]:
                            bundle.history.append_history_nodes(
                                branch, b, transaction_id=txns[i]
                            )
                            txns[i] += 1
                        engine.on_persisted(
                            "dom", f"pump-wf-{i}", f"pump-run-{i}",
                            chunks[c][-1][-1].event_id + 1,
                        )
                        time.sleep(0.004)
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    with engine._lock:
                        dirty = any(
                            l is not None and (
                                l.pending
                                or l.behind_through > l.next_staged
                            )
                            for l in engine._slots
                        )
                    if not dirty:
                        break
                    time.sleep(0.01)
                assert not dirty, "pump never composed the debt"
            finally:
                pump.stop()
            assert sched.injected_total() > 0, "storm never fired"
            stats = scope.registry.timer_stats("serving_staleness_ms")
            assert stats.count >= 3
            # the bound: pump cadence 10ms + fault-retry cycles, every
            # compose executable pre-compiled — tight vs the unbounded
            # pre-pump reality, with slack for a loaded CI host
            assert stats.p99 < 750.0, (
                f"staleness p99 {stats.p99:.1f}ms out of bound"
            )
            sched.disarm()
            for i, branch, _ in seeded:
                got = engine.read(f"pump-wf-{i}", f"pump-run-{i}")
                assert got is not None and got.resident
                pk = pack_lanes(
                    [(f"pump-wf-{i}", f"pump-run-{i}", full_batches)],
                    caps=caps,
                )
                want = S.state_row(replay_packed_lanes(pk), 0)
                for k in S.STATE_ROW_FIELDS:
                    np.testing.assert_array_equal(
                        got.state_row[k], want[k],
                        err_msg=f"pump wf {i} field {k}",
                    )
        finally:
            bundle.close()


# ---------------------------------------------------------------------------
# parallel queue executor under the write-fault storm (CHAOS_PARQUEUE=1)
# ---------------------------------------------------------------------------


class TestParallelQueueChaos:
    """Differential proof for the conflict-keyed wave executor
    (runtime/queues/parallel.py): draining the same topology through
    parallel waves under the ≥10% write-fault storm must produce
    byte-identical workflow histories to the sequential drain, and the
    effect witness must show every wave's recorded persistence calls
    inside the declared footprints — the commutativity matrix validated
    under execution, not just by AST reading. scripts/run_chaos.sh
    sweeps this family across seeds with CHAOS_PARQUEUE=1."""

    def test_parallel_drain_byte_identical_under_write_faults(self):
        wids = ["wf-1", "wf-2", "wf-3"]

        seq_sched = _write_fault_schedule(CHAOS_SEED)
        seq_box = ChaosBox(faults=seq_sched)
        try:
            sequential = _drive_workflows(seq_box, wids)
        finally:
            seq_box.stop()

        par_sched = _write_fault_schedule(CHAOS_SEED)
        par_box = ChaosBox(faults=par_sched, queue_parallel=4)
        try:
            parallel = _drive_workflows(par_box, wids)
            ex = par_box.queue_executor
            assert ex is not None and not ex.degraded
            # the executor actually carried the drain (the sequential
            # pump threads don't exist in this mode)
            assert ex.cycles > 0 and ex.tasks > 0 and ex.waves > 0
        finally:
            par_box.stop()

        # both storms actually happened (the differential's floor)
        assert seq_sched.injected_total() >= 5, seq_sched.snapshot()
        assert par_sched.injected_total() >= 5, par_sched.snapshot()

        for wid, a, b in zip(wids, sequential, parallel):
            assert a == b, (
                f"history for {wid} diverged under the parallel drain"
            )

    def test_effect_witness_clean_under_parallel_waves(self):
        """wrap_bundle(effects=True) + parallel drain: every
        persistence call recorded inside any wave's task scope must
        land inside the declared footprint table (recorded ⊆ declared
        — the safety direction the wave scheduler trusts)."""
        from cadence_tpu.testing.effect_witness import (
            EffectRecorder,
            check_witness,
        )

        sched = _write_fault_schedule(CHAOS_SEED)
        rec = EffectRecorder().install()
        try:
            box = ChaosBox(
                faults=sched, effects=True, queue_parallel=4
            )
            try:
                _drive_workflows(box, ["wf-1", "wf-2"])
                # the CloseExecution fan-out runs async after the
                # workflow completes: wait for the witness to see it
                deadline = time.monotonic() + 10.0
                while time.monotonic() < deadline:
                    if ("transfer", "CloseExecution") in rec.snapshot():
                        break
                    time.sleep(0.02)
                assert not box.queue_executor.degraded
                assert box.queue_executor.tasks > 0
            finally:
                box.stop()
        finally:
            rec.uninstall()

        snap = rec.snapshot()
        assert snap, "witness recorded nothing — wave scope wiring broken"
        assert ("transfer", "CloseExecution") in snap, snap
        assert sched.injected_total() > 0, sched.snapshot()
        assert check_witness(rec) == []  # recorded ⊆ declared

"""Continuous-batching serving engine: differential, lifecycle, SLOs.

The subsystem's correctness bar is byte identity: a resident lane's
state after K O(Δ) appends must equal a cold batched rebuild of the
full history exactly — for appended Δs, recycle-then-readmit, and
checkpoint-resume seeding. Plus the safety rails: the generation stamp (a
stale append can never land on a recycled slot), the shared
compiled-shape grid (the serving tick and the storm rebuild path pick
identical executables), the persist feed (O(1) on the persist path,
O(Δ) at the next tick), and the open-loop SLO harness's accounting.
"""

from __future__ import annotations

import numpy as np
import pytest

from cadence_tpu.checkpoint import (
    CheckpointManager,
    CheckpointPolicy,
    MemoryCheckpointStore,
)
from cadence_tpu.ops import schema as S
from cadence_tpu.ops.grid import grid_points, round_scan_len, staging_depth
from cadence_tpu.ops.pack import pack_histories, pack_lanes
from cadence_tpu.ops.replay import replay_packed
from cadence_tpu.runtime.persistence.memory import create_memory_bundle
from cadence_tpu.runtime.persistence.records import BranchToken
from cadence_tpu.serving import (
    ArrivalProcess,
    OpenLoopHarness,
    ResidentEngine,
    ServeWorkload,
)
from cadence_tpu.testing.event_generator import HistoryFuzzer
from cadence_tpu.utils.metrics import Scope

CAPS = S.Capacities(max_events=256)


def _fuzz(n, seed=11, target=40, close=False):
    out = []
    for i in range(n):
        fz = HistoryFuzzer(seed=seed + i, caps=CAPS)
        out.append((
            f"wf-{i}", f"run-{i}",
            fz.generate(target_events=target + (i * 13) % 60, close=close),
        ))
    return out


def _branch_token(i):
    return BranchToken(
        tree_id=f"run-{i}", branch_id=f"branch-{i}"
    ).to_json().encode()


def _cold_row(wf, run, batches):
    pk = pack_histories([(wf, run, batches)], caps=CAPS)
    return S.state_row(replay_packed(pk), 0)


def _assert_rows_equal(got_row, want_row, msg=""):
    for k in S.STATE_ROW_FIELDS:
        np.testing.assert_array_equal(
            got_row[k], want_row[k], err_msg=f"{msg} field {k}"
        )


def _split(batches, k):
    """prefix + k Δ groups covering the rest (each ≥ 1 batch)."""
    cut = max(1, len(batches) // 2)
    prefix, rest = batches[:cut], batches[cut:]
    if not rest:
        return prefix, []
    per = max(1, len(rest) // k)
    deltas = [rest[j : j + per] for j in range(0, len(rest), per)]
    return prefix, deltas


# ---------------------------------------------------------------------------
# the four seeding cases: resident-after-K-appends == cold full rebuild
# ---------------------------------------------------------------------------


class TestResidentDifferential:
    def _drive_and_compare(self, hists, engine, k=3, msg=""):
        tickets = {}
        splits = {}
        for wf, run, batches in hists:
            prefix, deltas = _split(batches, k)
            t = engine.admit("dom", wf, run, batches=prefix)
            assert t is not None, f"{msg}: admit failed for {wf}"
            tickets[(wf, run)] = t
            splits[(wf, run)] = deltas
        # K append rounds with a tick after each — every tick composes
        # ONE fused batch over all lanes that staged a Δ that round
        rounds = max(len(d) for d in splits.values())
        for r in range(rounds):
            for (wf, run), deltas in splits.items():
                if r < len(deltas):
                    assert engine.append(tickets[(wf, run)], deltas[r])
            engine.tick()
        for wf, run, batches in hists:
            got = engine.read(wf, run)
            assert got is not None and got.resident, f"{msg}: {wf} miss"
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg=f"{msg} {wf}",
            )

    @pytest.mark.parametrize("seed", [21, 33])
    def test_appends_byte_identical(self, seed):
        # 3 fuzzed histories: the byte-identity proof is per-history,
        # and the batch width grid-rounds to the same executable as a
        # wider cohort — breadth rides the slow-marked multi-seed
        # sweep + the CHAOS_SERVE storms, not the tier-1 wall clock
        hists = _fuzz(3, seed=seed, close=False)
        self._drive_and_compare(
            hists, ResidentEngine(lanes=8, caps=CAPS), msg=f"seed {seed}",
        )

    def test_recycle_then_readmit_byte_identical(self):
        hists = _fuzz(3, seed=55, close=False)
        engine = ResidentEngine(lanes=8, caps=CAPS)
        # seat + append half, evict (recycle), readmit FULL, compare
        for wf, run, batches in hists:
            prefix, deltas = _split(batches, 2)
            t = engine.admit("dom", wf, run, batches=prefix)
            assert engine.append(t, deltas[0] if deltas else [])
        engine.tick()
        for wf, run, _ in hists:
            assert engine.evict(wf, run)
        assert engine.occupancy() == 0.0
        for wf, run, batches in hists:
            t = engine.admit("dom", wf, run, batches=batches)
            assert t is not None
            got = engine.read(wf, run)
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg=f"recycle {wf}",
            )

    def test_checkpoint_resume_seeding_byte_identical(self):
        store = MemoryCheckpointStore()
        mgr = CheckpointManager(
            store, policy=CheckpointPolicy(every_events=1, keep_last=4)
        )
        engine = ResidentEngine(lanes=8, caps=CAPS, checkpoints=mgr)
        hists = _fuzz(3, seed=61, close=False)
        scope = Scope()
        engine._metrics = scope.tagged(layer="serving")
        # round 1: seat cold + append + evict — flush writes snapshots
        for i, (wf, run, batches) in enumerate(hists):
            prefix, deltas = _split(batches, 2)
            t = engine.admit(
                "dom", wf, run, branch_token=_branch_token(i),
                batches=prefix,
            )
            for d in deltas:
                assert engine.append(t, d)
        engine.tick()
        for wf, run, _ in hists:
            assert engine.evict(wf, run)
        assert store.count_checkpoints() >= len(hists)
        # round 2: readmit with the full history — the checkpoint
        # consult must seat every lane from its snapshot (suffix-only)
        out = engine.admit_many([
            dict(domain_id="dom", workflow_id=wf, run_id=run,
                 branch_token=_branch_token(i), batches=batches)
            for i, (wf, run, batches) in enumerate(hists)
        ])
        assert all(t is not None for t in out.values())
        reg = scope.registry
        assert reg.counter_value("serving_admit_resume") == len(hists)
        for wf, run, batches in hists:
            got = engine.read(wf, run)
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg=f"resume {wf}",
            )

    @pytest.mark.slow
    def test_fuzzed_multi_seed_sweep(self):
        # the fuzz sweep the acceptance bar names: several seeds, each
        # driven through K appends and compared byte-for-byte.
        # slow-marked: extra breadth over the four tier-1 seeding cases
        # (compile-dominated); CHAOS_SERVE=1 sweeps seeds further
        for seed in (101, 202, 303):
            hists = _fuzz(3, seed=seed, close=False)
            self._drive_and_compare(
                hists, ResidentEngine(lanes=4, caps=CAPS),
                msg=f"seed{seed}",
            )


# ---------------------------------------------------------------------------
# generation stamp: a stale append can never land on a recycled slot
# ---------------------------------------------------------------------------


class TestGenerationStamp:
    def test_stale_ticket_rejected_after_recycle(self):
        scope = Scope()
        engine = ResidentEngine(lanes=2, caps=CAPS, metrics=scope)
        (wf, run, batches), (wf2, run2, batches2) = _fuzz(2, seed=71)
        prefix, deltas = _split(batches, 2)
        stale = engine.admit("dom", wf, run, batches=prefix)
        assert stale is not None
        engine.tick()
        assert engine.evict(wf, run)  # generation bumps
        # the slot is re-seated by ANOTHER workflow
        fresh = engine.admit("dom", wf2, run2, batches=batches2)
        assert fresh is not None
        before = engine.read(wf2, run2).state_row
        # the stale ticket must be rejected whole — not silently
        # dropped into the new tenant's lane
        assert engine.append(stale, deltas[0]) is False
        assert (
            scope.registry.counter_value("serving_stale_appends") >= 1
        )
        engine.tick()
        _assert_rows_equal(
            engine.read(wf2, run2).state_row, before,
            msg="recycled slot mutated by a stale append",
        )

    def test_key_append_after_eviction_is_stale(self):
        engine = ResidentEngine(lanes=2, caps=CAPS)
        wf, run, batches = _fuzz(1, seed=77)[0]
        prefix, deltas = _split(batches, 2)
        engine.admit("dom", wf, run, batches=prefix)
        assert engine.evict(wf, run)
        assert engine.append((wf, run), deltas[0]) is False


# ---------------------------------------------------------------------------
# eviction / recycle / flush lifecycle
# ---------------------------------------------------------------------------


class TestServingLifecycle:
    def test_admission_queue_refills_on_eviction(self):
        engine = ResidentEngine(lanes=1, caps=CAPS, idle_ticks=1)
        hists = _fuzz(2, seed=81, close=False)
        wf0, run0, b0 = hists[0]
        wf1, run1, b1 = hists[1]
        t0 = engine.admit("dom", wf0, run0, batches=b0)
        assert t0 is not None
        # every lane busy: the second admit queues
        assert engine.admit("dom", wf1, run1, batches=b1) is None
        assert engine.describe()["queued"] == 1
        # idle_ticks=1 → the untouched lane evicts, the queue refills
        # in the SAME tick (a second tick would LRU-evict the newly
        # seated tenant too — that's the policy working)
        engine.tick()
        assert engine.describe()["queued"] == 0
        got = engine.read(wf1, run1)
        assert got is not None and got.resident
        _assert_rows_equal(got.state_row, _cold_row(wf1, run1, b1))

    def test_on_close_eviction_flushes_checkpoint(self):
        store = MemoryCheckpointStore()
        mgr = CheckpointManager(
            store, policy=CheckpointPolicy(every_events=1, keep_last=2)
        )
        engine = ResidentEngine(lanes=4, caps=CAPS, checkpoints=mgr)
        wf, run, batches = _fuzz(1, seed=91, target=30, close=True)[0]
        t = engine.admit(
            "dom", wf, run, branch_token=_branch_token(0),
            batches=batches,
        )
        assert t is not None
        # the seat committed a CLOSED row; the next tick must evict it
        # and flush the final state through the checkpoint plane
        engine.tick()
        assert engine.describe()["seated"] == 0
        assert store.count_checkpoints() == 1

    def test_flush_failure_degrades_not_fatal(self):
        class _Broken:
            def put_checkpoint(self, ckpt):
                raise RuntimeError("store down")

            def prune_tree(self, tree_id, keep):
                return 0

            def list_checkpoints(self, key):
                return []

            def list_tree_checkpoints(self, tree_id):
                return []

        scope = Scope()
        engine = ResidentEngine(
            lanes=2, caps=CAPS,
            checkpoints=CheckpointManager(_Broken()), metrics=scope,
        )
        wf, run, batches = _fuzz(1, seed=95, close=False)[0]
        engine.admit(
            "dom", wf, run, branch_token=_branch_token(0),
            batches=batches,
        )
        assert engine.evict(wf, run)  # flush fails, evict succeeds
        assert (
            scope.registry.counter_value("serving_flush_failures") == 1
        )
        # the engine still serves: readmit cold-replays
        t = engine.admit("dom", wf, run, batches=batches)
        assert t is not None
        _assert_rows_equal(
            engine.read(wf, run).state_row, _cold_row(wf, run, batches)
        )

    def test_drain_flushes_every_lane(self):
        store = MemoryCheckpointStore()
        engine = ResidentEngine(
            lanes=4, caps=CAPS,
            checkpoints=CheckpointManager(
                store, policy=CheckpointPolicy(keep_last=2)
            ),
        )
        hists = _fuzz(3, seed=99, close=False)
        for i, (wf, run, batches) in enumerate(hists):
            prefix, deltas = _split(batches, 2)
            t = engine.admit(
                "dom", wf, run, branch_token=_branch_token(i),
                batches=prefix,
            )
            for d in deltas:
                engine.append(t, d)
        # drain composes the pending Δs first, then flushes: the stored
        # snapshots must be at the FULL history tip
        out = engine.drain()
        assert out == {
            "flushed": 3, "flush_failed": 0, "queued_dropped": 0
        }
        assert engine.describe()["seated"] == 0
        assert store.count_checkpoints() == 3
        for i, (wf, run, batches) in enumerate(hists):
            cks = store.list_checkpoints(_branch_token(i).decode())
            want = _cold_row(wf, run, batches)
            assert cks, f"no flushed checkpoint for {wf}"
            _assert_rows_equal(cks[0].state_row, want, msg=f"drain {wf}")


# ---------------------------------------------------------------------------
# the persist feed: O(1) on the persist path, O(Δ) at the next tick
# ---------------------------------------------------------------------------


class TestPersistFeed:
    def _seed_store(self, history, batches, tree="run-0"):
        branch = history.new_history_branch(tree_id=tree)
        txn = 1
        for b in batches:
            history.append_history_nodes(branch, b, transaction_id=txn)
            txn += 1
        return branch, txn

    def test_on_persisted_catches_up_suffix_only(self):
        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=111, close=False)[0]
            cut = max(1, len(batches) // 2)
            branch, txn = self._seed_store(
                bundle.history, batches[:cut]
            )
            scope = Scope()
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history,
                metrics=scope,
            )
            token = branch.to_json().encode()
            t = engine.admit(
                "dom", wf, run, branch_token=token,
                batches=batches[:cut],
            )
            assert t is not None
            # history advances AFTER the seat (the engine's persist
            # path); the feed is one O(1) marker per durable write
            for b in batches[cut:]:
                bundle.history.append_history_nodes(
                    branch, b, transaction_id=txn
                )
                txn += 1
                engine.on_persisted(
                    "dom", wf, run, b[-1].event_id + 1
                )
            got = engine.read(wf, run)  # dirty lane composes first
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg="persist feed",
            )
            # O(Δ) proof: the composed events are the suffix, not the
            # full history
            reg = scope.registry
            suffix_events = sum(len(b) for b in batches[cut:])
            assert (
                reg.counter_value("serving_events_replayed")
                == suffix_events
            )
        finally:
            bundle.close()

    def test_close_hint_evicts_after_catch_up(self):
        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(
                1, seed=117, target=30, close=True
            )[0]
            cut = max(1, len(batches) - 2)
            branch, txn = self._seed_store(
                bundle.history, batches[:cut]
            )
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history
            )
            engine.admit(
                "dom", wf, run,
                branch_token=branch.to_json().encode(),
                batches=batches[:cut],
            )
            for b in batches[cut:]:
                bundle.history.append_history_nodes(
                    branch, b, transaction_id=txn
                )
                txn += 1
            engine.on_persisted(
                "dom", wf, run, batches[-1][-1].event_id + 1,
                running=False,
            )
            engine.tick()   # catch-up + compose (the close lands)
            engine.tick()   # on-close eviction
            assert engine.describe()["seated"] == 0
        finally:
            bundle.close()

    def test_unseated_workflow_is_noop(self):
        engine = ResidentEngine(lanes=2, caps=CAPS)
        engine.on_persisted("dom", "nobody", "nowhere", 10)
        assert engine.describe()["seated"] == 0


# ---------------------------------------------------------------------------
# compiled-shape discipline: one grid policy for serving AND rebuilds
# ---------------------------------------------------------------------------


class TestGridPolicy:
    def test_single_shared_policy_function(self):
        # the serving tick, the packer, and the dispatcher must size
        # executables from the SAME function object — re-exports, not
        # copies, so the planes cannot drift
        from cadence_tpu.ops import dispatch as D
        from cadence_tpu.ops import grid as G
        from cadence_tpu.ops import pack as P
        from cadence_tpu.serving import engine as E

        assert P.round_scan_len is G.round_scan_len
        assert D.round_scan_len is G.round_scan_len
        assert E.round_scan_len is G.round_scan_len

    def test_grid_points_enumerate_reachable_shapes(self):
        pts = grid_points(8, 4096)
        for n in range(1, 4097):
            assert round_scan_len(n) in pts or n <= 8
        # ≤ 2 shapes per octave: 8..4096 spans 9 octaves → ≤ 19 points
        assert len(pts) <= 19

    def test_staging_depth_bounds(self):
        assert staging_depth(0) == 1
        assert staging_depth(1) == 1
        assert staging_depth(2) == 2
        assert staging_depth(100) == 2       # double buffering cap
        assert staging_depth(100, depth=4) == 4
        assert staging_depth(3, depth=4) == 3

    def test_serving_tick_executable_set_bounded(self):
        # a storm of ragged Δ widths across many ticks may only compile
        # shapes on the shared grid — the executable-set-boundedness
        # contract the dispatcher already obeys
        engine = ResidentEngine(lanes=16, caps=CAPS)
        shapes = []
        real = engine._replay

        def spy(packed):
            shapes.append(packed.events.shape[:2])
            return real(packed)

        engine._replay = spy
        hists = _fuzz(6, seed=131, close=False)
        tickets = {}
        splits = {}
        for wf, run, batches in hists:
            prefix, deltas = _split(batches, 3)
            tickets[(wf, run)] = engine.admit(
                "dom", wf, run, batches=prefix
            )
            splits[(wf, run)] = deltas
        rounds = max(len(d) for d in splits.values())
        for r in range(rounds):
            # ragged: only a varying subset of lanes stages each round
            for i, ((wf, run), deltas) in enumerate(splits.items()):
                if r < len(deltas) and (i + r) % 3 != 0:
                    engine.append(tickets[(wf, run)], deltas[r])
            engine.tick()
        assert shapes, "no composes observed"
        pts = set(grid_points(8, 1 << 20))
        for lanes, t in shapes:
            assert lanes in pts, f"lane dim {lanes} off-grid"
            assert t in pts, f"scan len {t} off-grid"


# ---------------------------------------------------------------------------
# open-loop harness
# ---------------------------------------------------------------------------


class _VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-6)


class TestOpenLoopHarness:
    def test_arrival_validation(self):
        with pytest.raises(ValueError):
            ArrivalProcess(qps=0.0).validate()
        with pytest.raises(ValueError):
            ArrivalProcess(qps=10, kind="weird").validate()
        with pytest.raises(ValueError):
            ArrivalProcess(
                qps=10, kind="bursty", burst_frac=1.5
            ).validate()
        with pytest.raises(ValueError):
            ArrivalProcess(
                qps=10, kind="bursty", burst_factor=0.5
            ).validate()

    def test_poisson_schedule_deterministic_and_on_rate(self):
        p = ArrivalProcess(qps=100.0, seed=5)
        a, b = p.schedule(2000), p.schedule(2000)
        assert a == b, "same seed must give the same schedule"
        assert all(x < y for x, y in zip(a, a[1:]))
        mean_gap = a[-1] / len(a)
        assert 0.008 < mean_gap < 0.012  # ≈ 1/qps ± 20%

    def test_bursty_schedule_sustains_target_rate(self):
        p = ArrivalProcess(
            qps=100.0, kind="bursty", seed=9, burst_factor=4.0,
            burst_frac=0.2, burst_period_s=0.5,
        )
        sched = p.schedule(4000)
        rate = len(sched) / sched[-1]
        assert 80 < rate < 120  # average holds the target
        # burst windows are denser than off-windows
        in_burst = sum(1 for t in sched if (t % 0.5) < 0.1)
        assert in_burst / len(sched) > 0.35  # 20% of time, >35% load

    def _loads(self, n=3, seed=141):
        loads = []
        for i, (wf, run, batches) in enumerate(
            _fuzz(n, seed=seed, close=False)
        ):
            prefix, deltas = _split(batches, 3)
            loads.append(ServeWorkload(
                domain_id="dom", workflow_id=wf, run_id=run,
                branch_token=b"", prefix=prefix, deltas=deltas,
            ))
        return loads

    def test_open_loop_run_completes_and_records_latency(self):
        clock = _VirtualClock()
        scope = Scope()
        engine = ResidentEngine(lanes=4, caps=CAPS)
        loads = self._loads()
        h = OpenLoopHarness(
            engine, loads, ArrivalProcess(qps=50.0, seed=3),
            metrics=scope, clock=clock, sleep=clock.sleep,
        )
        out = h.run()
        n_requests = sum(len(w.deltas) for w in loads)
        assert out["requests"] == n_requests
        assert out["completed"] == n_requests
        assert out["shed"] == 0
        stats = scope.registry.timer_stats("serve_decision")
        assert stats.count == n_requests
        assert stats.p99 >= stats.p50 >= 0.0
        # the drive left every lane at the full-history tip
        for w in loads:
            got = engine.read(w.workflow_id, w.run_id)
            full = list(w.prefix) + [b for d in w.deltas for b in d]
            _assert_rows_equal(
                got.state_row,
                _cold_row(w.workflow_id, w.run_id, full),
                msg=f"open-loop {w.workflow_id}",
            )

    def test_shed_arrival_heals_by_reseat(self):
        # one shed mid-trajectory must not freeze the workload (every
        # later append gapped->shed) nor diverge it: the harness
        # re-seats at the arrival's position and the run completes with
        # every lane byte-identical to the full cold rebuild
        class _DenyOnce:
            def __init__(self, deny_at):
                self.calls = 0
                self.deny_at = deny_at

            def allow(self, n: int = 1):
                self.calls += 1
                return self.calls != self.deny_at

        clock = _VirtualClock()
        scope = Scope()
        engine = ResidentEngine(lanes=4, caps=CAPS, metrics=scope)
        loads = self._loads()
        h = OpenLoopHarness(
            engine, loads, ArrivalProcess(qps=50.0, seed=3),
            metrics=scope, admission_bucket=_DenyOnce(4),
            clock=clock, sleep=clock.sleep,
        )
        out = h.run()
        assert out["shed"] == 1
        assert out["completed"] == out["requests"] - 1
        reg = scope.registry
        # the engine refused the gapped append (observable), and the
        # harness healed it by re-seating — the byte-identity below is
        # the proof the refusal never froze or diverged the lane
        assert reg.counter_value("serving_gapped_appends") >= 1
        for w in loads:
            got = engine.read(w.workflow_id, w.run_id)
            full = list(w.prefix) + [b for d in w.deltas for b in d]
            _assert_rows_equal(
                got.state_row,
                _cold_row(w.workflow_id, w.run_id, full),
                msg=f"reseat {w.workflow_id}",
            )

    def test_admission_bucket_sheds_load(self):
        class _Deny:
            def allow(self, n: int = 1):
                return False

        clock = _VirtualClock()
        scope = Scope()
        h = OpenLoopHarness(
            ResidentEngine(lanes=4, caps=CAPS), self._loads(),
            ArrivalProcess(qps=50.0, seed=3), metrics=scope,
            admission_bucket=_Deny(), clock=clock, sleep=clock.sleep,
        )
        out = h.run()
        assert out["completed"] == 0
        assert out["shed"] == out["requests"]
        assert (
            scope.registry.counter_value("serve_shed")
            == out["requests"]
        )


# ---------------------------------------------------------------------------
# rebuilder consult: an exact-tip rebuild rehydrates from the lane
# ---------------------------------------------------------------------------


class TestRebuilderServingConsult:
    def _seed(self, bundle, batches, tree="run-0"):
        branch = bundle.history.new_history_branch(tree_id=tree)
        txn = 1
        for b in batches:
            bundle.history.append_history_nodes(
                branch, b, transaction_id=txn
            )
            txn += 1
        return branch

    def test_exact_tip_rebuild_hits_resident_lane(self):
        from cadence_tpu.runtime.replication.rebuilder import (
            RebuildRequest,
            StateRebuilder,
        )

        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=151, close=False)[0]
            branch = self._seed(bundle, batches)
            token = branch.to_json().encode()
            engine = ResidentEngine(lanes=2, caps=CAPS)
            engine.admit("dom", wf, run, branch_token=token,
                         batches=batches)
            tip = int(
                engine.read(wf, run).state_row["exec_info"][
                    S.X_NEXT_EVENT_ID
                ]
            )
            scope = Scope()
            rb = StateRebuilder(
                bundle.history, serving=engine, metrics=scope
            )
            req = RebuildRequest(
                domain_id="dom", workflow_id=wf, run_id=run,
                branch_token=token, next_event_id=tip,
            )
            (ms, transfer, timer), = rb.rebuild_many([req])
            assert (
                scope.registry.counter_value("serving_resident_hits")
                == 1
            )
            # byte identity vs the cold DEVICE rebuild it displaces
            (cold_ms, _, _), = StateRebuilder(
                bundle.history
            ).rebuild_many([req])
            assert ms.snapshot() == cold_ms.snapshot()
        finally:
            bundle.close()

    def test_tip_mismatch_falls_through_to_cold(self):
        from cadence_tpu.runtime.replication.rebuilder import (
            RebuildRequest,
            StateRebuilder,
        )

        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=161, close=False)[0]
            branch = self._seed(bundle, batches)
            token = branch.to_json().encode()
            # the lane holds only a PREFIX: its tip cannot match
            cut = max(1, len(batches) // 2)
            engine = ResidentEngine(lanes=2, caps=CAPS)
            engine.admit("dom", wf, run, branch_token=token,
                         batches=batches[:cut])
            scope = Scope()
            rb = StateRebuilder(
                bundle.history, serving=engine, metrics=scope
            )
            req = RebuildRequest(
                domain_id="dom", workflow_id=wf, run_id=run,
                branch_token=token,
                next_event_id=batches[-1][-1].event_id + 1,
            )
            (ms, _, _), = rb.rebuild_many([req])
            assert (
                scope.registry.counter_value("serving_resident_hits")
                == 0
            )
            (cold_ms, _, _), = StateRebuilder(
                bundle.history
            ).rebuild_many([req])
            assert ms.snapshot() == cold_ms.snapshot()
        finally:
            bundle.close()


# ---------------------------------------------------------------------------
# config section + Onebox acceptance
# ---------------------------------------------------------------------------


class TestServingConfig:
    def test_section_parsing_and_validation(self):
        from cadence_tpu.config.static import (
            ConfigError,
            load_config_dict,
        )

        cfg = load_config_dict(
            {"serving": {"enabled": True, "lanes": 8, "idleTicks": 16}}
        )
        assert cfg.serving.enabled and cfg.serving.lanes == 8
        eng = cfg.serving.build_engine()
        assert eng is not None and eng.lanes == 8
        assert load_config_dict({}).serving.build_engine() is None
        with pytest.raises(ConfigError):
            load_config_dict({"serving": {"lanes": 0}})
        with pytest.raises(ConfigError):
            load_config_dict({"serving": {"bogus": True}})

    def test_bootstrap_wires_serving_into_history_service(self):
        from cadence_tpu.config.bootstrap import start_services
        from cadence_tpu.config.static import load_config_dict

        cfg = load_config_dict(
            {"serving": {"enabled": True, "lanes": 4}}
        )
        s = start_services(
            cfg, services=["history", "matching", "frontend"]
        )
        try:
            assert s.serving is not None
            assert s.history.serving is s.serving
        finally:
            s.stop()


class TestOneboxServing:
    def test_serving_read_miss_then_resident_hit(self):
        import time

        from cadence_tpu.runtime.api import StartWorkflowRequest
        from cadence_tpu.testing.onebox import Onebox
        from cadence_tpu.worker import Worker

        box = Onebox(
            num_shards=2, checkpoints=True, serving=True
        ).start()
        w = Worker(
            box.frontend, "serve-dom", "serve-tl", identity="serve-w"
        )

        def doubler(ctx, inp):
            a = yield ctx.schedule_activity("double", inp)
            return a

        w.register_workflow("serve-wf-type", doubler)
        w.register_activity("double", lambda x: x * 2)
        try:
            box.domain_handler.register_domain("serve-dom")
            w.start()
            run_id = box.frontend.start_workflow_execution(
                StartWorkflowRequest(
                    domain="serve-dom", workflow_id="serve-wf",
                    workflow_type="serve-wf-type", task_list="serve-tl",
                    input=b"\x02", request_id="serve-req",
                    execution_start_to_close_timeout_seconds=60,
                )
            )
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                d = box.frontend.describe_workflow_execution(
                    "serve-dom", "serve-wf", run_id
                )
                if not d.is_running:
                    break
                time.sleep(0.02)
            assert not d.is_running
            dom_id = box.domains.get_by_name("serve-dom").info.id
            first = box.history.serving_read(
                dom_id, "serve-wf", run_id
            )
            assert first is not None and first.resident
            second = box.history.serving_read(
                dom_id, "serve-wf", run_id
            )
            assert second is not None and second.resident
            assert second.snapshot["exec"]["close_status"] != 0
            reg = box.metrics.registry
            assert reg.counter_value("serving_resident_hits") >= 1
            assert reg.counter_value("serving_cold_misses") == 1
        finally:
            w.stop()
            box.stop()

    def test_serving_disabled_raises(self):
        from cadence_tpu.testing.onebox import Onebox

        box = Onebox(num_shards=1, start_worker=False).start()
        try:
            with pytest.raises(RuntimeError, match="serving"):
                box.history.serving_read("d", "wf")
        finally:
            box.stop()


# ---------------------------------------------------------------------------
# the demo script: boot + open-loop burst + clean drain, for real
# ---------------------------------------------------------------------------


class TestServeDemoScript:
    def test_serve_demo_script_smoke(self):
        """scripts/run_serve_demo.sh boots Onebox with serving enabled,
        drives a short open-loop signal burst, and proves resident hits
        plus a clean shutdown drain — invoked for real so the wiring,
        the demo and the script can't rot apart."""
        import json
        import os
        import subprocess
        import sys

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "cadence_tpu.testing.serve_demo",
             "--quiet", "--requests", "12", "--qps", "120"],
            capture_output=True, text=True, cwd=repo, env=env,
            timeout=240,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        lines = [
            ln for ln in r.stdout.strip().splitlines() if ln.strip()
        ]
        assert len(lines) == 1, r.stdout
        out = json.loads(lines[0])
        assert out["resident_hits"] >= out["requests"] - out["workflows"]
        assert out["cold_misses"] <= out["workflows"]
        assert out["drain_flush_failures"] == 0
        assert out["drain_evictions"] >= out["workflows"]


# ---------------------------------------------------------------------------
# append watermark hardening: straddles trim, gaps never compose
# ---------------------------------------------------------------------------


class TestAppendWatermark:
    def _seed_store(self, history, batches, tree="run-0"):
        branch = history.new_history_branch(tree_id=tree)
        txn = 1
        for b in batches:
            history.append_history_nodes(branch, b, transaction_id=txn)
            txn += 1
        return branch, txn

    def test_straddling_append_trims_to_unseen_tail(self):
        # a redelivered batch re-chunked across the staged tip: the
        # staged prefix trims, the unseen tail stages — byte-identical
        wf, run, batches = _fuzz(1, seed=171, close=False)[0]
        cut = max(2, len(batches) // 2)
        engine = ResidentEngine(lanes=2, caps=CAPS)
        t = engine.admit("dom", wf, run, batches=batches[:cut])
        assert t is not None
        # one batch spanning [last staged batch .. first new batch]
        straddle = list(batches[cut - 1]) + list(batches[cut])
        assert engine.append(t, [straddle] + batches[cut + 1 :])
        got = engine.read(wf, run)
        assert got is not None and got.resident
        _assert_rows_equal(
            got.state_row, _cold_row(wf, run, batches), msg="straddle"
        )

    def test_gapped_append_refused_on_bare_lane(self):
        # no history feed to heal a hole: the gapped batch must be
        # refused (False + serving_gapped_appends) and the lane keeps
        # serving the last CONSISTENT row — never a divergent compose
        wf, run, batches = _fuzz(1, seed=173, close=False)[0]
        assert len(batches) >= 3
        scope = Scope()
        engine = ResidentEngine(lanes=2, caps=CAPS, metrics=scope)
        t = engine.admit("dom", wf, run, batches=batches[:1])
        assert t is not None
        assert not engine.append(t, batches[2:])  # skips batches[1]
        assert (
            scope.registry.counter_value("serving_gapped_appends") == 1
        )
        got = engine.read(wf, run)
        assert got is not None and got.resident
        _assert_rows_equal(
            got.state_row, _cold_row(wf, run, batches[:1]),
            msg="gap-refused lane must keep the pre-gap row",
        )

    def test_gapped_append_heals_through_history_catchup(self):
        # with a history feed the gap is DEBT, not refusal: the next
        # tick fetches the whole missing span — byte-identical
        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=175, close=False)[0]
            assert len(batches) >= 3
            branch, _ = self._seed_store(bundle.history, batches)
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history
            )
            t = engine.admit(
                "dom", wf, run,
                branch_token=branch.to_json().encode(),
                batches=batches[:1],
            )
            assert t is not None
            assert engine.append(t, batches[2:])  # gap: batches[1]
            got = engine.read(wf, run)  # catch-up composes the span
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg="gap-heal",
            )
        finally:
            bundle.close()

    def test_queued_admission_refills_at_fresh_tip(self):
        # an admission parked while history advances must seat at the
        # STORE tip on refill, not its stale queue-time batches
        bundle = create_memory_bundle()
        try:
            (wa, ra, ba), (wb, rb, bb) = _fuzz(2, seed=177, close=False)
            cut = max(1, len(bb) // 2)
            branch_b, txn = self._seed_store(
                bundle.history, bb[:cut], tree=rb
            )
            engine = ResidentEngine(
                lanes=1, caps=CAPS, history=bundle.history,
                idle_ticks=1,
            )
            assert engine.admit("dom", wa, ra, batches=ba) is not None
            assert engine.admit(
                "dom", wb, rb,
                branch_token=branch_b.to_json().encode(),
                batches=bb[:cut],
            ) is None  # queued: the only lane is busy
            # history advances while the admission waits
            for b in bb[cut:]:
                bundle.history.append_history_nodes(
                    branch_b, b, transaction_id=txn
                )
                txn += 1
            engine.tick()  # lane A idles out; refill seats B
            got = engine.read(wb, rb)
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wb, rb, bb),
                msg="refill must re-read the tip",
            )
        finally:
            bundle.close()

    def test_persist_during_seat_window_is_not_dropped(self):
        # events persisted WHILE the seat replay runs (lane reserved,
        # not yet seated) must land as catch-up debt, not vanish — the
        # fresh lane would otherwise serve a stale tip until the
        # workflow's next durable write (possibly never)
        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=181, close=False)[0]
            cut = max(1, len(batches) // 2)
            branch, txn = self._seed_store(
                bundle.history, batches[:cut], tree=run
            )
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history
            )
            orig_seat = engine._seat
            state = {"txn": txn}

            def seat_with_persist(seat):
                for b in batches[cut:]:
                    bundle.history.append_history_nodes(
                        branch, b, transaction_id=state["txn"]
                    )
                    state["txn"] += 1
                    engine.on_persisted(
                        "dom", wf, run, b[-1].event_id + 1
                    )
                return orig_seat(seat)

            engine._seat = seat_with_persist
            t = engine.admit(
                "dom", wf, run,
                branch_token=branch.to_json().encode(),
                batches=batches[:cut],
            )
            engine._seat = orig_seat
            assert t is not None
            got = engine.read(wf, run)  # the debt composes first
            assert got is not None and got.resident
            _assert_rows_equal(
                got.state_row, _cold_row(wf, run, batches),
                msg="seat-window persist",
            )
        finally:
            bundle.close()

    def test_unhealable_history_hole_frees_the_lane(self):
        # the store permanently lost a span (pruned/torn history): the
        # catch-up must FREE the lane instead of composing over the
        # hole — divergent state is never served as resident truth
        bundle = create_memory_bundle()
        try:
            wf, run, batches = _fuzz(1, seed=183, close=False)[0]
            assert len(batches) >= 3
            branch = bundle.history.new_history_branch(tree_id=run)
            bundle.history.append_history_nodes(
                branch, batches[0], transaction_id=1
            )
            for i, b in enumerate(batches[2:]):  # batches[1]: the hole
                bundle.history.append_history_nodes(
                    branch, b, transaction_id=2 + i
                )
            scope = Scope()
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history,
                metrics=scope,
            )
            t = engine.admit(
                "dom", wf, run,
                branch_token=branch.to_json().encode(),
                batches=[batches[0]],
            )
            assert t is not None
            engine.on_persisted(
                "dom", wf, run, batches[-1][-1].event_id + 1
            )
            engine.tick()  # the hole survives even the full refetch
            assert engine.occupancy() == 0.0
            reg = scope.registry
            assert (
                reg.counter_value("serving_compose_failures") == 1
            )
        finally:
            bundle.close()

    def test_freed_slot_refills_queue_without_an_eviction(self):
        # a slot freed OUTSIDE the tick's own eviction scan (explicit
        # evict / a failed compose) must still drain the admission
        # queue at the next tick — parked admissions never starve
        (wa, ra, ba), (wb, rb, bb) = _fuzz(2, seed=179, close=False)
        engine = ResidentEngine(lanes=1, caps=CAPS)
        assert engine.admit("dom", wa, ra, batches=ba) is not None
        assert engine.admit("dom", wb, rb, batches=bb) is None  # parked
        assert engine.evict(wa, ra)
        engine.tick()  # nothing evicts THIS tick; refill must still run
        got = engine.read(wb, rb)
        assert got is not None and got.resident
        _assert_rows_equal(
            got.state_row, _cold_row(wb, rb, bb), msg="starved refill"
        )

    def test_unreadable_branch_cold_read_returns_none(self):
        # a branch token the store cannot parse/read must be a counted
        # miss out of the read verb — never an exception
        bundle = create_memory_bundle()
        try:
            scope = Scope()
            engine = ResidentEngine(
                lanes=2, caps=CAPS, history=bundle.history,
                metrics=scope,
            )
            got = engine.read(
                "wf-x", "run-x", branch_token=b"not-a-branch-token"
            )
            assert got is None
            reg = scope.registry
            assert reg.counter_value("serving_cold_read_failures") == 1
            got = engine.read_through(
                "dom", "wf-x", "run-x", b"not-a-branch-token"
            )
            assert got is None
        finally:
            bundle.close()

"""Overload control plane (ISSUE 15): fair admission, retry budgets,
coordinated shedding, and the tick pump.

Property bar for the admission scheduler: deadline aging guarantees a
parked admission seats within K recycles for ANY weight assignment
(starvation-free), and a quota-exceeded domain never blocks a
quota-available one. Retry-budget bar: rejected work backs off and
total offered load stays bounded instead of amplifying the overload.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from cadence_tpu.runtime.api import ServiceBusyError
from cadence_tpu.serving.admission import (
    AdmissionPolicy,
    FairAdmissionQueue,
)
from cadence_tpu.utils.quotas import (
    MultiStageRateLimiter,
    RetryBudget,
    TokenBucket,
)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# TokenBucket / MultiStageRateLimiter satellite fixes
# ---------------------------------------------------------------------------


class TestTokenBucket:
    def test_set_rate_preserves_explicit_burst(self):
        # the ISSUE 15 satellite bug: set_rate silently reset a
        # caller-supplied burst back to int(rps)
        clock = _FakeClock()
        b = TokenBucket(10.0, burst=64, clock=clock)
        b.set_rate(5.0)
        assert b.burst == 64
        assert b.rps == 5.0

    def test_set_rate_rederives_derived_burst(self):
        clock = _FakeClock()
        b = TokenBucket(10.0, clock=clock)
        assert b.burst == 10
        b.set_rate(4.0)
        assert b.burst == 4

    def test_set_rate_accepts_new_explicit_burst(self):
        clock = _FakeClock()
        b = TokenBucket(10.0, clock=clock)
        b.set_rate(10.0, burst=3)
        assert b.burst == 3
        b.set_rate(20.0)  # explicit burst now sticky
        assert b.burst == 3

    def test_retry_after_hint_tracks_deficit(self):
        clock = _FakeClock()
        b = TokenBucket(2.0, burst=1, clock=clock)
        assert b.allow()
        assert not b.allow()
        # one token at 2 rps ≈ 0.5 s away
        assert 0.0 < b.retry_after_s() <= 0.5
        clock.advance(0.5)
        assert b.retry_after_s() == 0.0
        assert b.allow()

    def test_zero_rps_hint_is_finite(self):
        clock = _FakeClock()
        b = TokenBucket(0.0, burst=1, clock=clock)
        assert b.allow()
        assert b.retry_after_s() == 1.0  # never-refilling: finite hint


class TestMultiStageRateLimiter:
    def test_domain_table_bounded_under_churn(self):
        clock = _FakeClock()
        lim = MultiStageRateLimiter(
            1e6, lambda d: 1e6, clock=clock, max_domains=16
        )
        for i in range(500):
            lim.allow(f"churn-dom-{i}")
        assert lim.domain_count() <= 16

    def test_lru_keeps_hot_domains(self):
        clock = _FakeClock()
        lim = MultiStageRateLimiter(
            1e6, lambda d: 1e6, clock=clock, max_domains=4
        )
        for i in range(4):
            lim.allow(f"d{i}")
        lim.allow("d0")  # refresh
        lim.allow("d-new")  # evicts d1 (LRU), not d0
        with lim._lock:
            assert "d0" in lim._domains
            assert "d1" not in lim._domains

    def test_throttled_domain_does_not_drain_global(self):
        clock = _FakeClock()
        lim = MultiStageRateLimiter(
            global_rps=100.0,
            domain_rps=lambda d: 1000.0 if d == "good" else 0.0001,
            clock=clock, global_burst=10,
        )
        # the bad domain gets its burst token then throttles WITHOUT
        # consuming global budget
        assert lim.allow("bad")
        for _ in range(50):
            assert not lim.allow("bad")
        for _ in range(9):  # global burst 10, 1 spent by bad's success
            assert lim.allow("good")

    def test_retry_after_covers_both_stages(self):
        clock = _FakeClock()
        lim = MultiStageRateLimiter(
            global_rps=1000.0, domain_rps=lambda d: 1.0, clock=clock,
        )
        assert lim.allow("slow")
        assert not lim.allow("slow")
        assert lim.retry_after_s("slow") > 0.0


class TestRetryBudget:
    def test_budget_exhausts_and_refills_on_success(self):
        b = RetryBudget(ratio=0.5, cap=4.0, initial=2.0)
        assert b.can_retry() and b.can_retry()
        assert not b.can_retry()  # drained
        for _ in range(2):
            b.record_success()
        assert b.can_retry()  # 2 successes × 0.5 = 1 token
        assert not b.can_retry()

    def test_cap_bounds_accumulation(self):
        b = RetryBudget(ratio=1.0, cap=2.0, initial=0.0)
        for _ in range(100):
            b.record_success()
        assert b.tokens() == 2.0

    def test_thread_safety_conserves_tokens(self):
        b = RetryBudget(ratio=0.0, cap=1000.0, initial=100.0)
        granted = []

        def worker():
            n = 0
            for _ in range(100):
                if b.can_retry():
                    n += 1
            granted.append(n)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(granted) == 100  # never over-grants


# ---------------------------------------------------------------------------
# fair admission: the property bar
# ---------------------------------------------------------------------------


class _Adm:
    """Minimal admission-shaped object for queue-level tests."""

    def __init__(self, domain_id, key):
        self.domain_id = domain_id
        self.key = key


class TestFairAdmissionProperties:
    def _queue(self, policy, clock=None):
        # the guard is only identity-checked by the sanitizer; tests
        # run untracked so a plain lock stands in for the engine lock
        return FairAdmissionQueue(
            policy, threading.Lock(), clock=clock or _FakeClock()
        )

    def test_aging_seats_within_k_recycles_any_weights(self):
        """The starvation-free property: one victim admission parked in
        a random-weight domain, a heavy domain re-fed every round at
        the service rate (one seat per round — permanent saturation).
        The victim must seat within K = (w_max − w_min)/aging_boost +
        #domains rounds for EVERY sampled weight assignment."""
        rng = random.Random(1234)
        for trial in range(20):
            w_heavy = rng.uniform(1.0, 20.0)
            w_victim = rng.uniform(0.1, w_heavy)
            boost = rng.choice([0.5, 1.0, 2.0])
            policy = AdmissionPolicy(
                domain_weights={"heavy": w_heavy, "victim": w_victim},
                aging_boost=boost,
                starvation_recycles=10_000,  # pure-aging arm: no quota
            )
            q = self._queue(policy)
            q.park(_Adm("victim", ("v", "0")))
            k_bound = int((w_heavy - w_victim) / boost) + 2 + 1
            seated_at = None
            for rnd in range(k_bound + 1):
                q.park(_Adm("heavy", ("h", str(rnd))))  # sustained feed
                taken = q.take(1)
                assert len(taken) == 1
                if taken[0].adm.domain_id == "victim":
                    seated_at = rnd
                    break
            assert seated_at is not None, (
                f"trial {trial}: victim starved past K={k_bound} "
                f"(w_heavy={w_heavy:.2f}, w_victim={w_victim:.2f}, "
                f"boost={boost})"
            )

    def test_quota_exceeded_domain_never_blocks_available_one(self):
        clock = _FakeClock()
        policy = AdmissionPolicy(
            domain_weights={"greedy": 100.0, "modest": 1.0},
            quota_rps=0.001, quota_burst=1,  # one seat, then parched
            starvation_recycles=10_000,
        )
        q = self._queue(policy, clock=clock)
        for i in range(3):
            q.park(_Adm("greedy", ("g", str(i))))
        q.park(_Adm("modest", ("m", "0")))
        first = q.take(4)
        doms = [e.adm.domain_id for e in first]
        # greedy's quota admits exactly one; modest seats DESPITE the
        # higher-weight domain having backlog — quota-blocked bids are
        # skipped, never waited on
        assert doms.count("greedy") == 1
        assert doms.count("modest") == 1
        assert len(q) == 2  # greedy's remainder parked on quota

    def test_starvation_age_bypasses_quota(self):
        clock = _FakeClock()
        policy = AdmissionPolicy(
            quota_rps=0.001, quota_burst=1, starvation_recycles=3,
        )
        q = self._queue(policy, clock=clock)
        q.park(_Adm("d", ("a", "0")))
        q.park(_Adm("d", ("a", "1")))
        assert len(q.take(2)) == 1  # quota: one per refill epoch
        # rounds pass; at age >= 3 the parked bid seats anyway
        out = []
        for _ in range(4):
            out += q.take(1)
        assert len(out) == 1
        assert out[0].adm.key == ("a", "1")

    def test_requeue_preserves_starvation_clock(self):
        q = self._queue(AdmissionPolicy(starvation_recycles=10_000))
        q.park(_Adm("d", ("a", "0")))
        for _ in range(5):
            q.take(0)  # rounds pass without capacity
        (entry,) = q.take(1)
        q.park(entry.adm, requeued_from=entry)  # seat failed: re-park
        assert q.oldest_age_rounds() >= 6

    def test_fifo_within_domain(self):
        q = self._queue(AdmissionPolicy())
        for i in range(5):
            q.park(_Adm("d", ("a", str(i))))
        order = [e.adm.key[1] for e in q.take(5)]
        assert order == ["0", "1", "2", "3", "4"]

    def test_drain_and_len(self):
        q = self._queue(AdmissionPolicy())
        for i in range(3):
            q.park(_Adm(f"d{i}", ("a", str(i))))
        assert len(q) == 3
        assert q.drain() == 3
        assert len(q) == 0 and q.take(4) == []

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            AdmissionPolicy(aging_boost=0.0).validate()
        with pytest.raises(ValueError):
            AdmissionPolicy(default_weight=0.0).validate()
        with pytest.raises(ValueError):
            AdmissionPolicy(domain_weights={"d": -1.0}).validate()
        with pytest.raises(ValueError):
            AdmissionPolicy(starvation_recycles=0).validate()


# ---------------------------------------------------------------------------
# coordinated shedding: ServiceBusy beyond the frontend + retry budgets
# ---------------------------------------------------------------------------


class _DenyLimiter:
    def __init__(self, hint=0.25):
        self.hint = hint
        self.calls = 0

    def allow(self, domain=""):
        self.calls += 1
        return False

    def retry_after_s(self, domain=""):
        return self.hint


class _AdmitN:
    """Limiter admitting the first ``n`` calls, shedding the rest."""

    def __init__(self, n, hint=0.01):
        self.n = n
        self.hint = hint

    def allow(self, domain=""):
        self.n -= 1
        return self.n >= 0

    def retry_after_s(self, domain=""):
        return self.hint


class TestCoordinatedShedding:
    def test_frontend_shed_carries_hint_and_metric(self):
        from types import SimpleNamespace

        from cadence_tpu.frontend.handler import WorkflowHandler
        from cadence_tpu.utils.metrics import Scope

        scope = Scope()
        h = WorkflowHandler(
            SimpleNamespace(), SimpleNamespace(), SimpleNamespace(),
            SimpleNamespace(), rate_limiter=_DenyLimiter(hint=1.5),
            metrics=scope,
        )
        with pytest.raises(ServiceBusyError) as ei:
            h._check("shed-dom")
        assert ei.value.retry_after_s == 1.5
        assert scope.registry.counter_value("frontend_requests_shed") == 1

    def test_matching_add_sheds_retryable(self):
        from cadence_tpu.matching import MatchingEngine
        from cadence_tpu.runtime.persistence.memory import (
            create_memory_bundle,
        )

        bundle = create_memory_bundle()
        try:
            eng = MatchingEngine(
                bundle.task, history_client=None,
                rate_limiter=_DenyLimiter(hint=0.5),
            )
            with pytest.raises(ServiceBusyError) as ei:
                eng.add_decision_task("dom", "wf", "run", "tl", 2)
            assert ei.value.retry_after_s == 0.5
        finally:
            bundle.close()

    def test_history_client_budget_retries_then_succeeds(self):
        from types import SimpleNamespace

        from cadence_tpu.client.history import HistoryClient

        calls = {"n": 0}

        class _Engine:
            def signal_workflow_execution(self, request):
                calls["n"] += 1
                if calls["n"] <= 2:
                    raise ServiceBusyError(
                        "busy", retry_after_s=0.001
                    )
                return "ok"

        engine = _Engine()
        ctl = SimpleNamespace(
            identity="h0", get_engine=lambda wf: engine
        )
        hc = HistoryClient({"h0": ctl})
        req = SimpleNamespace(workflow_id="wf")
        assert hc.signal_workflow_execution(req) == "ok"
        assert calls["n"] == 3

    def test_history_client_budget_exhaustion_surfaces_shed(self):
        from types import SimpleNamespace

        from cadence_tpu.client.history import HistoryClient
        from cadence_tpu.utils.metrics import Scope

        class _Engine:
            def signal_workflow_execution(self, request):
                raise ServiceBusyError("busy", retry_after_s=0.001)

        ctl = SimpleNamespace(
            identity="h0", get_engine=lambda wf: _Engine()
        )
        scope = Scope()
        hc = HistoryClient(
            {"h0": ctl},
            retry_budget=RetryBudget(ratio=0.0, cap=1.0, initial=0.0),
            metrics=scope,
        )
        with pytest.raises(ServiceBusyError):
            hc.signal_workflow_execution(
                SimpleNamespace(workflow_id="wf")
            )
        assert (
            scope.registry.counter_value("retry_budget_exhausted") == 1
        )

    def test_history_engine_shed_via_onebox(self):
        from cadence_tpu.runtime.api import StartWorkflowRequest
        from cadence_tpu.testing.onebox import Onebox

        box = Onebox(num_shards=1, start_worker=False)
        box.history.rate_limiter = _DenyLimiter(hint=0.001)
        box.start()
        try:
            box.domain_handler.register_domain("ovl-dom")
            with pytest.raises(ServiceBusyError):
                box.frontend.start_workflow_execution(
                    StartWorkflowRequest(
                        domain="ovl-dom", workflow_id="ovl-wf",
                        workflow_type="t", task_list="tl",
                        request_id="r1",
                        execution_start_to_close_timeout_seconds=60,
                    )
                )
        finally:
            box.stop()


# ---------------------------------------------------------------------------
# tick pump
# ---------------------------------------------------------------------------


class TestTickPump:
    def _engine(self, **kw):
        from cadence_tpu.ops import schema as S
        from cadence_tpu.serving import ResidentEngine

        return ResidentEngine(
            lanes=2, caps=S.Capacities(max_events=256), **kw
        )

    def test_pump_drives_ticks_and_stops_clean(self):
        from cadence_tpu.serving import TickPump

        engine = self._engine()
        pump = TickPump(engine, 0.005).start()
        deadline = time.monotonic() + 2.0
        while pump.cycles < 3 and time.monotonic() < deadline:
            time.sleep(0.005)
        pump.stop()
        assert pump.cycles >= 3
        assert not pump.running

    def test_drain_on_stop_composes_staged_deltas(self):
        from cadence_tpu.serving import TickPump
        from cadence_tpu.testing.event_generator import HistoryFuzzer
        from cadence_tpu.ops import schema as S

        caps = S.Capacities(max_events=256)
        engine = self._engine()
        fz = HistoryFuzzer(seed=19, caps=caps)
        batches = fz.generate(target_events=30, close=False)
        cut = max(1, len(batches) // 2)
        t = engine.admit("dom", "wf", "run", batches=batches[:cut])
        assert t is not None
        # a LONG interval: the staged Δ would sit un-composed without
        # the drain tick
        pump = TickPump(engine, 60.0).start()
        assert engine.append(t, batches[cut:])
        pump.stop()
        with engine._lock:
            lane = engine._slots[engine._by_key[("wf", "run")]]
            assert not lane.pending

    def test_pump_survives_tick_errors_and_backs_off(self):
        from cadence_tpu.serving import TickPump
        from cadence_tpu.utils.metrics import Scope

        class _Sick:
            def __init__(self):
                self.calls = 0

            def tick(self):
                self.calls += 1
                if self.calls <= 2:
                    raise RuntimeError("store down")
                return {}

        scope = Scope()
        sick = _Sick()
        pump = TickPump(sick, 0.005, metrics=scope.tagged(x="t"))
        pump.start()
        deadline = time.monotonic() + 3.0
        while sick.calls < 4 and time.monotonic() < deadline:
            time.sleep(0.005)
        pump.stop()
        assert sick.calls >= 4  # kept pumping after the errors
        assert pump.errors == 2
        assert (
            scope.registry.counter_value("serving_tick_pump_errors")
            == 2
        )

    def test_interval_validation(self):
        from cadence_tpu.serving import TickPump

        with pytest.raises(ValueError):
            TickPump(object(), 0.0)

    def test_history_service_starts_and_drains_pump(self):
        from cadence_tpu.config.bootstrap import start_services
        from cadence_tpu.config.static import load_config_dict

        cfg = load_config_dict({
            "serving": {
                "enabled": True, "lanes": 4, "tickIntervalMs": 5,
            }
        })
        s = start_services(
            cfg, services=["history", "matching", "frontend"]
        )
        try:
            pump = s.history._tick_pump
            assert pump is not None and pump.running
            assert pump.interval_s == pytest.approx(0.005)
        finally:
            s.stop()
        assert s.history._tick_pump is None


# ---------------------------------------------------------------------------
# review-pass regressions
# ---------------------------------------------------------------------------


class TestReviewRegressions:
    def test_quota_bucket_survives_backlog_oscillation(self):
        """A domain whose queue oscillates to empty must NOT refund a
        full quota burst on every re-park — the bucket persists across
        empty backlogs (it is LRU-bounded, not dropped-on-empty)."""
        clock = _FakeClock()
        policy = AdmissionPolicy(
            quota_rps=0.001, quota_burst=1, starvation_recycles=10_000,
        )
        q = FairAdmissionQueue(policy, threading.Lock(), clock=clock)
        q.park(_Adm("osc", ("a", "0")))
        assert len(q.take(1)) == 1  # burst token spent; backlog empty
        for i in range(5):
            q.park(_Adm("osc", ("a", str(i + 1))))
            assert q.take(1) == [], (
                "empty-backlog oscillation refunded the quota burst"
            )
            (entry,) = q.take(0) or [None]  # rounds advance via take
            assert entry is None
        assert len(q) == 5

    def test_refill_seat_failure_reparks_at_original_age(self):
        """A parked admission whose refill SEAT REPLAY fails must go
        back into the fair queue at its original age (bounded
        attempts), not silently vanish until some future read."""
        from unittest import mock

        from cadence_tpu.ops import schema as S
        from cadence_tpu.serving import ResidentEngine
        from cadence_tpu.testing.event_generator import HistoryFuzzer

        caps = S.Capacities(max_events=256)
        engine = ResidentEngine(lanes=1, caps=caps, idle_ticks=1)
        hists = []
        for i in range(2):
            fz = HistoryFuzzer(seed=401 + i, caps=caps)
            hists.append((
                f"rp-wf-{i}", f"rp-run-{i}",
                fz.generate(target_events=20, close=False),
            ))
        (wa, ra, ba), (wb, rb, bb) = hists
        assert engine.admit("dom", wa, ra, batches=ba) is not None
        assert engine.admit("dom", wb, rb, batches=bb) is None  # parked
        assert engine.evict(wa, ra)

        def boom(*a, **kw):
            raise RuntimeError("storm")

        with mock.patch(
            "cadence_tpu.ops.dispatch.replay_stream", boom
        ), mock.patch.object(engine, "_replay", boom):
            engine.tick()  # refill takes B, the seat replay fails
            assert engine.describe()["queued"] == 1, (
                "failed refill seat dropped the parked admission"
            )
        engine.tick()  # storm over: the re-parked admission seats
        got = engine.read(wb, rb)
        assert got is not None and got.resident

    def test_config_validate_does_not_import_serving(self):
        """ServerConfig.validate() must stay importable/runnable
        without pulling cadence_tpu.serving (and thus jax) into
        frontend/matching-only processes."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from cadence_tpu.config.static import load_config_dict\n"
            "cfg = load_config_dict({'serving': {'lanes': 4,\n"
            "    'domainWeights': {'a': 2.0}, 'quotaRps': 5.0}})\n"
            "cfg.validate()\n"
            "assert 'cadence_tpu.serving' not in sys.modules, (\n"
            "    'validate() imported the serving package')\n"
            "print('LEAN-VALIDATE-OK')\n"
        )
        import os

        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, cwd=repo, env=env, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "LEAN-VALIDATE-OK" in r.stdout

    def test_onebox_client_budget_metric_lands_in_host_registry(self):
        """The retry-storm breaker must be observable in the registry
        operators scrape — not NOOP (review finding: production
        clients were built without the metrics scope)."""
        from cadence_tpu.runtime.api import StartWorkflowRequest
        from cadence_tpu.testing.onebox import Onebox
        from cadence_tpu.utils.quotas import RetryBudget

        box = Onebox(num_shards=1, start_worker=False)
        box.history.rate_limiter = _DenyLimiter(hint=0.001)
        box.start()
        try:
            box.domain_handler.register_domain("obm-dom")
            box.history_client.retry_budget = RetryBudget(
                ratio=0.0, cap=1.0, initial=0.0
            )
            with pytest.raises(ServiceBusyError):
                box.history_client.start_workflow_execution(
                    StartWorkflowRequest(
                        domain="obm-dom", workflow_id="obm-wf",
                        workflow_type="t", task_list="tl",
                        request_id="r1",
                        execution_start_to_close_timeout_seconds=60,
                    )
                )
            assert box.metrics.registry.counter_value(
                "retry_budget_exhausted"
            ) == 1
        finally:
            box.stop()

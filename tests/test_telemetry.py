"""Unified telemetry plane: tracing, histogram metrics, device telemetry.

Four surfaces under test:

* utils/tracing.py — spans, contexts, the thread-local current-span
  propagation, the workflow-keyed binding table, the flight-recorder
  ring buffer and its Chrome-trace export;
* the end-to-end acceptance invariant: ONE Onebox workflow decision
  driven inside a sampled root span yields a SINGLE trace spanning
  frontend → history → matching → queue → persistence with >= 6 spans
  and intact parent/child links;
* cross-process propagation: a context injected on the rpc client
  parents the server-side span (same trace_id across the hop);
* ops/dispatch.py device-step telemetry and the TELEMETRY/DEVICE
  metric-tuple coverage contract (every declared name really emitted).
"""

from __future__ import annotations

import json
import os
import time

import pytest

from cadence_tpu.utils.metrics import Scope
from cadence_tpu.utils.tracing import (
    NOOP_SPAN,
    TRACER,
    TraceContext,
    Tracer,
    extract_metadata,
    inject_metadata,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the process tracer quiet: rate 0,
    empty recorder, empty bindings (the singleton is shared)."""
    TRACER.configure(sample_rate=0.0)
    TRACER.clear()
    yield
    TRACER.configure(sample_rate=0.0)
    TRACER.clear()


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------


class TestTracer:
    def test_unsampled_paths_are_noops(self):
        t = Tracer(sample_rate=0.0)
        assert t.trace("root") is NOOP_SPAN        # rate-0 roll
        assert t.span("child") is NOOP_SPAN        # no current span
        t.annotate("dropped")                      # no current span
        t.bind(("wf", "w1"))                       # nothing to bind
        assert t.lookup(("wf", "w1")) is None
        assert t.spans() == []

    def test_explicit_sampling_overrides_rate(self):
        t = Tracer(sample_rate=0.0)
        with t.trace("root", sampled=True) as root:
            assert root is not NOOP_SPAN
            assert t.current() is root
        assert t.current() is None
        assert [s.name for s in t.spans()] == ["root"]

    def test_child_nesting_and_parent_links(self):
        t = Tracer()
        with t.trace("root", sampled=True) as root:
            with t.span("mid", service="history") as mid:
                with t.span("leaf") as leaf:
                    assert leaf.trace_id == root.trace_id
                    assert leaf.parent_id == mid.span_id
            assert mid.parent_id == root.span_id
        names = {s.name: s for s in t.spans()}
        assert set(names) == {"root", "mid", "leaf"}
        # finish order is leaf-first; durations nest
        assert names["root"].dur_us >= names["mid"].dur_us

    def test_exception_tags_error_and_restores_current(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.trace("root", sampled=True):
                with t.span("boom"):
                    raise ValueError("x")
        assert t.current() is None
        boom = [s for s in t.spans() if s.name == "boom"][0]
        assert boom.tags["error"] == "ValueError"

    def test_annotations_are_timestamped_breadcrumbs(self):
        t = Tracer()
        with t.trace("root", sampled=True):
            t.annotate("first")
            t.annotate("second")
        (root,) = t.spans()
        assert [a for _, a in root.annotations] == ["first", "second"]
        assert root.annotations[0][0] <= root.annotations[1][0]

    def test_ring_buffer_bounded_and_drop_counted(self):
        metrics = Scope()
        t = Tracer(capacity=4, metrics=metrics)
        for i in range(7):
            with t.trace(f"s{i}", sampled=True):
                pass
        spans = t.spans()
        assert len(spans) == 4
        assert [s.name for s in spans] == ["s3", "s4", "s5", "s6"]
        reg = metrics.registry
        assert reg.counter_value("spans_dropped") == 3
        assert reg.counter_value("spans_recorded") == 7
        assert reg.counter_value("traces_sampled") == 7

    def test_binding_table_is_lru_bounded(self):
        t = Tracer(bind_capacity=2)
        with t.trace("root", sampled=True) as root:
            t.bind("a")
            t.bind("b")
            t.bind("c")  # evicts "a"
        assert t.lookup("a") is None
        assert t.lookup("b").trace_id == root.trace_id
        assert t.lookup("c").span_id == root.span_id

    def test_binding_ttl_expires_stale_entries(self):
        # a binding must not outlive its request: a long-lived workflow
        # would otherwise pump every future timer task into one ancient
        # sampled trace forever
        t = Tracer(bind_ttl_s=0.05)
        with t.trace("root", sampled=True):
            t.bind(("wf", "w1"))
        assert t.lookup(("wf", "w1")) is not None
        time.sleep(0.06)
        assert t.lookup(("wf", "w1")) is None
        # expired entries are removed, not just hidden
        assert ("wf", "w1") not in t._bindings

    def test_span_from_bound_context_joins_trace(self):
        t = Tracer()
        with t.trace("root", sampled=True) as root:
            t.bind(("wf", "w1"))
        ctx = t.lookup(("wf", "w1"))
        with t.span("async-hop", parent=ctx) as hop:
            assert hop.trace_id == root.trace_id
            assert hop.parent_id == root.span_id

    def test_wire_roundtrip_and_malformed_tolerance(self):
        ctx = TraceContext("abc123", "7.42", True)
        back = TraceContext.from_wire(ctx.to_wire())
        assert (back.trace_id, back.span_id, back.sampled) == (
            "abc123", "7.42", True
        )
        for bad in ("", "nocolons", "a:b:c:d", None, ":x:1", 7):
            assert TraceContext.from_wire(bad) is None

    def test_metadata_inject_extract(self):
        assert inject_metadata() is None  # no active trace: unchanged
        t = TRACER
        with t.trace("root", sampled=True) as root:
            md = inject_metadata((("other", "1"),))
            assert ("other", "1") in md
            ctx = extract_metadata(md)
            assert ctx.trace_id == root.trace_id
            assert ctx.span_id == root.span_id
        assert extract_metadata((("other", "1"),)) is None
        assert extract_metadata(None) is None

    def test_chrome_trace_export_shape(self):
        t = Tracer()
        with t.trace("root", sampled=True, service="frontend"):
            t.annotate("note")
            with t.span("inner", service="history"):
                pass
        doc = t.chrome_trace()
        json.dumps(doc)  # must be serializable
        events = doc["traceEvents"]
        metas = [e for e in events if e["ph"] == "M"]
        complete = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        assert {m["args"]["name"] for m in metas} == {
            "frontend", "history"
        }
        assert {e["name"] for e in complete} == {"root", "inner"}
        assert [i["name"] for i in instants] == ["note"]
        # pid ties a span to its service's process_name metadata
        pid_of = {m["args"]["name"]: m["pid"] for m in metas}
        root_ev = [e for e in complete if e["name"] == "root"][0]
        assert root_ev["pid"] == pid_of["frontend"]
        # trace_id filter
        tid = root_ev["args"]["trace_id"]
        assert len([
            e for e in t.chrome_trace(tid)["traceEvents"]
            if e["ph"] == "X"
        ]) == 2
        assert [
            e for e in t.chrome_trace("nope")["traceEvents"]
            if e["ph"] == "X"
        ] == []

    def test_configure_rewires_capacity_and_rate(self, monkeypatch):
        t = Tracer(sample_rate=0.0, capacity=8)
        t.configure(sample_rate=1.0, capacity=2)
        assert t.trace("rolled") is not NOOP_SPAN  # rate 1.0 samples
        t.configure(sample_rate=0.0)
        assert t.trace("rolled2") is NOOP_SPAN


# ---------------------------------------------------------------------------
# the end-to-end acceptance invariant (Onebox, one workflow decision)
# ---------------------------------------------------------------------------


def _doubler(ctx, input):
    a = yield ctx.schedule_activity("double", input)
    b = yield ctx.schedule_activity("double", a)
    return b


class TestOneboxTrace:
    def test_one_decision_yields_single_cross_service_trace(self):
        """ONE workflow decision driven inside a sampled root span lands
        as a SINGLE trace spanning frontend → history → matching →
        queue → persistence, >= 6 spans, every parent link resolving
        inside the trace — the ISSUE 10 acceptance invariant."""
        from cadence_tpu.runtime.api import StartWorkflowRequest
        from cadence_tpu.testing.onebox import Onebox
        from cadence_tpu.worker import Worker

        box = Onebox(num_shards=2).start()
        w = Worker(box.frontend, "tel-dom", "tel-tl",
                   identity="tel-worker")
        w.register_workflow("tel-wf", _doubler)
        w.register_activity("double", lambda inp: inp * 2)
        try:
            box.domain_handler.register_domain("tel-dom")
            w.start()
            with TRACER.trace("workflow_decision", sampled=True,
                              service="test") as root:
                trace_id = root.trace_id
                run_id = box.frontend.start_workflow_execution(
                    StartWorkflowRequest(
                        domain="tel-dom", workflow_id="tel-wf-0",
                        workflow_type="tel-wf", task_list="tel-tl",
                        input=b"\x02", request_id="tel-req",
                        execution_start_to_close_timeout_seconds=60,
                    )
                )
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    d = box.frontend.describe_workflow_execution(
                        "tel-dom", "tel-wf-0", run_id
                    )
                    if not d.is_running:
                        break
                    time.sleep(0.02)
                else:
                    raise AssertionError("workflow did not complete")
            time.sleep(0.3)  # asynchronous tail (pump-thread spans)
        finally:
            w.stop()
            box.stop()

        spans = [s for s in TRACER.spans() if s.trace_id == trace_id]
        assert len(spans) >= 6, [s.name for s in spans]
        services = {s.service for s in spans}
        assert {"frontend", "history", "matching", "history_queue",
                "persistence"} <= services, services
        # single trace: every span this decision produced shares the id
        # and every non-root parent link resolves inside the trace
        ids = {s.span_id for s in spans}
        roots = [s for s in spans if not s.parent_id]
        assert [s.name for s in roots] == ["workflow_decision"]
        for s in spans:
            if s.parent_id:
                assert s.parent_id in ids, (s.name, s.parent_id)
        # the queue hop joined via the workflow binding, and nested
        # matching work under it
        queue_spans = [s for s in spans if s.service == "history_queue"]
        assert queue_spans, "queue tasks never joined the trace"
        queue_ids = {s.span_id for s in queue_spans}
        matching_spans = [s for s in spans if s.service == "matching"]
        assert any(
            m.parent_id in queue_ids for m in matching_spans
        ), "matching add-task did not nest under the queue span"

    def test_rpc_hop_joins_the_same_trace(self):
        """Client-injected context parents the server-side span: the
        cross-process half of one trace (rpc/client.py metadata →
        rpc/server.py extraction)."""
        from cadence_tpu.rpc.client import RemoteService
        from cadence_tpu.rpc.server import ServiceRPCServer

        class Handler:
            def echo_op(self, value):
                return {"v": value}

        server = ServiceRPCServer(
            "cadence_tpu.Frontend", [Handler()]
        ).start()
        client = RemoteService(server.address)
        try:
            with TRACER.trace("edge", sampled=True) as root:
                assert client.echo_op(41)["v"] == 41
                trace_id = root.trace_id
        finally:
            client.close()
            server.stop()
        rpc_spans = [
            s for s in TRACER.spans() if s.name == "rpc.echo_op"
        ]
        assert len(rpc_spans) == 1
        assert rpc_spans[0].trace_id == trace_id
        assert rpc_spans[0].parent_id == root.span_id
        assert rpc_spans[0].service == "frontend"

    def test_rpc_without_context_roots_nothing_at_rate_zero(self):
        from cadence_tpu.rpc.client import RemoteService
        from cadence_tpu.rpc.server import ServiceRPCServer

        class Handler:
            def echo_op(self, value):
                return value

        server = ServiceRPCServer(
            "cadence_tpu.Frontend", [Handler()]
        ).start()
        client = RemoteService(server.address)
        try:
            assert client.echo_op(1) == 1
        finally:
            client.close()
            server.stop()
        assert TRACER.spans() == []


# ---------------------------------------------------------------------------
# device-step telemetry (ops/dispatch.py)
# ---------------------------------------------------------------------------


class TestDeviceTelemetry:
    def _histories(self, n=6, depth=8):
        import random

        from cadence_tpu.testing import workloads as W

        rng = random.Random(7)
        return [
            (f"wf-{i}", f"run-{i}", W.retry_deep_history(rng, depth=depth))
            for i in range(n)
        ]

    def test_dispatcher_emits_device_metrics_when_wired(self):
        from cadence_tpu.ops.dispatch import replay_stream

        metrics = Scope()
        out = replay_stream(self._histories(), batch_size=3, metrics=metrics)
        assert len(out) == 2
        reg = metrics.registry
        assert reg.counter_value("device_batches") == 2
        stage = reg.timer_stats("host_stage_seconds")
        assert stage.count == 2 and stage.p50 > 0
        # per-width batch counters exist (grid-rounded widths)
        assert reg.counter_value("batch_width") == 2
        # padding as two counters, summed over every batch: the real
        # events and the staged cells (rows x scan length)
        real = sum(len(b) for h in self._histories() for b in h[2])
        assert reg.counter_value("replay_event_cells") == real
        staged = sum(p.batch * p.events.shape[1] for p, _ in out)
        assert reg.counter_value("replay_staged_cells") == staged > real
        snap = reg.snapshot()
        assert not any("padding_frac" in k for k in snap["gauges"])
        assert any(
            "jit_cache_entries" in k for k in snap["gauges"]
        )

    def test_lane_packed_batches_report_occupancy(self):
        from cadence_tpu.ops.dispatch import replay_stream

        metrics = Scope()
        out = replay_stream(
            self._histories(), batch_size=6, lane_pack=True, lane_len=32,
            metrics=metrics,
        )
        reg = metrics.registry
        (packed, _), = out
        # occupancy = lane_histories / lanes over any window
        assert reg.counter_value("lanes") == packed.lanes > 0
        assert reg.counter_value("lane_histories") == 6
        assert reg.counter_value("replay_staged_cells") == (
            packed.lanes * packed.scan_len)
        assert reg.counter_value("replay_event_cells") == (
            packed.total_events)
        assert not any("lane_occupancy" in k
                       for k in reg.snapshot()["gauges"])

    def test_wired_telemetry_never_waits_for_the_device(self,
                                                        monkeypatch):
        import jax

        from cadence_tpu.ops.dispatch import replay_stream

        def refuse(*a, **k):
            raise AssertionError("telemetry blocked on the device")

        monkeypatch.setattr(jax, "block_until_ready", refuse)
        metrics = Scope()
        out = replay_stream(
            self._histories(), batch_size=3, lane_pack=True, lane_len=32,
            metrics=metrics,
        )
        assert len(out) == 2
        assert metrics.registry.counter_value("device_batches") == 2

    def test_default_dispatcher_pays_nothing(self):
        from cadence_tpu.ops.dispatch import DeviceDispatcher
        from cadence_tpu.utils.metrics import NOOP

        d = DeviceDispatcher()
        assert d._telemetry is False
        # the shared NOOP sentinel means "no metrics wired" too: a
        # caller defaulting to NOOP must not pay for data nobody reads
        assert DeviceDispatcher(metrics=NOOP)._telemetry is False


# ---------------------------------------------------------------------------
# catalog coverage: every TELEMETRY/DEVICE name is really emitted
# ---------------------------------------------------------------------------


def _emitted_names(paths):
    import re

    pattern = re.compile(
        r"""\.(?:inc|gauge|record)\(\s*\n?\s*f?["']([a-z_]+)["']""",
    )
    out = set()
    for rel in paths:
        with open(os.path.join(REPO_ROOT, rel)) as f:
            out.update(pattern.findall(f.read()))
    return out


def test_device_metrics_tuple_covers_everything_emitted():
    from cadence_tpu.utils.metrics_defs import DEVICE_METRICS

    emitted = _emitted_names(["cadence_tpu/ops/dispatch.py"])
    assert emitted, "no device metric emissions found"
    assert emitted <= set(DEVICE_METRICS), (
        emitted - set(DEVICE_METRICS)
    )
    for name in DEVICE_METRICS:
        assert name in emitted, f"{name} declared but never emitted"


def test_telemetry_metrics_tuple_covers_everything_emitted():
    from cadence_tpu.utils.metrics import DROPPED_SERIES
    from cadence_tpu.utils.metrics_defs import TELEMETRY_METRICS

    emitted = _emitted_names(["cadence_tpu/utils/tracing.py"])
    # the registry's own overflow counter is emitted structurally
    # (direct dict write under the lock), asserted behaviorally in
    # tests/test_utils.py; the declared name must match the constant
    assert DROPPED_SERIES in TELEMETRY_METRICS
    declared = set(TELEMETRY_METRICS) - {DROPPED_SERIES}
    assert emitted == declared, (emitted, declared)

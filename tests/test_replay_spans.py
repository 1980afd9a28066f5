"""Program spans on the rebuild and replay paths (utils/tracing.py).

``StateRebuilder.rebuild_many`` and ``ops.replay.replay_packed`` are
trace entry points; the dispatcher's pumps join the submitter's trace on
their own threads; each span carries the counts taken where its work
happens (events, streamed cells, bytes moved). Unsampled, nothing is
built; with profiler annotation on, the spans land in a ``jax.profiler``
trace on the host planes.
"""

from __future__ import annotations

import glob
import os
import random

import jax
import numpy as np
import pytest

from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import SLOT_TABLES, pack_histories
from cadence_tpu.runtime.persistence.memory import create_memory_bundle
from cadence_tpu.runtime.replication.rebuilder import (
    RebuildRequest,
    StateRebuilder,
)
from cadence_tpu.testing import workloads as W
from cadence_tpu.testing.event_generator import HistoryFuzzer
from cadence_tpu.utils import tracing
from cadence_tpu.utils.tracing import NOOP_SPAN, TRACER, Tracer

CAPS = S.Capacities(max_events=64)

REBUILD_SPANS = {"rebuild_many", "rebuild.read", "rebuild.await",
                 "rebuild.fetch", "rebuild.unpack", "rebuild.refresh",
                 "dispatch.bucket", "dispatch.pack", "dispatch.h2d",
                 "dispatch.launch"}
REPLAY_SPANS = {"replay_packed", "replay.layout", "replay.h2d",
                "replay.launch", "replay.fetch"}


@pytest.fixture(autouse=True)
def _quiet_tracer():
    TRACER.configure(sample_rate=0.0)
    TRACER.set_profiler_prefix(None)
    TRACER.clear()
    yield
    TRACER.configure(sample_rate=0.0)
    TRACER.set_profiler_prefix(None)
    TRACER.clear()


@pytest.fixture()
def stored():
    bundle = create_memory_bundle()
    history = bundle.history
    fuzzer = HistoryFuzzer(seed=41)
    reqs, events, nodes = [], 0, 0
    for i in range(7):
        batches = fuzzer.generate(target_events=12 if i % 3 else 40)
        events += sum(len(b) for b in batches)
        nodes += len(batches)
        branch = history.new_history_branch(tree_id=f"run-{i}")
        for txn, batch in enumerate(batches, start=1):
            history.append_history_nodes(branch, batch, transaction_id=txn)
        reqs.append(RebuildRequest(
            domain_id="dom", workflow_id=f"wf-{i}", run_id=f"run-{i}",
            branch_token=branch.to_json().encode()))
    yield StateRebuilder(history, lane_len=128), reqs, events, nodes
    bundle.close()


def _trace_of(root):
    return [s for s in TRACER.spans() if s.trace_id == root.trace_id]


def _byname(spans, name):
    return [s for s in spans if s.name == name]


def test_rebuild_many_spans_join_one_trace_across_the_pumps(
        stored, monkeypatch):
    from cadence_tpu.ops.dispatch import DeviceDispatcher

    from cadence_tpu.ops import replay

    rb, reqs, events, nodes = stored
    moved, launched = [], []
    to_device, launch = replay.to_device, DeviceDispatcher._launch

    def spy_to_device(host, span, parent=None):
        moved.append(sum(int(x.nbytes)
                         for x in jax.tree_util.tree_leaves(host)))
        return to_device(host, span, parent)

    def spy_launch(self, mode, packed, *a):
        launched.append((packed.total_events,
                         packed.lanes * packed.scan_len))
        return launch(self, mode, packed, *a)

    monkeypatch.setattr(replay, "to_device", spy_to_device)
    monkeypatch.setattr(DeviceDispatcher, "_launch", spy_launch)
    with TRACER.trace("caller", sampled=True) as root:
        out = rb.rebuild_many(reqs)
    assert all(o is not None for o in out)
    spans = _trace_of(root)
    assert REBUILD_SPANS <= {s.name for s in spans}
    (top,) = _byname(spans, "rebuild_many")
    assert top.parent_id == root.span_id  # a child of the caller's span
    assert top.tags == {"requests": 7, "device_histories": 7,
                        "wide_histories": 0, "host_fallbacks": 0}
    (read,) = _byname(spans, "rebuild.read")
    assert read.tags == {"histories": 7, "events": events,
                         "nodes": nodes, "pages": 7}
    assert len(_byname(spans, "rebuild.unpack")) == 7
    assert len(_byname(spans, "rebuild.refresh")) == 7
    for s in spans:
        if s.name.startswith("rebuild.") or s.name == "dispatch.bucket":
            assert s.thread == top.thread
            want = top.span_id
        elif s.name.startswith("dispatch."):
            # the pumps' spans hang under rebuild_many, on their threads
            assert s.thread == ("dispatch-run" if s.name.endswith("launch")
                                else "dispatch-pack")
            want = top.span_id
        else:
            continue
        assert s.parent_id == want, s.name
    packs = _byname(spans, "dispatch.pack")
    assert sum(s.tags["histories"] for s in packs) == 7
    assert sum(s.tags["events"] for s in packs) == events
    (bucket,) = _byname(spans, "dispatch.bucket")
    # every history at the default caps; the peaks fit inside them
    default_slots = sum(getattr(S.Capacities(), f) for f in SLOT_TABLES)
    used = bucket.tags.pop("slots_used")
    assert bucket.tags == {"histories": 7, "wide_histories": 0,
                           "buckets": len(packs), "slots": 7 * default_slots}
    assert 0 < used <= 7 * default_slots
    h2d = _byname(spans, "dispatch.h2d")
    assert sorted(s.tags["bytes"] for s in h2d) == sorted(moved)
    got = sorted((s.tags["events"], s.tags["cells"])
                 for s in _byname(spans, "dispatch.launch"))
    assert got == sorted(launched)
    assert len(got) == len(packs) == len(h2d)
    # one fetch of the final state per device batch, on the caller's
    # thread, before that batch's rows unpack
    fetches = _byname(spans, "rebuild.fetch")
    assert len(fetches) == len(packs)
    assert sum(s.tags["histories"] for s in fetches) == \
        top.tags["device_histories"]
    assert all(s.tags["bytes"] > 0 for s in fetches)
    first_unpack = min(s.start_s for s in _byname(spans, "rebuild.unpack"))
    assert min(s.start_s for s in fetches) < first_unpack


def test_rebuild_read_counts_the_nodes_and_pages_it_reads(monkeypatch):
    """``rebuild.read`` carries ``nodes`` (the batches read) and
    ``pages`` (the store reads, one JSON parse each); a deep history
    reads in several 256-node pages."""
    bundle = create_memory_bundle()
    history = bundle.history
    stored = [W.retry_deep_history(random.Random(11), depth=600),
              HistoryFuzzer(seed=3).generate(target_events=30)]
    assert len(stored[0]) > 512
    reqs = []
    for i, batches in enumerate(stored):
        branch = history.new_history_branch(tree_id=f"deep-{i}")
        for txn, batch in enumerate(batches, start=1):
            history.append_history_nodes(branch, batch, transaction_id=txn)
        reqs.append(RebuildRequest(
            domain_id="dom", workflow_id=f"wf-{i}", run_id=f"run-{i}",
            branch_token=branch.to_json().encode()))
    reads = []
    read = history.read_history_branch

    def spy(*a, **k):
        batches, token = read(*a, **k)
        reads.append(len(batches))
        return batches, token

    monkeypatch.setattr(history, "read_history_branch", spy)
    with TRACER.trace("caller", sampled=True) as root:
        out = StateRebuilder(history, lane_len=1024).rebuild_many(reqs)
    assert all(o is not None for o in out)
    (sp,) = _byname(_trace_of(root), "rebuild.read")
    assert sp.tags["nodes"] == sum(reads) == sum(len(b) for b in stored)
    assert sp.tags["pages"] == len(reads) == 3 + 1
    bundle.close()


def test_rebuild_many_unpacks_rows_from_host_arrays(stored, monkeypatch):
    """Each row is unpacked from the batch's host copy, so no row costs
    a device gather; the answers are the host replayer's."""
    from cadence_tpu.ops import unpack
    from cadence_tpu.ops.unpack import mutable_state_to_snapshot

    rb, reqs, *_ = stored
    seen = []
    orig = unpack.state_row_to_mutable_state

    def spy(state, b, *a, **k):
        seen.append([type(x) for x in jax.tree_util.tree_leaves(state)])
        return orig(state, b, *a, **k)

    monkeypatch.setattr(unpack, "state_row_to_mutable_state", spy)
    out = rb.rebuild_many(reqs, use_device=True)
    assert len(seen) == len(reqs)
    assert all(t is np.ndarray for leaves in seen for t in leaves)
    for (ms, _, _), r in zip(out, reqs):
        want, _, _ = rb.rebuild(r)
        assert mutable_state_to_snapshot(ms) == mutable_state_to_snapshot(want)


def test_rebuild_many_roots_its_own_trace_at_the_sample_rate(stored):
    rb, reqs, *_ = stored
    TRACER.configure(sample_rate=1.0)
    rb.rebuild_many(reqs[:2])
    (top,) = [s for s in TRACER.spans() if s.name == "rebuild_many"]
    assert top.parent_id == ""
    assert {s.trace_id for s in TRACER.spans()} == {top.trace_id}


def test_fallback_span_counts_the_batch_rebuilt_on_the_host(
        stored, monkeypatch):
    from cadence_tpu.ops.dispatch import DeviceDispatcher

    rb, reqs, *_ = stored

    def broken(self, *a, **k):
        raise RuntimeError("launch refused")

    monkeypatch.setattr(DeviceDispatcher, "_launch", broken)
    with TRACER.trace("caller", sampled=True) as root:
        out = rb.rebuild_many(reqs)
    assert all(o is not None for o in out)
    spans = _trace_of(root)
    falls = _byname(spans, "rebuild.fallback")
    assert sum(s.tags["histories"] for s in falls) == 7
    (top,) = _byname(spans, "rebuild_many")
    assert top.tags["host_fallbacks"] == 7
    assert top.tags["device_histories"] == 0
    assert not _byname(spans, "rebuild.unpack")
    assert not _byname(spans, "rebuild.fetch")


def _packed(n=6, depth=20, seed=7):
    rng = random.Random(seed)
    hs = [(f"wf-{i}", f"run-{i}", W.retry_deep_history(rng, depth=depth))
          for i in range(n)]
    return pack_histories(hs, caps=CAPS)


@pytest.mark.parametrize("branch", ["tpu_branch", "scan"])
def test_replay_packed_spans_count_what_they_move(branch, monkeypatch):
    """Each branch of ``_replay_histories`` tags what it moves: the XLA
    scan lays its time-major events out on the host, the TPU branch
    (steered onto the CPU, its kernel stubbed) ships the packer's rows
    as they are and lays them out on the device."""
    from cadence_tpu.ops import replay_pallas
    from cadence_tpu.ops.grid import round_scan_len
    from cadence_tpu.ops.replay import replay_packed

    packed = _packed()
    tpu = branch == "tpu_branch"
    if tpu:
        monkeypatch.setattr(replay_pallas, "on_tpu", lambda: True)
        monkeypatch.setattr(replay_pallas, "replay_scan_pallas_teb",
                            lambda state, events, caps, **kw: state)
    with TRACER.trace("caller", sampled=True) as root:
        final = replay_packed(packed)
    spans = _trace_of(root)
    assert {s.name for s in spans} == REPLAY_SPANS | {"caller"}
    (top,) = _byname(spans, "replay_packed")
    assert top.parent_id == root.span_id
    events = int(packed.lengths.sum())
    assert top.tags == {"histories": packed.batch, "events": events}
    for s in spans:
        if s.name.startswith("replay."):
            assert s.parent_id == top.span_id and s.thread == top.thread
    bp = packed.batch if tpu else round_scan_len(packed.batch)
    T = packed.events.shape[1]
    ev_bytes = S.EV_N * bp * T * 4
    layouts = _byname(spans, "replay.layout")
    if tpu:
        # no byte laid out on the host, every event byte on the device
        # (six rows are no whole tile, so the kernel makes its own
        # presence masks)
        assert [s.tags for s in layouts] == [
            {"bytes": 0, "device_bytes": 0},
            {"bytes": 0, "device_bytes": ev_bytes},
        ]
    else:
        assert [s.tags for s in layouts] == [
            {"bytes": ev_bytes, "device_bytes": 0}]
    # the state goes first, its copy overlapping the layout; the events
    # after it (the grid's padding rows are made on the device)
    state_bytes = sum(
        int(np.asarray(x).nbytes) for x in
        jax.tree_util.tree_leaves(S.empty_state(packed.batch, CAPS)))
    h2d = _byname(spans, "replay.h2d")
    assert [s.tags["bytes"] for s in h2d] == [state_bytes, ev_bytes]
    assert h2d[0].start_s < layouts[0].start_s < h2d[1].start_s
    (launch,) = _byname(spans, "replay.launch")
    assert launch.tags == (
        {"events": events} if tpu else {"events": events, "cells": bp * T})
    (fetch,) = _byname(spans, "replay.fetch")
    assert fetch.tags == {"bytes": sum(
        int(x.nbytes) for x in jax.tree_util.tree_leaves(final))}
    kids = sum(s.dur_us for s in spans if s.parent_id == top.span_id)
    assert kids <= top.dur_us


def test_pallas_kernels_tag_the_cells_they_stream(tpu_branch_on_cpu):
    """Both Pallas kernels count what they stream after tile padding;
    the teb kernel pads the batch to its tile and time to its block."""
    import jax.numpy as jnp

    from cadence_tpu.ops.dispatch import DeviceDispatcher

    caps = S.Capacities(   # small tables: the kernels run interpreted
        max_events=16, max_activities=2, max_timers=2, max_children=2,
        max_request_cancels=1, max_signals_ext=1, max_version_items=2)
    rng = random.Random(5)
    hs = [(f"wf-{i}", f"run-{i}", W.retry_deep_history(rng, depth=8))
          for i in range(3)]
    with TRACER.trace("caller", sampled=True) as root:
        with DeviceDispatcher(caps=caps, bt=1024, tb=8) as d:
            d.submit(0, hs)
            d.finish()
            ((_, packed, _),) = list(d.results())
    (launch,) = _byname(_trace_of(root), "dispatch.launch")
    T = packed.events.shape[1]
    assert launch.tags["cells"] == 1024 * (T + (-T) % 8)
    assert launch.tags["events"] == int(packed.lengths.sum())

    from cadence_tpu.ops.pack import pack_lanes
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    lanes = pack_lanes(hs, caps=caps, target_lane_len=32, seg_align=8)
    with TRACER.trace("launch", sampled=True) as sp:
        replay_scan_pallas_packed(
            jax.tree_util.tree_map(jnp.asarray, lanes.lane_state0()),
            jax.tree_util.tree_map(jnp.asarray, S.empty_state(8, caps)),
            jnp.asarray(lanes.teb()), jnp.asarray(lanes.seg_end),
            jnp.asarray(lanes.out_row), caps, tb=8, interpret=True,
            bt=1024)
    assert sp.tags["cells"] == 1024 * lanes.scan_len


def test_unsampled_paths_build_no_span_and_open_no_annotation(
        stored, monkeypatch):
    from cadence_tpu.ops.replay import replay_packed

    built, opened = [], []

    class CountingSpan(tracing.Span):
        __slots__ = ()

        def __init__(self, *a, **k):
            built.append(a[1])
            super().__init__(*a, **k)

    class CountingAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(tracing, "Span", CountingSpan)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    TRACER.set_profiler_prefix("t.")  # on, but nothing is sampled
    rb, reqs, *_ = stored
    assert TRACER.current() is None and TRACER.sample_rate == 0.0
    rb.rebuild_many(reqs)
    replay_packed(_packed())
    assert built == [] and opened == []
    assert TRACER.spans() == []
    # the same run sampled does build and annotate: the counters work
    with TRACER.trace("caller", sampled=True):
        replay_packed(_packed())
    assert "replay.launch" in built and "t.replay.launch" in opened


def test_annotations_land_on_the_profilers_host_planes(stored, tmp_path):
    from jax.profiler import ProfileData

    rb, reqs, *_ = stored
    rb.rebuild_many(reqs[:2])  # compile outside the profile
    TRACER.set_profiler_prefix("t.")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TRACER.trace("caller", sampled=True):
            rb.rebuild_many(reqs[:2])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"t.caller", "t.rebuild_many", "t.rebuild.read",
            "t.dispatch.pack", "t.dispatch.launch"} <= names


class TestTracerSeams:
    def test_entry_is_a_child_of_the_current_span_else_a_rolled_root(self):
        t = Tracer(sample_rate=0.0)
        assert t.entry("e") is NOOP_SPAN
        with t.trace("root", sampled=True) as root:
            with t.entry("e", service="replay") as e:
                assert e.parent_id == root.span_id
        t.configure(sample_rate=1.0)
        with t.entry("e2") as e2:
            assert e2.parent_id == "" and e2.trace_id != root.trace_id

    def test_profiler_prefix_is_off_by_default_and_restorable(self):
        t = Tracer()
        assert t.profiler_prefix is None
        assert t.set_profiler_prefix("p.") is None
        assert t.set_profiler_prefix(None) == "p."

    def test_start_s_is_the_monotonic_start(self):
        import time

        t = Tracer()
        before = time.perf_counter()
        with t.trace("a", sampled=True) as a:
            with t.span("b") as b:
                pass
        after = time.perf_counter()
        assert before <= a.start_s <= b.start_s <= after
        assert b.start_s + b.dur_us / 1e6 <= a.start_s + a.dur_us / 1e6

    def test_dropped_counts_spans_off_the_ring_until_clear(self):
        t = Tracer(capacity=2)
        for i in range(5):
            with t.trace(f"s{i}", sampled=True):
                pass
        assert t.dropped == 3
        t.clear()
        assert t.dropped == 0


def test_pprof_device_trace_turns_annotation_on_and_off(tmp_path):
    import urllib.request

    from cadence_tpu.utils.pprof import PProfServer

    srv = PProfServer().start()
    try:
        base = f"http://{srv.address}/debug/pprof/device"
        req = urllib.request.Request(f"{base}/start?dir={tmp_path}",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
        assert TRACER.profiler_prefix == ""
        req = urllib.request.Request(f"{base}/stop", method="POST")
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
        assert TRACER.profiler_prefix is None
    finally:
        srv.stop()

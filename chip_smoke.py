#!/usr/bin/env python3
"""Chip smoke: drive the replay path end to end on a TPU, once.

One process, and it fails on anything but a TPU: the device check runs
first and there is no CPU branch on the measured path. Phases, in order,
each printing one JSON line (sizes, compile/run seconds, rows compared,
mismatches, peak device bytes):

  a  deep replay      16,384 unique retry_deep histories at depth 1000
                      through ops.replay.replay_packed (Pallas kernel,
                      two bt=8192 tiles), every row compared exactly
                      with the C++ sequential replayer (native)
  b  lane-packed      echo histories lane-packed (8192 lanes x 256
                      steps, seg_align 16) through replay_packed
                      (Pallas packed kernel), every snapshot compared
                      with the C++ replayer on the same segments
  c  service rebuild  2,048 fuzzed mixed-depth histories loaded into a
                      history store, rebuilt by ONE
                      StateRebuilder.rebuild_many(use_device=True),
                      a sample checked against the host rebuild
  d  server           Onebox(4 shards, serving, checkpoints) behind the
                      gRPC frontend; 64 workflows driven through an
                      activity and a signal to completion, then read
                      back through the resident serving plane

``--chips 4`` runs only the four-chip path (batch-sharded replay, NDC
snapshot exchange, pipelined replay with seq=2 over 4 x 8,192 ndc_storm
rows) and what it is compared with (a one-device replay_packed of the
same batch on device 0, and host-side digests/counters).

``--cpu-rehearsal`` runs the same phases at tiny sizes on the CPU (with
``--chips 4``: on 4 virtual CPU devices) so a tier-1 test keeps this
script from rotting. It never prints the TPU result line.

The last line of stdout, on success only:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}
Any mismatch or exception exits non-zero without it.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

FULL = dict(
    deep_n=16384, deep_depth=1000, deep_max_events=1024, pack_chunk=1024,
    lanes=8192, lane_len=256, seg_align=16,
    rebuild_n=2048, rebuild_min=20, rebuild_max=1000, rebuild_sample=256,
    server_wf=64,
    mc_unique=2048, mc_rows_per_chip=8192, mc_depth=1000,
    mc_max_events=1024,
)
REHEARSAL = dict(
    deep_n=40, deep_depth=50, deep_max_events=64, pack_chunk=16,
    lanes=16, lane_len=64, seg_align=16,
    rebuild_n=24, rebuild_min=10, rebuild_max=60, rebuild_sample=24,
    server_wf=6,
    mc_unique=16, mc_rows_per_chip=16, mc_depth=40, mc_max_events=64,
)


def _parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny sizes on the CPU; never the TPU result line")
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# measurement helpers
# ---------------------------------------------------------------------------


class _CompileClock:
    """Sums XLA backend-compile seconds (JAX's own monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0
        event = self.EVENT

        def listen(name, secs, **_):
            if name == event:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(listen)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def _spy(module, name, calls):
    """Record every call of ``module.name`` (the kernel entry point a
    facade resolves at call time), so the phase can lower exactly the
    call that ran."""
    orig = getattr(module, name)

    def spy(*args, **kwargs):
        calls.append((args, kwargs))
        return orig(*args, **kwargs)

    setattr(module, name, spy)
    try:
        yield orig
    finally:
        setattr(module, name, orig)


def _lowered_text(fn, args, kwargs, n_array_args):
    """StableHLO text of ``fn`` lowered on the recorded call: the first
    ``n_array_args`` positionals and every array-valued keyword become
    jit arguments, everything else stays static."""
    import jax
    import numpy as np

    pos = list(args[:n_array_args])
    static_pos = args[n_array_args:]
    dyn_kw = {k: v for k, v in kwargs.items()
              if isinstance(v, (np.ndarray, jax.Array))}
    static_kw = {k: v for k, v in kwargs.items() if k not in dyn_kw}

    def call(pos, dyn_kw):
        return fn(*pos, *static_pos, **dyn_kw, **static_kw)

    return jax.jit(call).lower(pos, dyn_kw).as_text()


def _rows_mismatched(got, want) -> int:
    """Rows of a StateTensors batch where any leaf differs."""
    import jax
    import numpy as np

    bad = None
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} != {w.shape}")
        row_bad = (g != w).reshape(g.shape[0], -1).any(axis=1)
        bad = row_bad if bad is None else (bad | row_bad)
    return int(bad.sum())


class _Phase:
    """One phase's JSON line; a mismatch ends the script non-zero."""

    def __init__(self, name, clock):
        self.rec = {"phase": name}
        self.clock = clock
        self.c0 = clock.total
        self.t0 = time.perf_counter()

    def emit(self):
        self.rec["compile_s"] = self.clock.total - self.c0
        self.rec["wall_s"] = time.perf_counter() - self.t0
        self.rec["peak_bytes_in_use"] = _peak_bytes()
        print(json.dumps(self.rec), flush=True)
        if self.rec["mismatches"]:
            raise SystemExit(
                f"phase {self.rec['phase']}: "
                f"{self.rec['mismatches']} mismatches")


def _timed_twice(fn):
    """(first result, first-call s, second result, steady-call s): the
    first call compiles, the second measures the run alone (both end in
    a host fetch)."""
    t0 = time.perf_counter()
    r1 = fn()
    t1 = time.perf_counter()
    r2 = fn()
    return r1, t1 - t0, r2, time.perf_counter() - t1


REHEARSED = "not checked (cpu rehearsal)"


def _check_chip_path(text, rec):
    if "tpu_custom_call" not in text:
        raise SystemExit(f"phase {rec['phase']}: the replay call did not "
                         "lower to a TPU kernel (tpu_custom_call missing)")
    rec["chip_path"] = "tpu_custom_call"


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def _retry_deep_caps(max_events):
    from cadence_tpu.ops import schema as S

    # bench.py's retry_deep capacities
    return S.Capacities(max_events=max_events, max_activities=4,
                        max_timers=2, max_children=2, max_request_cancels=2,
                        max_signals_ext=2, max_version_items=2)


def _pack_in_chunks(make, n, caps, chunk):
    """pack_histories over ``n`` generated histories, ``chunk`` at a time
    (the event objects of one chunk are dropped before the next is made),
    merged into one PackedHistories."""
    import numpy as np

    from cadence_tpu.native import scatter_batch_major
    from cadence_tpu.ops.pack import PackedHistories, pack_histories

    rows, lengths, side, epochs = [], [], [], set()
    for lo in range(0, n, chunk):
        pk = pack_histories([make(i) for i in range(lo, min(n, lo + chunk))],
                            caps=caps)
        rows.append(np.array(pk.rows_concat))
        lengths.append(pk.lengths)
        side.extend(pk.side)
        epochs.add(pk.epoch_s)
    if len(epochs) != 1:
        raise AssertionError(f"chunks packed against epochs {epochs}")
    rows_concat = np.concatenate(rows)
    lengths = np.concatenate(lengths)
    return PackedHistories(
        events=scatter_batch_major(rows_concat, lengths, caps.max_events),
        lengths=lengths, side=side, caps=caps, epoch_s=epochs.pop(),
        rows_concat=rows_concat)


def _unique_rows(packed) -> int:
    return len({
        hashlib.blake2b(packed.events[i, : packed.lengths[i]]).digest()
        for i in range(packed.batch)
    })


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_deep(z, seed, clock, on_tpu):
    from cadence_tpu import native
    from cadence_tpu.ops import replay_pallas
    from cadence_tpu.ops.replay import replay_packed
    from cadence_tpu.testing import workloads as W

    ph = _Phase("a_deep_replay", clock)
    caps = _retry_deep_caps(z["deep_max_events"])
    rng = random.Random(seed)
    t0 = time.perf_counter()
    packed = _pack_in_chunks(
        lambda i: (f"deep-{i}", f"run-{i}",
                   W.retry_deep_history(rng, depth=z["deep_depth"])),
        z["deep_n"], caps, z["pack_chunk"])
    gen_s = time.perf_counter() - t0
    want = native.replay_sequential(packed)
    calls = []
    with _spy(replay_pallas, "replay_scan_pallas_teb", calls) as kernel:
        got1, first_s, got, run_s = _timed_twice(
            lambda: replay_packed(packed))
    ph.rec.update(
        sizes={"histories": packed.batch, "max_events": caps.max_events,
               "events": int(packed.lengths.sum()),
               "event_bytes": int(packed.events.nbytes),
               "unique_histories": _unique_rows(packed)},
        host_setup_s=gen_s, first_call_s=first_s, run_s=run_s,
        kernel_calls=len(calls), rows_compared=packed.batch,
        mismatches=(_rows_mismatched(got, want)
                    + _rows_mismatched(got1, want)),
    )
    if on_tpu:
        if len(calls) != 2:
            raise SystemExit(f"deep replay made {len(calls)} Pallas calls")
        args, kwargs = calls[-1]
        ph.rec["batch_tiles"] = -(-packed.batch // kwargs["bt"])
        _check_chip_path(_lowered_text(kernel, args, kwargs, 2), ph.rec)
    else:
        ph.rec["chip_path"] = REHEARSED
    ph.emit()


def _lane_reference(lanes):
    """The lane-packed segments regrouped one history per row (padding
    rows included: type -1 is a no-op), for the C++ replayer."""
    import types

    import numpy as np

    from cadence_tpu.ops import schema as S

    seg_len = max(end - start for segs in lanes.lane_segments
                  for _, start, end in segs)
    ev = np.zeros((lanes.n_histories, seg_len, S.EV_N), np.int32)
    ev[:, :, S.EV_TYPE] = -1
    lengths = np.zeros((lanes.n_histories,), np.int64)
    for ln, segs in enumerate(lanes.lane_segments):
        for row, start, end in segs:
            ev[row, : end - start] = lanes.events[ln, start:end]
            lengths[row] = end - start
    return types.SimpleNamespace(events=ev, lengths=lengths,
                                 caps=lanes.caps)


def phase_lanes(z, seed, clock, on_tpu):
    from cadence_tpu import native
    from cadence_tpu.ops import replay_pallas
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import pack_lanes
    from cadence_tpu.ops.replay import replay_packed
    from cadence_tpu.testing import workloads as W

    ph = _Phase("b_lane_packed", clock)
    # bench.py's echo capacities
    caps = S.Capacities(max_events=16, max_activities=2, max_timers=2,
                        max_children=2, max_request_cancels=2,
                        max_signals_ext=2, max_version_items=2)
    rng = random.Random(seed + 1)
    t0 = time.perf_counter()
    # an echo history (11 events) takes one seg_align block, so the
    # lane grid holds lane_len // seg_align of them per lane
    n = z["lanes"] * (z["lane_len"] // z["seg_align"])
    hs = [(f"echo-{i}", f"run-{i}", W.echo_history(v=rng.randint(1, 9999)))
          for i in range(n)]
    lanes = pack_lanes(hs, caps=caps, target_lane_len=z["lane_len"],
                       seg_align=z["seg_align"])
    del hs
    gen_s = time.perf_counter() - t0
    if lanes.lanes != z["lanes"]:
        raise AssertionError(f"packed {lanes.lanes} lanes, "
                             f"expected {z['lanes']}")
    want = native.replay_sequential(_lane_reference(lanes))
    calls = []
    with _spy(replay_pallas, "replay_scan_pallas_packed", calls) as kernel:
        got1, first_s, got, run_s = _timed_twice(
            lambda: replay_packed(lanes))
    ph.rec.update(
        sizes={"histories": lanes.n_histories, "lanes": lanes.lanes,
               "scan_len": lanes.scan_len, "seg_align": lanes.seg_align,
               "events": lanes.total_events,
               "padding_frac": lanes.padding_frac},
        host_setup_s=gen_s, first_call_s=first_s, run_s=run_s,
        kernel_calls=len(calls), rows_compared=lanes.n_histories,
        mismatches=(_rows_mismatched(got, want)
                    + _rows_mismatched(got1, want)),
    )
    if on_tpu:
        if len(calls) != 2:
            raise SystemExit(f"lane replay made {len(calls)} Pallas calls")
        args, kwargs = calls[-1]
        _check_chip_path(_lowered_text(kernel, args, kwargs, 5), ph.rec)
    else:
        ph.rec["chip_path"] = REHEARSED
    ph.emit()


def phase_rebuild(z, seed, clock, on_tpu):
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.unpack import mutable_state_to_snapshot
    from cadence_tpu.runtime.persistence.memory import create_memory_bundle
    from cadence_tpu.runtime.replication.rebuilder import (
        RebuildRequest, StateRebuilder,
    )
    from cadence_tpu.testing.event_generator import HistoryFuzzer
    from cadence_tpu.utils.metrics import Scope

    ph = _Phase("c_service_rebuild", clock)
    rng = random.Random(seed + 2)
    t0 = time.perf_counter()
    history = create_memory_bundle().history
    reqs, depths = [], []
    for i in range(z["rebuild_n"]):
        fz = HistoryFuzzer(seed=seed * 1_000_003 + i, caps=S.Capacities())
        # close_prob 0: each history runs to its drawn depth, then closes
        batches = fz.generate(
            target_events=rng.randint(z["rebuild_min"], z["rebuild_max"]),
            close_prob=0.0)
        depths.append(sum(len(b) for b in batches))
        run = f"rebuild-run-{i}"
        branch = history.new_history_branch(tree_id=run)
        for txn, b in enumerate(batches, start=1):
            history.append_history_nodes(branch, b, transaction_id=txn)
        reqs.append(RebuildRequest(
            domain_id="dom", workflow_id=f"rebuild-{i}", run_id=run,
            branch_token=branch.to_json().encode()))
    gen_s = time.perf_counter() - t0

    metrics = Scope()
    rb = StateRebuilder(history, metrics=metrics)
    host_fallbacks = []
    host_rebuild = rb.rebuild

    def counted(req):  # rebuild_many's per-request host fallback
        host_fallbacks.append(req.workflow_id)
        return host_rebuild(req)

    rb.rebuild = counted
    dev1, first_s, dev, run_s = _timed_twice(lambda: rb.rebuild_many(
        reqs, use_device=True))
    reg = metrics.registry
    batches = reg.counter_value("device_batches")
    pallas = reg.counter_value(
        "device_batches", {"layer": "device", "kernel": "pallas",
                           "mode": "lanes"})

    sample = sorted(rng.sample(range(len(reqs)),
                               min(z["rebuild_sample"], len(reqs))))
    oracle = StateRebuilder(history)
    bad = 0
    for i in sample:
        h_ms, h_tr, h_ti = oracle.rebuild(reqs[i])
        for d_ms, d_tr, d_ti in (dev[i], dev1[i]):
            same = (
                mutable_state_to_snapshot(h_ms)
                == mutable_state_to_snapshot(d_ms)
                and [t.task_type for t in h_tr]
                == [t.task_type for t in d_tr]
                and [(t.task_type, t.visibility_timestamp) for t in h_ti]
                == [(t.task_type, t.visibility_timestamp) for t in d_ti])
            bad += not same
    ph.rec.update(
        sizes={"histories": len(reqs), "events": sum(depths),
               "min_depth": min(depths), "max_depth": max(depths)},
        host_setup_s=gen_s, first_call_s=first_s, run_s=run_s,
        device_batches=batches, pallas_lane_batches=pallas,
        host_fallbacks=len(host_fallbacks),
        rows_compared=len(sample), mismatches=bad,
    )
    if host_fallbacks or not batches:
        raise SystemExit(f"rebuild_many fell back to the host for "
                         f"{len(host_fallbacks)} requests "
                         f"({batches} device batches)")
    if on_tpu:
        if pallas != batches:
            raise SystemExit(f"only {pallas} of {batches} rebuild batches "
                             "rode the Pallas lane kernel")
        ph.rec["chip_path"] = "pallas lanes"
    else:
        ph.rec["chip_path"] = REHEARSED
    ph.emit()


def _drive_workflows(fe, domain, task_list, run_ids):
    """Scripted poller: each workflow schedules one activity, completes
    it, waits for its signal, then completes."""
    from cadence_tpu.core.enums import DecisionType, EventType
    from cadence_tpu.runtime.api import Decision, SignalRequest

    seen = {wf: set() for wf in run_ids}
    signaled = set()
    closed = set()
    deadline = time.monotonic() + 600
    while len(closed) < len(run_ids):
        if time.monotonic() > deadline:
            raise SystemExit(f"server phase: {len(closed)} of "
                             f"{len(run_ids)} workflows closed in time")
        task = fe.poll_for_decision_task(domain, task_list,
                                         identity="smoke", timeout_s=0.2)
        if task is not None:
            types = seen[task.workflow_id]
            types.update(e.event_type for e in task.history)
            if EventType.ActivityTaskScheduled not in types:
                decisions = [Decision(DecisionType.ScheduleActivityTask, {
                    "activity_id": "a1", "activity_type": "smoke-act",
                    "task_list": task_list, "input": b"ping",
                    "schedule_to_close_timeout_seconds": 60,
                    "start_to_close_timeout_seconds": 60,
                })]
            elif (EventType.ActivityTaskCompleted in types
                  and EventType.WorkflowExecutionSignaled in types):
                decisions = [Decision(
                    DecisionType.CompleteWorkflowExecution,
                    {"result": b"done"})]
                closed.add(task.workflow_id)
            else:
                decisions = []
            fe.respond_decision_task_completed(task.task_token, decisions)
        act = fe.poll_for_activity_task(domain, task_list,
                                        identity="smoke", timeout_s=0.2)
        if act is not None:
            fe.respond_activity_task_completed(act.task_token,
                                               result=b"pong")
            if act.workflow_id not in signaled:
                fe.signal_workflow_execution(SignalRequest(
                    domain=domain, workflow_id=act.workflow_id,
                    run_id=run_ids[act.workflow_id], signal_name="go",
                    input=b"1"))
                signaled.add(act.workflow_id)


def phase_server(z, seed, clock, on_tpu):
    from cadence_tpu.rpc import FrontendRPCServer, RemoteFrontend
    from cadence_tpu.runtime.api import StartWorkflowRequest
    from cadence_tpu.testing.onebox import Onebox

    ph = _Phase("d_server", clock)
    box = Onebox(num_shards=4, serving=True, checkpoints=True).start()
    server = FrontendRPCServer(box.frontend, box.admin).start()
    fe = RemoteFrontend(server.address)
    try:
        domain, tl = "smoke-dom", "smoke-tl"
        fe.register_domain(domain)
        t0 = time.perf_counter()
        run_ids = {}
        for i in range(z["server_wf"]):
            wf = f"smoke-wf-{seed}-{i}"
            run_ids[wf] = fe.start_workflow_execution(StartWorkflowRequest(
                domain=domain, workflow_id=wf, workflow_type="smoke",
                task_list=tl, request_id=f"smoke-req-{i}",
                execution_start_to_close_timeout_seconds=600))
        _drive_workflows(fe, domain, tl, run_ids)
        run_s = time.perf_counter() - t0
        running = [wf for wf, run in run_ids.items()
                   if fe.describe_workflow_execution(
                       domain, wf, run).is_running]
        # the serving plane: the first read seats each closed workflow
        # in a resident lane (device replay), the second answers from it
        dom_id = box.domains.get_by_name(domain).info.id
        t0 = time.perf_counter()
        bad_reads = 0
        for wf, run in run_ids.items():
            box.history.serving_read(dom_id, wf, run)
            got = box.history.serving_read(dom_id, wf, run)
            bad_reads += (got is None or not got.resident
                          or got.snapshot["exec"]["close_status"] == 0)
        reads_s = time.perf_counter() - t0
        reg = box.metrics.registry
        ph.rec.update(
            sizes={"workflows": len(run_ids), "shards": 4},
            run_s=run_s, serving_reads_s=reads_s,
            rows_compared=len(run_ids),
            still_running=len(running), bad_serving_reads=bad_reads,
            mismatches=len(running) + bad_reads,
            serving_cold_misses=reg.counter_value("serving_cold_misses"),
            serving_resident_hits=reg.counter_value(
                "serving_resident_hits"),
        )
    finally:
        fe.close()
        server.stop()
        box.stop()
    ph.emit()


# ---------------------------------------------------------------------------
# four-chip path
# ---------------------------------------------------------------------------


def _spans(arr, devices, what):
    """``arr`` must live on every device of the mesh, never on one."""
    got = set(arr.sharding.device_set)
    if got != set(devices):
        raise SystemExit(f"{what} lives on {len(got)} of "
                         f"{len(devices)} devices")


def phase_four_chip(z, seed, clock, on_tpu):
    import jax
    import numpy as np

    from cadence_tpu.native import scatter_batch_major
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import PackedHistories, pack_histories
    from cadence_tpu.ops.replay import replay_packed
    from cadence_tpu.parallel import (
        make_mesh, ndc_snapshot_exchange, replay_packed_sharded,
        replay_pipelined, replay_sharded_fn,
    )
    from cadence_tpu.parallel.mesh import events_spec, shard_spec
    from cadence_tpu.parallel.replay_sharded import _DIGEST_COLS
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.testing.event_generator import HistoryFuzzer

    ph = _Phase("four_chip", clock)
    devices = jax.devices()
    caps = S.Capacities(max_events=z["mc_max_events"])
    t0 = time.perf_counter()
    fz = HistoryFuzzer(seed=seed + 3, caps=caps)
    uniq = pack_histories(
        [(f"ndc-{i}", f"run-{i}",
          W.ndc_storm_history(fz, depth=z["mc_depth"]))
         for i in range(z["mc_unique"])], caps=caps)
    # tile the uniques up to 4 x rows_per_chip rows
    B = len(devices) * z["mc_rows_per_chip"]
    per = np.split(np.asarray(uniq.rows_concat),
                   np.cumsum(uniq.lengths)[:-1])
    idx = [i % uniq.batch for i in range(B)]
    rows = np.concatenate([per[i] for i in idx])
    lengths = uniq.lengths[idx].astype(np.int32)
    packed = PackedHistories(
        events=scatter_batch_major(rows, lengths, caps.max_events),
        lengths=lengths, side=[uniq.side[i] for i in idx], caps=caps,
        epoch_s=uniq.epoch_s, rows_concat=rows)
    gen_s = time.perf_counter() - t0

    # reference: one-device replay_packed of the same batch on device 0
    t0 = time.perf_counter()
    want = replay_packed(packed)
    ref_s = time.perf_counter() - t0

    mesh = make_mesh()
    if set(mesh.devices.flat) != set(devices):
        raise SystemExit("the mesh does not span jax.devices()")
    t0 = time.perf_counter()
    got, _ = replay_packed_sharded(packed, mesh)
    sharded_s = time.perf_counter() - t0
    bad_sharded = _rows_mismatched(got, want)

    # the same program with its device arrays kept: every output shard
    # lives on its own device, then the NDC exchange runs over ICI
    state0 = jax.device_put(
        jax.tree_util.tree_map(np.asarray, S.empty_state(B, caps)),
        shard_spec(mesh))
    final = replay_sharded_fn(mesh)(
        state0, jax.device_put(packed.time_major(), events_spec(mesh)))[0]
    _spans(final.exec_info, devices, "sharded replay output")
    t0 = time.perf_counter()
    digests, vh, vh_len, replayed, max_version = jax.block_until_ready(
        ndc_snapshot_exchange(final, mesh))
    exchange_s = time.perf_counter() - t0
    _spans(digests, devices, "all-gathered digests")
    ex = want.exec_info
    want_digest = np.stack([ex[:, c] for c in _DIGEST_COLS], axis=-1)
    bad_exchange = int(
        (np.asarray(digests) != want_digest).any(axis=1).sum()
        + (np.asarray(vh) != want.vh_items).reshape(B, -1).any(axis=1).sum()
        + (np.asarray(vh_len) != want.vh_len).sum()
        + (int(replayed) != int((ex[:, S.X_START_TS] > 0).sum()))
        + (int(max_version) != int(ex[:, S.X_CUR_VERSION].max())))

    mesh2 = make_mesh(seq=2)
    t0 = time.perf_counter()
    piped = replay_pipelined(
        jax.tree_util.tree_map(np.asarray, S.empty_state(B, caps)),
        packed.time_major(), mesh2)
    _spans(piped.exec_info, devices, "pipelined replay output")
    piped = jax.tree_util.tree_map(np.asarray, piped)
    pipelined_s = time.perf_counter() - t0
    bad_piped = _rows_mismatched(piped, want)

    ph.rec.update(
        sizes={"rows": B, "rows_per_device": z["mc_rows_per_chip"],
               "unique_histories": uniq.batch,
               "max_events": caps.max_events,
               "events": int(lengths.sum()),
               "mesh": dict(mesh.shape), "pipelined_mesh":
               dict(mesh2.shape)},
        host_setup_s=gen_s, reference_s=ref_s, sharded_s=sharded_s,
        exchange_s=exchange_s, pipelined_s=pipelined_s,
        rows_compared=B,
        mismatches_sharded=bad_sharded, mismatches_exchange=bad_exchange,
        mismatches_pipelined=bad_piped,
        mismatches=bad_sharded + bad_exchange + bad_piped,
        replayed=int(replayed), max_version=int(max_version),
    )
    ph.emit()


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    args = _parse(argv)
    if args.cpu_rehearsal:
        # exactly --chips virtual CPU devices, whatever the caller set
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={args.chips}"])
    import jax

    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "--cpu-rehearsal is the only CPU mode", file=sys.stderr)
        return 2
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 but JAX sees {len(devices)} "
              "devices", file=sys.stderr)
        return 2

    sys.path.insert(0, REPO)
    from cadence_tpu.utils.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    z = REHEARSAL if args.cpu_rehearsal else FULL
    print(json.dumps({"start": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "chips": args.chips,
        "seed": args.seed, "rehearsal": args.cpu_rehearsal,
        "compile_cache": cache}}), flush=True)
    clock = _CompileClock()
    if args.chips == 4:
        phase_four_chip(z, args.seed, clock, on_tpu)
    else:
        for phase in (phase_deep, phase_lanes, phase_rebuild,
                      phase_server):
            phase(z, args.seed, clock, on_tpu)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.cpu_rehearsal:
        print(json.dumps({"ok": True, "rehearsal": "cpu",
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""North-star benchmark: batched history-replay throughput vs a compiled
host baseline, across the five BASELINE.md workload configurations.

One device step = replay scan + vectorized task refresh, i.e. the full
rebuild semantics of the reference's nDCStateRebuilder.rebuild
(/root/reference/service/history/nDCStateRebuilder.go:92-160: replay all
batches, then taskRefresher.refreshTasks).

Baseline: ``native.replay_sequential`` — the C++ (-O3) sequential
replayer in native/sidecar.cpp, one workflow and one event at a time
with bit-identical transition semantics (differential-tested in
tests/test_native_replayer.py). This is the compiled stand-in for the
reference's Go stateBuilder.applyEvents loop
(/root/reference/service/history/stateBuilder.go:112-613) — measured on
this host, on the same packed tensors, so ``vs_baseline`` compares the
same computation on the same data. If anything it is a *stronger*
baseline than Go, which replays into pointer-heavy structs and maps.

Timing discipline: every device timing chains ``iters`` dependent
kernel calls and then fetches a scalar checksum that data-depends on
the final state — the wall clock covers exactly ``iters`` full
executions, nothing hides in the async queue.

Two device kernels are reported side by side:
  xla     lax.scan over replay_step (ops/replay.py) — state carry
          round-trips HBM every step
  pallas  VMEM-resident-state kernel (ops/replay_pallas.py), fed the
          field-major event layout + host-precomputed presence masks
          from the C++ packer — bound by streaming the event tensor

The roofline column reports the effective HBM bandwidth implied by each
kernel's event+state traffic vs the measured copy bandwidth of this
chip (``streams_gbps`` / ``copy_bw_gbps``).

Workload configs (BASELINE.md / reference canary/const.go:64-84):
  echo        1k-class workflows, ~11-event histories
  signal      signal-heavy ragged histories
  timer_storm timer-fire-dominated streams
  retry_deep  ~1k-event activity-retry histories (the headline config)
  ndc_storm   mixed fuzzer histories + ICI snapshot exchange

Prints ONE JSON line: the headline metric (histories/s at ~1k-event
depth, vs_baseline against the C++ replayer) plus per-config numbers
under "configs".
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

# BENCH_SMOKE=1: tiny shapes for CI coverage of the harness itself
# (tests/test_bench_smoke.py) — minutes -> seconds, CPU-safe.
SMOKE = os.environ.get("BENCH_SMOKE") == "1"

CPU_RUN = "--cpu" in sys.argv or SMOKE


def _device_record() -> dict:
    """The device this run measures, as JAX reports it. A run that was
    not asked for the CPU (``--cpu`` / ``BENCH_SMOKE=1``) and finds no
    TPU exits non-zero here, before any record is written: a CPU number
    is never reported under the device metric's name."""
    if CPU_RUN:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "tpu" and not CPU_RUN:
        print(f"bench.py: no TPU (JAX found {devs[0].platform}); pass "
              "--cpu or BENCH_SMOKE=1 for a CPU run", file=sys.stderr)
        sys.exit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---- single-print guarantee + wall-clock watchdog -----------------------
# The driver records stdout; whatever happens (a hang mid-run,
# unbounded compile, crash) exactly one parseable JSON line must appear.
_PRINT_LOCK = threading.Lock()
_PRINTED = False
_PARTIAL: dict = {}


def _emit(obj) -> None:
    global _PRINTED
    with _PRINT_LOCK:
        if _PRINTED:
            return
        _PRINTED = True
        print(json.dumps(obj), flush=True)


def _fail_record(error: str) -> dict:
    """Shared shape for any non-success record (driver parses these keys)."""
    head = _PARTIAL.get("retry_deep") or {}
    return {
        "metric": "histories_replayed_per_sec_at_1k_depth",
        "value": head.get("histories_per_sec", 0),
        "unit": "histories/s",
        "vs_baseline": head.get("vs_baseline", 0),
        "error": error,
        "configs": dict(_PARTIAL),
    }


def _watchdog(wall_s: float) -> None:
    def fire():
        _emit(_fail_record(
            f"wall-clock watchdog fired after {wall_s:.0f}s "
            "(backend hung or compile unbounded)"))
        os._exit(0)
    t = threading.Timer(wall_s, fire)
    t.daemon = True
    t.start()

def _build_histories(config: str, n_unique: int, caps):
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.testing.event_generator import HistoryFuzzer

    rng = random.Random(42)
    fz = HistoryFuzzer(seed=42, caps=caps)
    retry_depth, timer_depth, ndc_depth = (
        (40, 40, 40) if SMOKE else (1000, 400, 1000))
    out = []
    for i in range(n_unique):
        if config == "echo":
            b = W.echo_history()
        elif config == "signal":
            b = W.signal_history(rng)
        elif config == "timer_storm":
            b = W.timer_storm_history(rng, depth=timer_depth)
        elif config == "retry_deep":
            b = W.retry_deep_history(rng, depth=retry_depth)
        else:  # ndc_storm
            b = W.ndc_storm_history(fz, depth=ndc_depth)
        out.append((f"wf-{i}", f"run-{i}", b))
    return out


def _tile(packed, batch: int):
    """Tile a packed batch of uniques up to `batch` rows (batch-major)."""
    n = packed.events.shape[0]
    reps = (batch + n - 1) // n
    events = np.tile(packed.events, (reps, 1, 1))[:batch]
    lengths = np.tile(packed.lengths, reps)[:batch]
    return events, lengths


def _pack_tiled_lanes(histories, caps, lanes: int, lane_len: int):
    """Tile a small unique set into a full PackedLanes grid — the packed
    analogue of ``_tile``: pack each unique once (host packing cost stays
    O(uniques)), then fill every lane back-to-back, exactly the layout
    ops/pack.pack_lanes produces for a homogeneous stream."""
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import (
        PackedLanes, pack_histories, round_scan_len,
    )

    ph = pack_histories(histories, caps=caps)
    per = [
        np.asarray(ph.events[i, : ph.lengths[i]])
        for i in range(len(histories))
    ]
    t = round_scan_len(lane_len)
    events = np.zeros((lanes, t, S.EV_N), np.int32)
    events[:, :, S.EV_TYPE] = -1
    seg_end = np.zeros((lanes, t), bool)
    out_row = np.zeros((lanes, t), np.int32)
    lengths = []
    lane_segments = [[] for _ in range(lanes)]
    k = 0
    for ln in range(lanes):
        cur = 0
        while True:
            arr = per[k % len(per)]
            n = arr.shape[0]
            if cur + n > t:
                break
            events[ln, cur : cur + n] = arr
            seg_end[ln, cur + n - 1] = True
            out_row[ln, cur + n - 1] = len(lengths)
            lane_segments[ln].append((len(lengths), cur, cur + n))
            lengths.append(n)
            cur += n
            k += 1
    return PackedLanes(
        events=events, seg_end=seg_end, out_row=out_row,
        lengths=np.asarray(lengths, np.int32),
        side=[None] * len(lengths), caps=caps, epoch_s=ph.epoch_s,
        lane_segments=lane_segments,
    )


def _bench_config_packed(config: str, caps, lanes: int, lane_len: int,
                         iters: int, baseline_histories: int):
    """Lane-packed replay throughput (ragged time packing + depth
    bucketing): histories ride back-to-back in each lane, so the scan
    spends steps on real events instead of per-history padding —
    effective scan length per history is its own depth, not the batch
    max. The step body is statically specialized to the batch's event
    types (replay.type_signature). mixed_depth additionally splits the
    stream into depth buckets (ops/dispatch.depth_buckets semantics) so
    the 10% deep stragglers don't stretch the shallow lanes."""
    from cadence_tpu import native
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import pack_histories, round_scan_len
    from cadence_tpu.ops.refresh import refresh_tasks_device
    from cadence_tpu.ops.replay import (
        replay_scan, replay_scan_packed, type_signature,
    )
    from cadence_tpu.testing import workloads as W

    rng = random.Random(43)
    if config == "mixed_depth":
        sh_d, dp_d = (8, 40) if SMOKE else (16, 1000)
        shallow = [
            (f"wf-s{i}", f"run-s{i}", W.retry_deep_history(rng, depth=sh_d))
            for i in range(16)
        ]
        deep = [
            (f"wf-d{i}", f"run-d{i}", W.retry_deep_history(rng, depth=dp_d))
            for i in range(8)
        ]
        mean_sh = float(np.mean(
            [sum(len(b) for b in h[2]) for h in shallow]))
        mean_dp = float(np.mean(
            [sum(len(b) for b in h[2]) for h in deep]))
        # lane budget split for a 90/10 history mix: each class packs
        # its own depth-bucketed lanes
        share_d = 0.1 * mean_dp / (0.9 * mean_sh + 0.1 * mean_dp)
        lanes_d = max(1, round(lanes * share_d))
        lanes_s = max(1, lanes - lanes_d)
        packs = [
            _pack_tiled_lanes(shallow, caps, lanes_s, lane_len),
            _pack_tiled_lanes(deep, caps, lanes_d, lane_len),
        ]
        uniques = shallow + deep
        base_mix = (shallow, deep)
    else:  # echo
        uniques = _build_histories(config, 32, caps)
        packs = [_pack_tiled_lanes(uniques, caps, lanes, lane_len)]
        base_mix = None

    n_hist = sum(p.n_histories for p in packs)
    total_events = sum(p.total_events for p in packs)
    total_cells = sum(p.lanes * p.scan_len for p in packs)
    total_steps = sum(p.scan_len for p in packs)
    padding_frac = (total_cells - total_events) / max(total_events, 1)
    mean_depth = total_events / max(n_hist, 1)
    present = set()
    for p in packs:
        present.update(p.present_types)
    types = type_signature(present)

    arrays = []
    for p in packs:
        ev, seg, row = p.time_major()
        arrays.append((
            jnp.asarray(ev), jnp.asarray(seg), jnp.asarray(row),
            S.empty_state(round_scan_len(p.n_histories), caps),
        ))
    states0 = tuple(
        jax.device_put(jax.tree_util.tree_map(
            jnp.asarray, S.empty_state(p.lanes, caps)))
        for p in packs
    )

    def step(states):
        new_states, outs = [], []
        for st, (ev, seg, row, out0) in zip(states, arrays):
            out0j = jax.tree_util.tree_map(jnp.asarray, out0)
            st2, out = replay_scan_packed(
                st, out0j, ev, seg, row, types=types)
            new_states.append(st2)
            outs.append(refresh_tasks_device(out))
        return tuple(new_states), tuple(outs)

    step_j = jax.jit(step)
    dt, _ = _time_chained(step_j, states0, iters)
    rate = n_hist / dt
    results = {"xla_packed": {
        "histories_per_sec": round(rate, 2),
        "batch_rebuild_ms": round(dt * 1000, 3),
        "us_per_step": round(dt / total_steps * 1e6, 3),
        "scan_steps": total_steps,
    }}

    # per-dispatch latency distribution through the registry's
    # exponential-bucket histogram (utils/metrics.py): the headline
    # latency lines are Registry.timer_stats-backed p50/p99, the same
    # machinery the serving scopes report — not a bench-local avg/max
    from cadence_tpu.utils.metrics import Scope as _Scope

    lat = _Scope()
    st = states0
    for _ in range(max(8, iters * 2)):
        with lat.timer("batch_rebuild"):
            out = jax.block_until_ready(step_j(st))
        st = out[0]
    lat_stats = lat.registry.timer_stats("batch_rebuild")

    # ---- today's path on the same workload: one scan padded to the
    # deepest history — the number lane packing is judged against
    nb_u = min(512, n_hist)
    if base_mix is not None:
        sh, dp = base_mix
        n_dp = max(1, round(nb_u * 0.1))
        ev_s, len_s = _tile(pack_histories(sh, caps=caps), nb_u - n_dp)
        ev_d, len_d = _tile(pack_histories(dp, caps=caps), n_dp)
        events_u = np.concatenate([ev_s, ev_d], axis=0)
        lengths_u = np.concatenate([len_s, len_d])
    else:
        events_u, lengths_u = _tile(
            pack_histories(uniques, caps=caps), nb_u)
    ev_tm_u = jnp.asarray(
        np.ascontiguousarray(np.transpose(events_u, (1, 0, 2))))
    state_u = jax.device_put(jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(nb_u, caps)))

    def step_u(state):
        final = replay_scan(state, ev_tm_u)
        return final, refresh_tasks_device(final)

    dt_u, _ = _time_chained(jax.jit(step_u), state_u, max(2, iters // 2))
    unpacked_rate = nb_u / dt_u
    padding_u = (
        events_u.shape[0] * events_u.shape[1] - lengths_u.sum()
    ) / max(int(lengths_u.sum()), 1)

    # ---- compiled-host baseline on the same histories
    class _Sub:
        pass

    sub = _Sub()
    nb = min(baseline_histories, n_hist)
    if base_mix is not None:
        n_dp = max(1, round(nb * 0.1))
        ev_s, len_s = _tile(pack_histories(sh, caps=caps), nb - n_dp)
        ev_d, len_d = _tile(pack_histories(dp, caps=caps), n_dp)
        sub.events = np.concatenate([ev_s, ev_d], axis=0)
        sub.lengths = np.concatenate([len_s, len_d])
    else:
        sub.events, sub.lengths = _tile(
            pack_histories(uniques, caps=caps), nb)
    sub.caps = caps
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < 0.5:
        native.replay_sequential(sub)
        reps += 1
    cpp_rate = nb / ((time.perf_counter() - t0) / reps)

    return {
        "histories_per_sec": round(rate, 2),
        "kernel": "xla_packed",
        "packed": True,
        "baseline_cpp_per_sec": round(cpp_rate, 2),
        "vs_baseline": round(rate / cpp_rate, 2),
        "mean_depth": round(mean_depth, 1),
        "batch_rebuild_ms": round(dt * 1000, 3),
        "latency_p50_ms": round(lat_stats.p50 * 1e3, 3),
        "latency_p99_ms": round(lat_stats.p99 * 1e3, 3),
        "batch": n_hist,
        "lanes": sum(p.lanes for p in packs),
        "buckets": len(packs),
        "padding_frac": round(padding_frac, 4),
        "lanes_per_history": round(
            sum(p.lanes for p in packs) / max(n_hist, 1), 4),
        "unpacked_histories_per_sec": round(unpacked_rate, 2),
        "unpacked_padding_frac": round(float(padding_u), 4),
        "vs_unpacked": round(rate / unpacked_rate, 2),
        "kernels": results,
    }


def _bench_reshard_live(duration_s: float, load_threads: int = 2,
                        probe_interval_s: float = 0.004):
    """Elastic resharding under sustained load: a shard split executed
    mid-run while load threads drive the echo workflow end-to-end and a
    probe thread times every frontend start call (the routed write path
    — exactly what stalls while the source shard is fenced).

    Reports the steady-state completion rate next to the handoff
    record: total ``handoff_ms`` (dominated by the pre-fence checkpoint
    flush, which runs under live traffic), the write-unavailability
    ``pause_ms``, and the probe-call p50/p99 — overall and within the
    handoff window, the decision-latency cost of the reconfiguration.
    """
    import threading as _threading

    from cadence_tpu.runtime.api import StartWorkflowRequest
    from cadence_tpu.runtime.resharding import ReshardCoordinator
    from cadence_tpu.testing.onebox import Onebox
    from cadence_tpu.worker import Worker

    box = Onebox(num_shards=2, checkpoints=True,
                 start_worker=False).start()
    box.domain_handler.register_domain("bench")

    def _echo_wf(ctx, input):
        out = yield ctx.schedule_activity("echo", input)
        return out

    w = Worker(box.frontend, "bench", "bench-tl", identity="bench-w",
               sticky=False)
    w.register_workflow("echo-wf", _echo_wf)
    w.register_activity("echo", lambda x: x)
    w.start()

    stop = _threading.Event()
    completed = [0]
    lock = _threading.Lock()

    def _start(wid):
        return box.frontend.start_workflow_execution(StartWorkflowRequest(
            domain="bench", workflow_id=wid, workflow_type="echo-wf",
            task_list="bench-tl", input=b"x", request_id=f"req-{wid}",
            execution_start_to_close_timeout_seconds=60,
        ))

    def _load(tid):
        i = 0
        while not stop.is_set():
            wid = f"load-{tid}-{i}"
            try:
                rid = _start(wid)
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline and not stop.is_set():
                    d = box.frontend.describe_workflow_execution(
                        "bench", wid, rid
                    )
                    if not d.is_running:
                        with lock:
                            completed[0] += 1
                        break
                    time.sleep(0.002)
            except Exception:
                pass  # fenced-window stragglers: the probe counts those
            i += 1

    probes = []  # (t_monotonic, latency_s)

    def _probe():
        j = 0
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                _start(f"probe-{j}")
            except Exception:
                pass
            probes.append((t0, time.monotonic() - t0))
            j += 1
            time.sleep(probe_interval_s)

    threads = [
        _threading.Thread(target=_load, args=(t,), daemon=True)
        for t in range(load_threads)
    ] + [_threading.Thread(target=_probe, daemon=True)]
    t_run0 = time.monotonic()
    try:
        for t in threads:
            t.start()
        time.sleep(duration_s / 2)

        coord = ReshardCoordinator(
            box.persistence, [box.history.controller]
        )
        t_h0 = time.monotonic()
        plan = coord.split(0)
        t_h1 = time.monotonic()

        time.sleep(duration_s / 2)
    finally:
        # a failed split must not leak live pumps into later configs
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        elapsed = time.monotonic() - t_run0
        w.stop()
        box.stop()

    # headline percentiles through Registry.timer_stats — the same
    # exponential-bucket histograms the serving scopes report, not a
    # bench-local sorted-list estimator (utils/metrics.py)
    from cadence_tpu.utils.metrics import Scope as _Scope

    lat_scope = _Scope()
    lat_handoff = []
    for t0, dt in probes:
        lat_scope.record("start_latency", dt)
        if t_h0 <= t0 <= t_h1:
            lat_scope.tagged(window="handoff").record(
                "start_latency_handoff", dt
            )
            lat_handoff.append(dt)
    reg = lat_scope.registry
    lat_all_stats = reg.timer_stats("start_latency")
    lat_handoff_stats = reg.timer_stats("start_latency_handoff")
    return {
        "steady_rate_wf_per_sec": round(completed[0] / elapsed, 2),
        "workflows_completed": completed[0],
        "probe_calls": len(probes),
        "start_p50_ms": round(lat_all_stats.p50 * 1e3, 3),
        "start_p99_ms": round(lat_all_stats.p99 * 1e3, 3),
        "during_handoff": {
            "samples": len(lat_handoff),
            "p50_ms": round(lat_handoff_stats.p50 * 1e3, 3),
            "p99_ms": round(lat_handoff_stats.p99 * 1e3, 3),
            "max_ms": round(max(lat_handoff, default=0.0) * 1e3, 3),
        },
        "handoff": {
            "state": plan.state,
            "epoch": plan.epoch_to,
            "handoff_ms": round(plan.handoff_ms, 1),
            "pause_ms": round(plan.pause_ms, 1),
            "moved_workflows": plan.moved_workflows,
            "moved_tasks": plan.moved_tasks,
            "checkpoints_shipped": plan.checkpoints_shipped,
            "suffix_events_replayed": plan.suffix_events_replayed,
        },
    }


def _bench_replication_lag(workflows: int, signals_each: int,
                           bytes_per_s: float, payload: int = 96):
    """Geo-replication catch-up under a throttled WAN link: event-ship
    vs snapshot-ship vs adaptive (runtime/replication/transport.py).

    Per arm, a fresh two-cluster pair: the active side accumulates a
    replication backlog (starts + signals, no worker — every write
    mints a replication task), then the standby drains it through a
    seeded ``SimulatedLink`` with a ``bytes_per_s`` budget.

      events    the pre-adaptive pull plane (no transport): the full
                hydrated event backlog pages over the throttled link
      snapshot  mode controller pinned to snapshot shipping: one
                backlog probe, then per-run delta-compressed
                ReplayCheckpoints + deferred history backfill
      adaptive  the controller decides per measured budget (the
                mode-switch count proves it actually switched)

    ``catch_up_s`` is time-to-state-current (every standby run's state
    tip matches the active tip — what failover readiness means);
    ``converged_s`` additionally drains the history backfill debt so
    the standby is byte-identical. For the events arm the two
    coincide. ``events_replayed_saved`` on the snapshot arms proves the
    suffix-only resume path carried the installs.
    """
    import uuid as _uuid

    from cadence_tpu.client import HistoryClient, MatchingClient
    from cadence_tpu.cluster import ClusterInformation, ClusterMetadata
    from cadence_tpu.matching import MatchingEngine
    from cadence_tpu.runtime.api import SignalRequest, StartWorkflowRequest
    from cadence_tpu.runtime.domains import DomainCache, register_domain
    from cadence_tpu.runtime.membership import single_host_monitor
    from cadence_tpu.runtime.persistence.memory import create_memory_bundle
    from cadence_tpu.runtime.replication import (
        AdaptiveTransport,
        HistoryRereplicator,
        ReplicationTaskFetcher,
        ReplicationTaskProcessor,
    )
    from cadence_tpu.runtime.service import HistoryService
    from cadence_tpu.testing.faults import LinkProfile, chaos_link
    from cadence_tpu.utils.metrics import Scope

    DOMAIN = "repl-bench"

    def make_cluster(name, domain_id, metrics=None):
        meta = ClusterMetadata(
            failover_version_increment=10,
            master_cluster_name="active", current_cluster_name=name,
            cluster_info={
                "active": ClusterInformation(initial_failover_version=1),
                "standby": ClusterInformation(initial_failover_version=2),
            },
        )
        persistence = create_memory_bundle()
        register_domain(
            persistence.metadata, DOMAIN, is_global=True,
            clusters=["active", "standby"], active_cluster="active",
            domain_id=domain_id, failover_version=1,
        )
        domains = DomainCache(persistence.metadata)
        svc = HistoryService(
            1, persistence, domains, single_host_monitor(f"{name}-h"),
            cluster_metadata=meta, metrics=metrics,
        )
        hc = HistoryClient(svc.controller)
        matching = MatchingEngine(persistence.task, hc)
        svc.wire(MatchingClient(matching), hc)
        svc.start()
        return {"svc": svc, "hc": hc, "matching": matching,
                "persistence": persistence, "domain_id": domain_id}

    class Adapter:
        def __init__(self, svc):
            self.svc = svc

        def get_replication_messages(self, shard_id, last, max_tasks=None):
            return self.svc.get_replication_messages(
                shard_id, last, cluster="standby", max_tasks=max_tasks)

        def get_workflow_history_raw(self, *a):
            return self.svc.get_workflow_history_raw(*a)

        def get_replication_backlog(self, shard_id, last):
            return self.svc.get_replication_backlog(shard_id, last)

        def get_replication_checkpoint(self, *a):
            return self.svc.get_replication_checkpoint(*a)

    def run_arm(arm):
        domain_id = str(_uuid.uuid4())
        scope = Scope()
        active = make_cluster("active", domain_id)
        # one registry for the whole standby side: the transport's
        # counters and the rebuilder's events_replayed_saved must land
        # together for the record to read coherently
        standby = make_cluster("standby", domain_id, metrics=scope)
        runs = {}
        try:
            for i in range(workflows):
                wid = f"lag-wf-{i}"
                rid = active["hc"].start_workflow_execution(
                    StartWorkflowRequest(
                        domain=DOMAIN, workflow_id=wid,
                        workflow_type="echo", task_list="tl",
                        request_id=f"req-{wid}",
                        execution_start_to_close_timeout_seconds=600,
                    ))
                for k in range(signals_each):
                    active["hc"].signal_workflow_execution(SignalRequest(
                        domain=DOMAIN, workflow_id=wid,
                        signal_name=f"s{k}", input=b"x" * payload,
                        identity="bench",
                    ))
                runs[wid] = rid
            tips = {}
            total_events = 0
            for wid, rid in runs.items():
                resp = active["persistence"].execution.\
                    get_workflow_execution(0, domain_id, wid, rid)
                tips[wid] = resp.next_event_id - 1
                total_events += tips[wid]
            # small fetch pages: the first page is the link probe, not
            # the whole hydrated backlog in one transfer
            emit = active["svc"].controller.get_engine_for_shard(0)\
                .replicator_queue
            emit.batch_size = 8
            if arm != "events":
                # absorb the snapshot-serving compile (rebuild_many
                # device path) outside the timed window, exactly the
                # warm-up discipline every other config applies
                wid0 = next(iter(runs))
                active["svc"].get_replication_checkpoint(
                    domain_id, wid0, runs[wid0])

            link = chaos_link(
                Adapter(active["svc"]),
                LinkProfile(bytes_per_s=bytes_per_s), seed=7,
            )
            fetcher = ReplicationTaskFetcher("active", link)
            engine = standby["svc"].controller.get_engine_for_shard(0)
            transport = None
            if arm != "events":
                transport = AdaptiveTransport(
                    link, "active",
                    min_gap_events=8, min_dwell=1,
                    snapshot_bytes_prior=4096,
                    force_mode=("snapshot" if arm == "snapshot" else None),
                    metrics=scope,
                )
            rerepl = HistoryRereplicator(
                link, engine.ndc_replicator, transport=transport,
                metrics=scope,
            )
            proc = ReplicationTaskProcessor(
                engine.shard, engine.ndc_replicator, fetcher,
                rereplicator=rerepl, metrics=scope, transport=transport,
            )

            def state_current():
                ex = standby["persistence"].execution
                for wid, rid in runs.items():
                    try:
                        resp = ex.get_workflow_execution(
                            0, domain_id, wid, rid)
                    except Exception:
                        return False
                    if resp.next_event_id - 1 < tips[wid]:
                        return False
                return True

            t0 = time.monotonic()
            catch_up_s = None
            deadline = t0 + 300.0
            while time.monotonic() < deadline:
                n = proc.process_once()
                if catch_up_s is None and state_current():
                    catch_up_s = time.monotonic() - t0
                if n == 0 and catch_up_s is not None:
                    break
            converged_s = time.monotonic() - t0
            # byte-parity sanity: every replicated event landed
            standby_events = 0
            for wid, rid in runs.items():
                ev, _ = engine.get_workflow_execution_history(
                    DOMAIN, wid, rid)
                standby_events += len(ev)
            reg = scope.registry
            return {
                "catch_up_s": round(catch_up_s or converged_s, 3),
                "converged_s": round(converged_s, 3),
                "bytes_shipped": link.link.bytes_total,
                "backlog_events": total_events,
                "converged": standby_events == total_events,
                "mode_switches": (
                    transport.controller.switches if transport else 0
                ),
                "snapshots_shipped": reg.counter_value(
                    "replication_snapshots_shipped"),
                "events_replayed_saved": reg.counter_value(
                    "events_replayed_saved"),
            }
        finally:
            standby["svc"].stop()
            standby["matching"].shutdown()
            active["svc"].stop()
            active["matching"].shutdown()

    out = {}
    for arm in ("events", "snapshot", "adaptive"):
        out[arm] = run_arm(arm)
    ev, ad = out["events"], out["adaptive"]
    out["adaptive_vs_events"] = round(
        ad["catch_up_s"] / max(ev["catch_up_s"], 1e-9), 3
    )
    out["link_bytes_per_s"] = bytes_per_s
    return out


def _bench_failover_drill(workflows: int, signals_each: int,
                          bytes_per_s: float,
                          unavailability_slo_ms: float = 5000.0,
                          payload: int = 96):
    """Domain failover drills over a throttled WAN link
    (runtime/replication/failover.py; README "Domain failover").

    One two-cluster pair runs all three drill shapes in sequence:

      managed   graceful handover active->standby: the handover pays
                the backlog catch-up through the throttled link before
                the flip (handover_ms), with a metadata-only
                unavailability window (unavailability_ms) and a
                drained link at promote time (lag 0)
      forced    region loss: the link partitions with divergent events
                outstanding on the now-active side, the survivor is
                promoted blind (unavailability_ms = flip->observed)
      failback  the recovered region re-syncs, the version-branch
                storm resolves (conflicts_resolved — the NDC
                rebuild-at-LCA path), and ownership returns home

    The ``slo`` block is the contract the smoke test pins: every
    drill's unavailability window inside ``unavailability_slo_ms``,
    at least one conflict actually resolved, and zero replication lag
    after the final convergence.
    """
    import uuid as _uuid

    from cadence_tpu.client import HistoryClient, MatchingClient
    from cadence_tpu.cluster import ClusterInformation, ClusterMetadata
    from cadence_tpu.matching import MatchingEngine
    from cadence_tpu.runtime.api import SignalRequest, StartWorkflowRequest
    from cadence_tpu.runtime.domains import DomainCache, register_domain
    from cadence_tpu.runtime.membership import single_host_monitor
    from cadence_tpu.runtime.persistence.memory import create_memory_bundle
    from cadence_tpu.runtime.replication import (
        AdaptiveTransport,
        ClusterHandle,
        DomainFailoverCoordinator,
        HistoryRereplicator,
        ReplicationTaskFetcher,
        ReplicationTaskProcessor,
    )
    from cadence_tpu.runtime.service import HistoryService
    from cadence_tpu.testing.faults import (
        LinkPartitionedError,
        LinkProfile,
        chaos_link,
    )
    from cadence_tpu.utils.metrics import Scope

    DOMAIN = "fo-bench"
    domain_id = str(_uuid.uuid4())

    def meta(name):
        return ClusterMetadata(
            failover_version_increment=10,
            master_cluster_name="active", current_cluster_name=name,
            cluster_info={
                "active": ClusterInformation(initial_failover_version=1),
                "standby": ClusterInformation(initial_failover_version=2),
            },
        )

    def make_cluster(name):
        scope = Scope()
        persistence = create_memory_bundle()
        register_domain(
            persistence.metadata, DOMAIN, is_global=True,
            clusters=["active", "standby"], active_cluster="active",
            domain_id=domain_id, failover_version=1,
        )
        domains = DomainCache(persistence.metadata)
        svc = HistoryService(
            1, persistence, domains, single_host_monitor(f"fo-{name}"),
            cluster_metadata=meta(name), metrics=scope,
        )
        hc = HistoryClient(svc.controller)
        matching = MatchingEngine(persistence.task, hc)
        svc.wire(MatchingClient(matching), hc)
        svc.start()
        svc.controller.get_engine_for_shard(0)\
            .replicator_queue.batch_size = 8
        return {"svc": svc, "hc": hc, "matching": matching,
                "persistence": persistence, "domains": domains,
                "scope": scope}

    class Adapter:
        def __init__(self, svc, consumer):
            self.svc = svc
            self.consumer = consumer

        def get_replication_messages(self, shard_id, last, max_tasks=None):
            return self.svc.get_replication_messages(
                shard_id, last, cluster=self.consumer,
                max_tasks=max_tasks)

        def get_workflow_history_raw(self, *a):
            return self.svc.get_workflow_history_raw(*a)

        def get_replication_backlog(self, shard_id, last):
            return self.svc.get_replication_backlog(shard_id, last)

        def get_replication_checkpoint(self, *a):
            return self.svc.get_replication_checkpoint(*a)

    clusters = {n: make_cluster(n) for n in ("active", "standby")}
    links, processors = {}, {}
    for consumer, source in (("standby", "active"), ("active", "standby")):
        wrapped = chaos_link(
            Adapter(clusters[source]["svc"], consumer),
            LinkProfile(bytes_per_s=bytes_per_s, max_sleep_s=1.0),
            seed=7,
        )
        links[consumer] = wrapped.link
        engine = clusters[consumer]["svc"].controller\
            .get_engine_for_shard(0)
        transport = AdaptiveTransport(
            wrapped, source, min_gap_events=1 << 30,
            metrics=clusters[consumer]["scope"],
        )
        rerepl = HistoryRereplicator(
            wrapped, engine.ndc_replicator, transport=transport,
            metrics=clusters[consumer]["scope"],
        )
        processors[consumer] = ReplicationTaskProcessor(
            engine.shard, engine.ndc_replicator,
            ReplicationTaskFetcher(source, wrapped),
            rereplicator=rerepl,
            metrics=clusters[consumer]["scope"], transport=transport,
        )
        clusters[consumer]["transport"] = transport

    fo_scope = Scope()
    coordinator = DomainFailoverCoordinator(
        meta("active"),
        [ClusterHandle(
            name=n, metadata=clusters[n]["persistence"].metadata,
            domains=clusters[n]["domains"], history=clusters[n]["svc"],
            processors=[processors[n]],
            transport=clusters[n].get("transport"),
            registry=clusters[n]["scope"].registry,
        ) for n in ("active", "standby")],
        metrics=fo_scope, drain_timeout_s=240.0,
    )
    retryable = (LinkPartitionedError,)

    def signal(cluster, wid, name):
        clusters[cluster]["hc"].signal_workflow_execution(SignalRequest(
            domain=DOMAIN, workflow_id=wid, signal_name=name,
            input=b"x" * payload, identity="fo-bench",
        ))

    try:
        # backlog on the home region
        wids = [f"fo-wf-{i}" for i in range(workflows)]
        for wid in wids:
            clusters["active"]["hc"].start_workflow_execution(
                StartWorkflowRequest(
                    domain=DOMAIN, workflow_id=wid, workflow_type="echo",
                    task_list="fo-tl", request_id=f"req-{wid}",
                    execution_start_to_close_timeout_seconds=600,
                ))
            for k in range(signals_each):
                signal("active", wid, f"s{k}")

        # drill 1: managed handover pays the backlog catch-up
        r_managed = coordinator.managed_handover(DOMAIN, "standby")

        # drill 2: divergence on the new active side, then region loss
        coordinator.await_convergence(DOMAIN, swallow=retryable)
        for wid in wids:
            signal("standby", wid, "orphan")
        for link in links.values():
            link.force_partition(True)
        t_loss = time.monotonic()
        r_forced = coordinator.forced_failover(
            DOMAIN, "active", lost_clusters=["standby"]
        )
        detect_to_promote_ms = (time.monotonic() - t_loss) * 1000.0
        for wid in wids:
            signal("active", wid, "promoted")

        # drill 3: the lost region recovers; storm resolves; failback
        for link in links.values():
            link.force_partition(False)
        t_heal = time.monotonic()
        r_failback = coordinator.failback(
            DOMAIN, "standby", swallow=retryable
        )
        converged_s = time.monotonic() - t_heal
        lag_final = max(
            int(c["transport"].estimator.lag_events)
            for c in clusters.values() if "transport" in c
        )

        def row(r, extra=None):
            d = {
                "handover_ms": round(r.handover_ms, 2),
                "unavailability_ms": round(r.unavailability_ms, 2),
                "lag_at_promote_events": r.replication_lag_at_promote,
                "conflicts_resolved": r.conflicts_resolved,
            }
            if extra:
                d.update(extra)
            return d

        unavail = [r_managed.unavailability_ms,
                   r_forced.unavailability_ms,
                   r_failback.unavailability_ms]
        return {
            "managed": row(r_managed,
                           {"drained_tasks": r_managed.drained_tasks}),
            "forced": row(r_forced, {
                "detect_to_promote_ms": round(detect_to_promote_ms, 2),
            }),
            "failback": row(r_failback, {
                "converged_s": round(converged_s, 3),
            }),
            "slo": {
                "unavailability_ms_bound": unavailability_slo_ms,
                "unavailability_ms_worst": round(max(unavail), 2),
                "met": bool(
                    max(unavail) < unavailability_slo_ms
                    and r_failback.conflicts_resolved >= 1
                    and lag_final == 0
                ),
            },
            "conflicts_resolved_total": r_failback.conflicts_resolved,
            "replication_lag_events_final": lag_final,
            "link_bytes_per_s": bytes_per_s,
            "bytes_shipped": sum(l.bytes_total for l in links.values()),
        }
    finally:
        for c in clusters.values():
            c["svc"].stop()
            c["matching"].shutdown()


def _bench_rebuild_warm(n_hist: int, depth: int, iters: int,
                        tail_frac: float = 0.125):
    """Checkpointed incremental replay: rebuild the same cohort twice.

    Builds ``n_hist`` retry_deep-shaped runs in a memory history store,
    seeds checkpoints at ~(1 - tail_frac) of each history (an untimed
    rebuild of the prefix), appends the tails, then times two full
    rebuild_many passes over identical requests: COLD (no checkpoint
    manager — replay from event 1) and WARM (resume from the prefix
    snapshots — replay only the tail). Both passes run the complete
    pipeline (history read, pack, device scan, MutableState rehydrate,
    task refresh), so the ratio is the end-to-end win of converting
    repeat-rebuild cost from O(depth) to O(new events).

    ``suffix_frac`` = events actually replayed on the warm pass ÷ total
    events; ``checkpoint_hit_rate`` from the warm rebuilder's counters.
    """
    import random as _random

    from cadence_tpu.checkpoint import CheckpointManager, CheckpointPolicy
    from cadence_tpu.runtime.persistence.memory import create_memory_bundle
    from cadence_tpu.runtime.replication.rebuilder import (
        RebuildRequest,
        StateRebuilder,
    )
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.utils.metrics import Scope

    rng = _random.Random(45)
    bundle = create_memory_bundle()
    history = bundle.history

    reqs = []
    prefixes, tails = [], []
    total_events = 0
    suffix_events = 0
    for i in range(n_hist):
        batches = W.retry_deep_history(rng, depth=depth)
        n_events = sum(len(b) for b in batches)
        cut_events = int(n_events * (1.0 - tail_frac))
        cut, seen = len(batches), 0
        for k, b in enumerate(batches):
            if seen + len(b) > cut_events:
                cut = max(k, 1)  # keep at least the start batch
                break
            seen += len(b)
        prefix, tail = batches[:cut], batches[cut:]
        total_events += n_events
        suffix_events += sum(len(b) for b in tail)
        branch = history.new_history_branch(tree_id=f"run-{i}")
        txn = 1
        for b in prefix:
            history.append_history_nodes(branch, b, transaction_id=txn)
            txn += 1
        prefixes.append(txn)
        tails.append((branch, tail))
        reqs.append(RebuildRequest(
            domain_id="dom", workflow_id=f"wf-{i}", run_id=f"run-{i}",
            branch_token=branch.to_json().encode(),
        ))

    # seed: untimed prefix rebuild writes the checkpoints the warm pass
    # resumes from (every_events=1 → always write; keep_last=1 floors
    # the store at one snapshot per run)
    mgr = CheckpointManager(
        bundle.checkpoint, CheckpointPolicy(every_events=1, keep_last=1)
    )
    StateRebuilder(history, checkpoints=mgr).rebuild_many(reqs)
    for (branch, tail), txn in zip(tails, prefixes):
        for b in tail:
            history.append_history_nodes(branch, b, transaction_id=txn)
            txn += 1

    def _timed(rebuilder, lat_scope=None):
        # warm-up run first: jit compiles (each pass's scan shapes and
        # the resume-variant kernel differ) must not masquerade as
        # replay cost — same discipline as _time_chained elsewhere.
        # ``lat_scope`` additionally records each pass into a registry
        # histogram timer (the p50/p99 the record reports are
        # Registry.timer_stats-backed, like the serving scopes)
        rebuilder.rebuild_many(reqs)
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            if lat_scope is not None:
                with lat_scope.timer("rebuild_many"):
                    out = rebuilder.rebuild_many(reqs)
            else:
                out = rebuilder.rebuild_many(reqs)
        dt = (time.perf_counter() - t0) / iters
        assert all(r is not None for r in out)
        return dt

    cold_dt = _timed(StateRebuilder(history))
    # a huge every_events keeps the warm pass read-only on the store
    # (the tail advance is below the write threshold)
    warm_metrics = Scope()
    warm_mgr = CheckpointManager(
        bundle.checkpoint,
        CheckpointPolicy(every_events=1 << 30, keep_last=1),
    )
    warm_lat = Scope()
    warm_dt = _timed(StateRebuilder(
        history, checkpoints=warm_mgr, metrics=warm_metrics,
    ), lat_scope=warm_lat)
    warm_stats = warm_lat.registry.timer_stats("rebuild_many")

    reg = warm_metrics.registry
    hits = reg.counter_value("checkpoint_hit")
    lookups = (
        hits
        + reg.counter_value("checkpoint_miss")
        + reg.counter_value("checkpoint_invalidated")
    )
    warm_rate = n_hist / warm_dt
    cold_rate = n_hist / cold_dt
    # the warm-up pass inside _timed does the same lookups as each
    # timed pass, so the counters hold (iters + 1) identical passes
    saved_per_pass = int(
        reg.counter_value("events_replayed_saved") // (iters + 1)
    )
    return {
        "histories_per_sec": round(warm_rate, 2),
        "kernel": "rebuild_many",
        "cold_histories_per_sec": round(cold_rate, 2),
        "vs_cold": round(warm_rate / cold_rate, 2),
        "checkpoint_hit_rate": round(hits / max(lookups, 1), 4),
        # MEASURED from the warm counters (not the workload's configured
        # cut): a resume regression that silently replays full histories
        # pushes this back toward 1.0 even while lookups still hit
        "suffix_frac": round(
            1.0 - saved_per_pass / max(total_events, 1), 4
        ),
        "suffix_frac_configured": round(
            suffix_events / max(total_events, 1), 4
        ),
        "events_replayed_saved": saved_per_pass,
        "mean_depth": round(total_events / max(n_hist, 1), 1),
        "batch": n_hist,
        "batch_rebuild_ms": round(warm_dt * 1000, 3),
        "latency_p50_ms": round(warm_stats.p50 * 1e3, 3),
        "latency_p99_ms": round(warm_stats.p99 * 1e3, 3),
        "cold_batch_rebuild_ms": round(cold_dt * 1000, 3),
    }


def _bench_serve_continuous(workflows: int, qps: float, lanes: int = 64,
                            prefix_frac: float = 0.4,
                            min_events: int = 60, max_events: int = 400,
                            delta_batches: int = 3,
                            kind: str = "poisson"):
    """Continuous-batching serving under open-loop load.

    Builds ``workflows`` signal-dominated OPEN histories, seats a
    prefix of each into the resident engine (cadence_tpu/serving/), and
    drives the remaining batches as per-arrival Δ appends on an
    open-loop schedule (``kind``: poisson | bursty) at sustained
    ``qps`` through a token bucket. Every request's decision latency is
    measured from its SCHEDULED arrival to the resident read — falling
    behind shows up as queueing delay in the p99, exactly as it would
    for real users (closed-loop benches hide this).

    The O(Δ) proof is ``suffix_frac``: events the engine actually
    composed across all appends ÷ events a cold per-arrival rebuild of
    the same cohort would have replayed (each arrival re-replaying its
    full prefix). ``events_per_append`` ≈ the mean Δ width — resident
    appends never pay O(depth). p50/p99 come from the PR 9
    exponential-bucket histograms (``Registry.timer_stats``), the same
    plane production scrapes.
    """
    import random as _random

    from cadence_tpu.ops import schema as S
    from cadence_tpu.serving import (
        ArrivalProcess,
        OpenLoopHarness,
        ResidentEngine,
        ServeWorkload,
    )
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.utils.metrics import Scope
    from cadence_tpu.utils.quotas import TokenBucket

    caps = S.Capacities(
        max_events=512, max_activities=2, max_timers=2,
        max_children=2, max_request_cancels=2, max_signals_ext=4,
        max_version_items=2)

    def build(tag):
        # same seed per call: the warm round sees IDENTICAL history
        # shapes (and therefore identical jit keys) as the timed round
        rng = _random.Random(46)
        loads, cold_events, appended_events = [], 0, 0
        for i in range(workflows):
            batches = W.signal_history(
                rng, min_events=min_events, max_events=max_events)
            cut = max(1, int(len(batches) * prefix_frac))
            deltas = [
                batches[k : k + delta_batches]
                for k in range(cut, len(batches), delta_batches)
            ]
            seen = sum(len(b) for b in batches[:cut])
            for d in deltas:
                dn = sum(len(b) for b in d)
                seen += dn
                appended_events += dn
                cold_events += seen  # cold replays the full prefix
            loads.append(ServeWorkload(
                domain_id="bench", workflow_id=f"serve-{tag}-wf-{i}",
                run_id=f"serve-{tag}-run-{i}", branch_token=b"",
                prefix=batches[:cut], deltas=deltas,
            ))
        return loads, cold_events, appended_events

    def drive(tag, scope):
        loads, cold_events, appended_events = build(tag)
        engine = ResidentEngine(lanes=lanes, caps=caps, metrics=scope)
        harness = OpenLoopHarness(
            engine, loads,
            ArrivalProcess(qps=qps, kind=kind, seed=7),
            metrics=scope,
            # the admission token bucket: sized above the target rate
            # so steady state admits, but a burst beyond 2x qps sheds
            # load instead of queueing it into the p99
            admission_bucket=TokenBucket(
                rps=qps * 2.0, burst=max(8, int(qps))),
        )
        run = harness.run()
        return loads, cold_events, appended_events, run, engine

    # warm round first (untimed, own registry): jit compiles of the
    # tick/seat shapes must not masquerade as open-loop queueing delay
    # — same discipline as _time_chained / _bench_rebuild_warm
    from cadence_tpu.utils.metrics import NOOP as _NOOP

    drive("warm", _NOOP)[4].drain()
    scope = Scope()
    reg = scope.registry
    loads, cold_events, appended_events, run, engine = drive(
        "run", scope)
    drained = engine.drain()

    # cold comparison cohort: ONE batched rebuild of the final
    # histories — context for what the resident plane displaced
    from cadence_tpu.ops.dispatch import replay_stream

    full = [
        (w.workflow_id, w.run_id,
         list(w.prefix) + [b for d in w.deltas for b in d])
        for w in loads
    ]
    t0 = time.perf_counter()
    replay_stream(full, caps=caps, lane_pack=True)
    cold_cohort_ms = (time.perf_counter() - t0) * 1000
    total_events = sum(
        sum(len(b) for b in batches) for _, _, batches in full
    )

    stats = reg.timer_stats("serve_decision")
    hits = reg.counter_value("serving_resident_hits")
    misses = reg.counter_value("serving_cold_misses")
    appends = reg.counter_value("serving_appends")
    replayed = reg.counter_value("serving_events_replayed")
    ticks = reg.counter_value("serving_ticks")
    return {
        "arrival": kind,
        "workflows": workflows,
        "lanes": lanes,
        "requests": run["requests"],
        "completed": run["completed"],
        "shed": run["shed"],
        "qps_target": round(run["qps_target"], 1),
        "qps_sustained": round(run["qps_sustained"], 1),
        "wall_s": round(run["wall_s"], 3),
        # the SLO block: open-loop decision latency (scheduled arrival
        # -> resident read done) off the histogram plane
        "latency_p50_ms": round(stats.p50 * 1e3, 3),
        "latency_p99_ms": round(stats.p99 * 1e3, 3),
        "resident_hit_rate": round(hits / max(hits + misses, 1), 4),
        # the O(Δ) block: composed ≈ appended, never ≈ cold
        "appends": appends,
        "ticks": ticks,
        "appends_per_tick": round(appends / max(ticks, 1), 2),
        "events_appended": appended_events,
        "events_replayed": replayed,
        "events_per_append": round(replayed / max(appends, 1), 2),
        "cold_events_equiv": cold_events,
        "suffix_frac": round(replayed / max(cold_events, 1), 4),
        "total_events": total_events,
        "cold_cohort_rebuild_ms": round(cold_cohort_ms, 3),
        "drain_flush_failed": drained["flush_failed"],
    }


def _bench_serve_overload(workflows: int, qps: float, lanes: int = 8,
                          capacity_frac: float = 0.5, domains: int = 3,
                          min_events: int = 20, max_events: int = 60,
                          delta_batches: int = 3,
                          tick_interval_ms: float = 5.0,
                          staleness_bound_ms: float = 500.0):
    """Graceful degradation under sustained overload (ISSUE 15).

    Offers an open-loop Poisson stream at ``qps`` against a limiter
    admitting only ``capacity_frac`` of it — sustained 1/capacity_frac×
    overload (the default is 2×). Workloads spread over ``domains``
    weighted domains through the fair-admission engine; rejected
    arrivals re-offer through a success-refilled RetryBudget; a
    background TickPump bounds resident staleness. The record reports
    the degradation ladder's observables: ``shed_frac`` (> 0 at 2× —
    excess load is shed, not queued into the p99), per-domain p99 +
    progress counters (no starvation), ``staleness_p99_ms`` vs the
    bound, and goodput vs offered."""
    import random as _random

    from cadence_tpu.ops import schema as S
    from cadence_tpu.serving import (
        AdmissionPolicy,
        ArrivalProcess,
        OpenLoopHarness,
        ResidentEngine,
        ServeWorkload,
        TickPump,
    )
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.utils.metrics import Scope
    from cadence_tpu.utils.quotas import (
        MultiStageRateLimiter,
        RetryBudget,
    )

    caps = S.Capacities(
        max_events=512, max_activities=2, max_timers=2,
        max_children=2, max_request_cancels=2, max_signals_ext=4,
        max_version_items=2)
    dom_names = [f"dom-{d}" for d in range(domains)]

    def build(tag):
        rng = _random.Random(52)
        loads = []
        for i in range(workflows):
            batches = W.signal_history(
                rng, min_events=min_events, max_events=max_events)
            cut = max(1, int(len(batches) * 0.4))
            deltas = [
                batches[k : k + delta_batches]
                for k in range(cut, len(batches), delta_batches)
            ]
            loads.append(ServeWorkload(
                domain_id=dom_names[i % domains],
                workflow_id=f"ovl-{tag}-wf-{i}",
                run_id=f"ovl-{tag}-run-{i}", branch_token=b"",
                prefix=batches[:cut], deltas=deltas,
            ))
        return loads

    def drive(tag, scope):
        loads = build(tag)
        engine = ResidentEngine(
            lanes=lanes, caps=caps, metrics=scope, idle_ticks=2,
            admission=AdmissionPolicy(
                domain_weights={
                    d: float(2 ** (domains - i))
                    for i, d in enumerate(dom_names)
                },
                quota_rps=qps, aging_boost=1.0,
            ),
        )
        capacity = qps * capacity_frac
        harness = OpenLoopHarness(
            engine, loads,
            ArrivalProcess(qps=qps, seed=11),
            metrics=scope,
            limiter=MultiStageRateLimiter(
                global_rps=capacity,
                domain_rps=lambda d: capacity,
                global_burst=max(4, int(capacity / 8)),
            ),
            retry_budget=RetryBudget(ratio=0.2, cap=16.0, initial=8.0),
        )
        pump = TickPump(
            engine, tick_interval_ms / 1e3, metrics=scope
        ).start()
        try:
            run = harness.run()
        finally:
            pump.stop()
        return run, engine

    from cadence_tpu.utils.metrics import NOOP as _NOOP

    drive("warm", _NOOP)[1].drain()  # jit warm round, own registry
    scope = Scope()
    reg = scope.registry
    run, engine = drive("run", scope)
    drained = engine.drain()

    per_domain = {}
    for d in dom_names:
        stats = reg.timer_stats(
            "serve_decision",
            tags={"layer": "serving_harness", "domain": d},
        )
        prog = run["domains"].get(d, {})
        per_domain[d] = {
            "completed": prog.get("completed", 0),
            "shed": prog.get("shed", 0),
            "retries": prog.get("retries", 0),
            "p99_ms": round(stats.p99 * 1e3, 3),
        }
    stats = reg.timer_stats("serve_decision")
    staleness = reg.timer_stats("serving_staleness_ms")
    starvation = reg.timer_stats("serving_admit_starvation_age_ms")
    wall = max(run["wall_s"], 1e-9)
    return {
        "workflows": workflows,
        "lanes": lanes,
        "domains": domains,
        "qps_offered_target": round(qps, 1),
        "capacity_frac": capacity_frac,
        "requests": run["requests"],
        "offered": run["offered"],
        "retries": run["retries"],
        "completed": run["completed"],
        "shed": run["shed"],
        "shed_frac": round(run["shed"] / max(run["requests"], 1), 4),
        "offered_amplification": round(
            run["offered"] / max(run["requests"], 1), 3),
        "goodput_qps": round(run["completed"] / wall, 1),
        "offered_qps": round(run["offered"] / wall, 1),
        "latency_p50_ms": round(stats.p50 * 1e3, 3),
        "latency_p99_ms": round(stats.p99 * 1e3, 3),
        "per_domain": per_domain,
        "staleness_p99_ms": round(staleness.p99, 3),
        "staleness_bound_ms": staleness_bound_ms,
        "staleness_in_bound": bool(
            staleness.p99 <= staleness_bound_ms
        ),
        "starvation_age_max_ms": round(starvation.max_s, 3),
        "retry_budget_exhausted": reg.counter_value(
            "retry_budget_exhausted"
        ),
        "drain_flush_failed": drained["flush_failed"],
    }


def _bench_capacity_diurnal(workflows_per_chunk: int = 8,
                            qps_low: float = 60.0,
                            qps_high: float = 600.0,
                            chunks_low: int = 3, chunks_high: int = 4,
                            chunks_trough: int = 4, lanes: int = 16,
                            min_events: int = 12, max_events: int = 24,
                            initial_rps: float = 150.0):
    """Capacity autopilot closed loop under a diurnal curve (ISSUE 16).

    Offers a low -> high -> low open-loop stream against a live
    limiter the ``CapacityController`` retunes between chunks — the
    same sense (windowed serve_decision/serve_shed readings), decide
    (EWMA'd offered-demand + hysteresis gate + guardrail), actuate
    (``set_global_rate`` hook) loop the bootstrap wires. The record
    pins the autopilot story: the admission setpoint tracks the curve
    BOTH directions with zero operator calls and zero guardrail
    freezes, while per-phase p99/shed stay explicit fields."""
    import random as _random

    from cadence_tpu.config.static import AutopilotConfig
    from cadence_tpu.ops import schema as S
    from cadence_tpu.runtime.autopilot import (
        CapacityController,
        KEY_HISTORY_RPS,
    )
    from cadence_tpu.serving import (
        ArrivalProcess,
        OpenLoopHarness,
        ResidentEngine,
        ServeWorkload,
    )
    from cadence_tpu.testing import workloads as W
    from cadence_tpu.utils.metrics import NOOP as _NOOP, Scope, Window
    from cadence_tpu.utils.quotas import (
        MultiStageRateLimiter,
        RetryBudget,
    )

    caps = S.Capacities(
        max_events=512, max_activities=2, max_timers=2,
        max_children=2, max_request_cancels=2, max_signals_ext=4,
        max_version_items=2)

    def make_chunk(rng, serial, tag):
        loads = []
        for _ in range(workflows_per_chunk):
            serial[0] += 1
            batches = W.signal_history(
                rng, min_events=min_events, max_events=max_events)
            cut = max(1, int(len(batches) * 0.4))
            loads.append(ServeWorkload(
                domain_id=f"dom-{serial[0] % 2}",
                workflow_id=f"diurnal-{tag}-wf-{serial[0]}",
                run_id=f"diurnal-{tag}-run-{serial[0]}",
                branch_token=b"",
                prefix=batches[:cut],
                deltas=[batches[k:k + 2]
                        for k in range(cut, len(batches), 2)],
            ))
        return loads

    # jit warm round on its own engine/registry (serve_overload idiom)
    warm_engine = ResidentEngine(lanes=lanes, caps=caps, metrics=_NOOP,
                                 idle_ticks=2)
    OpenLoopHarness(
        warm_engine, make_chunk(_random.Random(41), [0], "warm"),
        ArrivalProcess(qps=qps_low, seed=5), metrics=_NOOP,
    ).run()
    warm_engine.drain()

    scope = Scope()
    reg = scope.registry
    engine = ResidentEngine(lanes=lanes, caps=caps, metrics=scope,
                            idle_ticks=2)
    limiter = MultiStageRateLimiter(
        global_rps=initial_rps, domain_rps=lambda d: 1e9)
    ap = CapacityController(
        AutopilotConfig(
            enabled=True, target_p99_ms=60_000.0, ewma_alpha=0.5,
            min_dwell=1, cooldown_epochs=0, max_step_frac=0.5,
            headroom_frac=0.5, min_rps=5.0),
        registry=reg,
        rate_hooks={KEY_HISTORY_RPS: limiter.set_global_rate},
        initial_rates={KEY_HISTORY_RPS: limiter.global_rps},
        metrics=scope,
    )
    rng = _random.Random(97)
    serial = [0]
    phase_window = Window(reg)

    def run_phase(name, qps, chunks):
        for _ in range(chunks):
            OpenLoopHarness(
                engine, make_chunk(rng, serial, name),
                ArrivalProcess(qps=qps, seed=serial[0]),
                metrics=scope, limiter=limiter,
                retry_budget=RetryBudget(ratio=0.2, cap=16.0,
                                         initial=8.0),
            ).run()
            ap.run_epoch_once()
        r = phase_window.advance()
        st = r.timer_stats("serve_decision")
        shed = r.counter("serve_shed")
        return {
            "chunks": chunks,
            "offered_qps_target": round(qps, 1),
            "admitted": st.count,
            "shed": shed,
            "shed_frac": round(shed / max(shed + st.count, 1), 4),
            "p99_ms": round(st.p99 * 1e3, 3),
            "rate_rps": round(
                ap.status()["rates"][KEY_HISTORY_RPS], 2),
            "demand_rps": round(
                r.gauge("autopilot_demand_rps"), 2),
        }

    try:
        low = run_phase("low", qps_low, chunks_low)
        high = run_phase("high", qps_high, chunks_high)
        trough = run_phase("trough", qps_low, chunks_trough)
    finally:
        drained = engine.drain()

    status = ap.status()
    st = reg.timer_stats("serve_decision")
    total_shed = reg.counter_value("serve_shed")
    ap_tags = {"layer": "autopilot"}
    operator_calls = (
        reg.counter_value("autopilot_pauses", tags=ap_tags)
        + reg.counter_value("autopilot_resumes", tags=ap_tags)
    )
    return {
        "workflows_per_chunk": workflows_per_chunk,
        "lanes": lanes,
        "qps_low": round(qps_low, 1),
        "qps_high": round(qps_high, 1),
        "initial_rps": round(initial_rps, 1),
        "phases": {"low": low, "high": high, "trough": trough},
        "rate_low_rps": low["rate_rps"],
        "rate_high_rps": high["rate_rps"],
        "rate_final_rps": trough["rate_rps"],
        "rate_tracks_load": bool(
            high["rate_rps"] > low["rate_rps"] * 1.2
            and trough["rate_rps"] < high["rate_rps"]
        ),
        "epochs": status["epochs_run"],
        "retunes": reg.counter_value(
            "autopilot_rate_retunes", tags=ap_tags),
        "guardrail_freezes": status["guardrail_freezes"],
        "gate_switches": status["gate_switches"],
        "overloaded_final": status["overloaded"],
        "operator_calls": operator_calls,
        "p99_overall_ms": round(st.p99 * 1e3, 3),
        "shed_frac_overall": round(
            total_shed / max(total_shed + st.count, 1), 4),
        "drain_flush_failed": drained["flush_failed"],
    }


def _bench_telemetry_overhead(calls: int = 30000, rounds: int = 5):
    """Unsampled telemetry cost on the instrumented serving path.

    The telemetry plane's contract is that DISABLED tracing is nearly
    free: the instrument_methods wrapper's tracing hook is one
    thread-local read returning a shared no-op. This config measures an
    echo-shaped handler op (the serving hot path's wrapper stack, no
    kernel noise) three ways — a metrics-only control wrapper (the
    pre-telemetry shape), the real tracing-aware wrapper with NO active
    trace (unsampled), and the same wrapper inside a sampled trace —
    and reports the unsampled overhead fraction the smoke contract pins
    at ≤3% (tests/test_bench_smoke.py). Rates are best-of-``rounds`` so
    host-load noise shrinks the estimate, never inflates it.
    """
    from cadence_tpu.rpc import codec
    from cadence_tpu.utils import metrics_defs
    from cadence_tpu.utils.metrics import Scope
    from cadence_tpu.utils.tracing import TRACER

    # an echo request's cheapest honest unit of work: the rpc codec
    # roundtrip of a start-shaped payload (tens of µs — far BELOW the
    # ms-scale cost of a real Onebox echo decision, so the measured
    # overhead fraction is an upper bound on the serving-path one)
    payload = {
        "domain": "bench", "workflow_id": "echo-wf-0000",
        "workflow_type": "echo", "task_list": "bench-tl",
        "input": "x" * 256, "request_id": "req-0000",
        "timeout_seconds": 60, "identity": "bench-worker",
    }

    class _Echo:
        def echo(self, i):
            return codec.loads(codec.dumps(([payload], {"seq": i})))

    instrumented = _Echo()
    metrics_defs.instrument_methods(
        instrumented, Scope().tagged(service="bench"), ("echo",)
    )

    control = _Echo()
    ctrl_scope = Scope().tagged(service="bench", operation="echo")
    ctrl_fn = control.echo

    def ctrl_wrapped(*args, **kwargs):
        ctrl_scope.inc(metrics_defs.REQUESTS)
        t0 = time.perf_counter()
        try:
            return ctrl_fn(*args, **kwargs)
        finally:
            ctrl_scope.record(
                metrics_defs.LATENCY, time.perf_counter() - t0
            )

    control.echo = ctrl_wrapped

    import gc as _gc

    def _round(target):
        op = target.echo
        t0 = time.perf_counter()
        for i in range(calls):
            op(i)
        return time.perf_counter() - t0

    # paired interleaved rounds: each round times control then
    # instrumented back to back, so slow host-load drift cancels in the
    # per-round ratio; the reported overhead is the MINIMUM paired
    # ratio — timing noise on this codec-bound loop is strictly
    # additive, so every observed ratio is an upper bound on the true
    # wrapper cost and the tightest one is the honest estimate. GC is
    # paused through the rounds (allocation-heavy codec bodies
    # otherwise donate multi-percent variance to whichever arm the
    # collector fires in).
    _round(control), _round(instrumented)  # warm both paths
    ratios = []
    best = {"ctrl": None, "inst": None}
    _gc.disable()
    try:
        for _ in range(rounds):
            dt_c = _round(control)
            dt_i = _round(instrumented)
            ratios.append(dt_i / dt_c)
            if best["ctrl"] is None or dt_c < best["ctrl"]:
                best["ctrl"] = dt_c
            if best["inst"] is None or dt_i < best["inst"]:
                best["inst"] = dt_i
        with TRACER.trace("bench_telemetry_overhead", sampled=True):
            sampled = calls / min(
                _round(instrumented) for _ in range(rounds)
            )
    finally:
        _gc.enable()
    untraced = calls / best["ctrl"]
    unsampled = calls / best["inst"]
    TRACER.clear()  # the bench spans must not linger in the recorder
    overhead = min(ratios) - 1.0
    return {
        "calls_per_round": calls,
        "rounds": rounds,
        "untraced_calls_per_sec": round(untraced, 1),
        "unsampled_calls_per_sec": round(unsampled, 1),
        "sampled_calls_per_sec": round(sampled, 1),
        # the guarded number: unsampled tracing vs the metrics-only
        # wrapper, min over the paired rounds (negative = measurement
        # noise in telemetry's favor)
        "overhead_unsampled_frac": round(overhead, 4),
        "overhead_unsampled_frac_median": round(
            sorted(ratios)[len(ratios) // 2] - 1.0, 4),
        "overhead_sampled_frac": round(untraced / sampled - 1.0, 4),
    }


def _bench_queue_drain(tasks_per_queue=2000, n_wf=48, parallelism=4,
                       batch_size=128, close_every=500, stall_us=150):
    """Queue-drain throughput: sequential pump vs the conflict-keyed
    wave executor (runtime/queues/parallel.py) over an identical mixed
    transfer/timer storm.

    Three queue pipelines (two transfer shards + one timer) carry
    ``tasks_per_queue`` rows each, round-robin over ``n_wf`` workflows
    with a sprinkle of CloseExecution (the untargeted cross-workflow
    fan-out that serializes its cycle). Both arms run the identical
    handler — a commutative per-(workflow, task-type) accumulator — so
    the final state must match byte-for-byte. The sequential arm is
    the production one-task-at-a-time drain (``QueueProcessorBase``
    own pump, one worker: per-task ack lock + per-task pool submit);
    the parallel arm registers the same pipelines on one shared
    ``ParallelQueueExecutor`` gated on the regenerated conflict-matrix
    artifact (``ensure_conflict_matrix``).

    Each task carries a ``stall_us`` GIL-releasing stall modeling the
    persistence/matching round-trip a real transfer or timer task
    spends most of its wall-clock in — the latency the wave executor
    exists to overlap: the ordered baseline pays it serially, while
    provably-commuting conflict groups overlap it across the worker
    pool (plus batched ack-lock and per-group instead of per-task
    submit amortization). The baseline is ``worker_count=1`` because
    that is the configuration with the SAME ordering guarantee the
    wave schedule preserves; a wider naive pool overlaps arbitrary
    tasks with no commutativity proof. The smoke contract
    (tests/test_bench_smoke.py) pins the record shape, state equality,
    and the non-degraded matrix gate; real runs carry the >=2x
    speedup acceptance bar.
    """
    import threading as _threading
    from types import SimpleNamespace

    from cadence_tpu.core.enums import TimerTaskType, TransferTaskType
    from cadence_tpu.runtime.queues.ack import QueueAckManager
    from cadence_tpu.runtime.queues.base import QueueProcessorBase
    from cadence_tpu.runtime.queues.parallel import (
        ParallelQueueExecutor,
        ensure_conflict_matrix,
    )

    queues = ("transfer-0", "transfer-1", "timer-0")

    # closes live at the storm's tail — a workflow's CloseExecution is
    # the last task of its lifecycle, not a uniform sprinkle. The
    # untargeted fan-out serializes its whole cycle, so tail placement
    # also keeps the serialized window where a real drain has it: at
    # the end, once the commuting bulk has already overlapped
    n_close = (tasks_per_queue // close_every) if close_every else 0

    def _mk_tasks(queue):
        rows = []
        for i in range(tasks_per_queue):
            if queue.startswith("timer"):
                tt = (TimerTaskType.UserTimer if i % 3
                      else TimerTaskType.ActivityTimeout)
            elif i >= tasks_per_queue - n_close:
                tt = TransferTaskType.CloseExecution
            else:
                tt = (TransferTaskType.DecisionTask if i % 2
                      else TransferTaskType.ActivityTask)
            rows.append(SimpleNamespace(
                task_id=i + 1, task_type=tt, domain_id="bench",
                workflow_id=f"wf-{i % n_wf}", run_id=f"run-{i % n_wf}",
                target_workflow_id="", target_domain_id="",
            ))
        return rows

    total = len(queues) * tasks_per_queue

    def _run_arm(executor):
        state = {}
        lock = _threading.Lock()
        done = _threading.Event()
        counter = [0]

        def process(task):
            # the persistence/matching round-trip stand-in (GIL
            # released, like the real blocking call)
            if stall_us:
                time.sleep(stall_us / 1e6)
            # commutative per-(workflow, type) accumulator: commuting
            # reorder cannot change it, a lost/duplicated task must.
            # The last task trips the event — drain completion is
            # detected on the worker side, not through a polling loop
            # whose sleep quantum would swamp the measurement
            key = f"{task.workflow_id}:{int(task.task_type)}"
            with lock:
                state[key] = state.get(key, 0) + task.task_id
                counter[0] += 1
                if counter[0] == total:
                    done.set()

        procs = []
        for q in queues:
            rows = _mk_tasks(q)

            def read(level, limit, rows=rows):
                return [t for t in rows if t.task_id > level][:limit]

            procs.append(QueueProcessorBase(
                name=q, ack=QueueAckManager(0), read_batch=read,
                process_task=process, complete_task=lambda t: None,
                task_key=lambda t: t.task_id,
                worker_count=1,  # the one-task-at-a-time baseline
                batch_size=batch_size, poll_interval_s=0.005,
                executor=executor,
            ))
        t0 = time.perf_counter()
        for p in procs:
            p.start()
        if executor is not None:
            executor.start()
            executor.notify()
        else:
            for p in procs:
                p.notify()
        drained = done.wait(timeout=120.0)
        dt = time.perf_counter() - t0
        # let the final acks land and the watermark sweep before teardown
        sweep_deadline = time.monotonic() + 10.0
        while time.monotonic() < sweep_deadline:
            if all(p.ack.update_ack_level() >= tasks_per_queue
                   for p in procs):
                break
            time.sleep(0.002)
        for p in procs:
            p.stop()
        if executor is not None:
            executor.stop()
        rate = total / dt if dt > 0 else 0.0
        return state, rate, drained

    seq_state, seq_rate, seq_drained = _run_arm(None)
    ex = ParallelQueueExecutor(
        parallelism=parallelism, batch_size=batch_size,
        poll_interval_s=0.005,
        matrix_path=ensure_conflict_matrix(
            "build/queue_conflict_matrix.json"),
    )
    par_state, par_rate, par_drained = _run_arm(ex)
    return {
        "tasks": len(queues) * tasks_per_queue,
        "queues": len(queues),
        "n_workflows": n_wf,
        "parallelism": parallelism,
        "seq_tasks_per_sec": round(seq_rate, 1),
        "par_tasks_per_sec": round(par_rate, 1),
        "speedup": round(par_rate / seq_rate, 2) if seq_rate else 0.0,
        # mean concurrent conflict groups per shared cycle (the
        # parqueue_wave_width metric) and the fraction of tasks folded
        # into an already-open group (parqueue_conflict_frac)
        "wave_width_mean": round(ex.waves / max(1, ex.cycles), 2),
        "conflict_frac": round(1.0 - ex.waves / max(1, ex.tasks), 4),
        "cycles": ex.cycles,
        "stale_skipped": ex.stale_skipped,
        "degraded": ex.degraded,
        "drained": bool(seq_drained and par_drained),
        "state_identical": seq_state == par_state,
    }


def _checksum(state):
    acc = jnp.int32(0)
    for leaf in jax.tree_util.tree_leaves(state):
        acc = acc + jnp.sum(leaf, dtype=jnp.int32)
    return acc


def _time_chained(fn, state0, iters):
    """fn: state -> (state, aux). Forced-materialization amortized s/call.

    Chains the state through ``iters`` calls and fetches a checksum that
    data-depends on the last call's full output (state + aux)."""
    cs = jax.jit(lambda out: _checksum(out))
    out = fn(state0)                      # compile + warm
    np.asarray(cs(out))
    t0 = time.perf_counter()
    st = state0
    for _ in range(iters):
        out = fn(st)
        st = out[0]
    v = int(np.asarray(cs(out)))
    return (time.perf_counter() - t0) / iters, v


def measure_copy_bw_gbps(nbytes: int = 1 << 28) -> float:
    """Measured r+w HBM bandwidth of a jitted elementwise copy."""
    x = jax.jit(lambda k: jax.random.randint(
        k, (nbytes // 4,), 0, 100, jnp.int32))(jax.random.PRNGKey(0))
    f = jax.jit(lambda x: x + 1)
    y = f(x)
    np.asarray(jnp.sum(y[:1]))
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(y)
    np.asarray(jnp.sum(y[:1]))
    dt = (time.perf_counter() - t0) / iters
    return 2 * nbytes / dt / 1e9


def _bench_config(config: str, caps, batch: int, iters: int,
                  baseline_histories: int, bt: int, tb: int,
                  use_pallas: bool, chain: int = 1):
    """Returns a per-config result dict.

    ``chain`` > 1 additionally times ``chain`` kernel executions inside
    ONE jit dispatch (lax.scan over the replay+refresh step) after the
    single-dispatch run has proven checksum parity: the chained number
    amortizes the per-dispatch host overhead to 1/chain. Both numbers
    are reported.
    """
    from cadence_tpu import native
    from cadence_tpu.native import presence_masks
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import pack_histories
    from cadence_tpu.ops.refresh import refresh_tasks_device
    from cadence_tpu.ops.replay import replay_scan, type_signature
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_teb

    n_unique = min(32, batch)
    packed = pack_histories(_build_histories(config, n_unique, caps),
                            caps=caps)
    events, lengths = _tile(packed, batch)
    # static event-type specialization, exactly as the serving
    # dispatcher applies it (DeviceDispatcher._type_set)
    types = type_signature(
        int(t) for t in np.unique(events[:, :, S.EV_TYPE]) if t >= 0)
    mean_depth = float(lengths.mean())
    T = events.shape[1]
    state0 = jax.device_put(
        jax.tree_util.tree_map(jnp.asarray, S.empty_state(batch, caps))
    )
    state_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(state0))
    ev_bytes_step = batch * S.EV_N * 4

    results = {}

    # ---- XLA scan kernel
    ev_tm = jnp.asarray(np.ascontiguousarray(np.transpose(events, (1, 0, 2))))

    def step_xla(state):
        final = replay_scan(state, ev_tm, types=types)
        return final, refresh_tasks_device(final)

    dt, cs_xla = _time_chained(jax.jit(step_xla), state0, iters)
    results["xla"] = {
        "histories_per_sec": round(batch / dt, 2),
        "batch_rebuild_ms": round(dt * 1000, 3),
        "us_per_step": round(dt / T * 1e6, 3),
        # state read+write + event row read, per scan step
        "streams_gbps": round(
            (2 * state_bytes + ev_bytes_step) / (dt / T) / 1e9, 1),
    }

    del ev_tm

    # ---- Pallas kernel (field-major events + host presence masks)
    if use_pallas:
        from cadence_tpu.ops.replay_pallas import narrow_events_teb

        ev_teb_np = np.ascontiguousarray(np.transpose(events, (1, 2, 0)))
        ev_teb = jnp.asarray(ev_teb_np)
        valid = events[:, :, S.EV_TYPE] >= 0
        pres = None
        if batch % bt == 0:
            pres = jnp.asarray(presence_masks(
                events[valid], valid.sum(axis=1).astype(np.int64), T, bt))

        def step_pallas(state):
            final = replay_scan_pallas_teb(
                state, ev_teb, caps, tb=tb, interpret=False, bt=bt,
                presence=pres)
            return final, refresh_tasks_device(final)

        def _chained(kernel_kwargs):
            """One jit dispatch running ``chain`` replay+refresh steps
            (lax.scan) — amortizes the per-dispatch rig RTT. Returns
            amortized seconds per step."""
            from jax import lax

            def stepped(state):
                def body(c, _):
                    final = replay_scan_pallas_teb(
                        c, caps=caps, tb=tb, interpret=False, bt=bt,
                        presence=pres, **kernel_kwargs)
                    return final, refresh_tasks_device(final)

                return lax.scan(body, state, None, length=chain)

            dt_c, _ = _time_chained(
                jax.jit(stepped), state0, max(2, iters // 2))
            return dt_c / chain

        try:
            dt_p, cs_p = _time_chained(jax.jit(step_pallas), state0, iters)
            if cs_p != cs_xla:
                results["pallas"] = {"error": "checksum mismatch vs xla"}
            else:
                results["pallas"] = {
                    "histories_per_sec": round(batch / dt_p, 2),
                    "batch_rebuild_ms": round(dt_p * 1000, 3),
                    "us_per_step": round(dt_p / T * 1e6, 3),
                    "streams_gbps": round(ev_bytes_step / (dt_p / T) / 1e9, 1),
                }
                if chain > 1:
                    per_exec = _chained({"events_teb": ev_teb})
                    results["pallas"].update({
                        "chain": chain,
                        "histories_per_sec_chained": round(
                            batch / per_exec, 2),
                        "batch_rebuild_ms_chained": round(
                            per_exec * 1000, 3),
                        "dispatch_overhead_ms": round(
                            (dt_p - per_exec) * 1000, 3),
                    })
        except Exception as exc:  # compile/runtime failure is a reportable
            results["pallas"] = {
                "error": f"{type(exc).__name__}: {str(exc)[:160]}"}

        # ---- int16 narrow stream: the kernel is event-stream-bound,
        # so ~halving its bytes is the per-tile lever (r5); parity is
        # asserted against the XLA checksum before any number is kept
        pallas_ok = "histories_per_sec" in results.get("pallas", {})
        narrowed = narrow_events_teb(ev_teb_np) if pallas_ok else None
        if narrowed is not None:
            ev16_np, nbase, nwide = narrowed
            ev16 = jnp.asarray(ev16_np)
            n16 = {"events_teb": ev16, "base": nbase, "wide_cols": nwide}

            def step_pallas16(state):
                final = replay_scan_pallas_teb(
                    state, caps=caps, tb=tb, interpret=False, bt=bt,
                    presence=pres, **n16)
                return final, refresh_tasks_device(final)

            try:
                dt_n, cs_n = _time_chained(
                    jax.jit(step_pallas16), state0, iters)
                if cs_n != cs_xla:
                    results["pallas16"] = {"error": "checksum mismatch"}
                else:
                    results["pallas16"] = {
                        "histories_per_sec": round(batch / dt_n, 2),
                        "batch_rebuild_ms": round(dt_n * 1000, 3),
                        "us_per_step": round(dt_n / T * 1e6, 3),
                        "wide_cols": list(nwide),
                        "stream_bytes_frac": round(
                            ev16_np.shape[1] * 2 / (S.EV_N * 4), 3),
                    }
                    if chain > 1:
                        per_exec16 = _chained(n16)
                        results["pallas16"].update({
                            "chain": chain,
                            "histories_per_sec_chained": round(
                                batch / per_exec16, 2),
                            "batch_rebuild_ms_chained": round(
                                per_exec16 * 1000, 3),
                        })
            except Exception as exc:
                results["pallas16"] = {
                    "error": f"{type(exc).__name__}: {str(exc)[:160]}"}

    # ---- compiled-host baseline: C++ sequential replay of the same tensors
    class _Sub:
        pass

    sub = _Sub()
    nb = min(baseline_histories, batch)
    sub.events = events[:nb]
    sub.lengths = lengths[:nb]
    sub.caps = caps
    t0 = time.perf_counter()
    reps = 0
    while time.perf_counter() - t0 < 0.5:
        native.replay_sequential(sub)
        reps += 1
    cpp_s = (time.perf_counter() - t0) / reps
    cpp_rate = nb / cpp_s

    def _rate(k):
        # SELECTION compares per-dispatch rates only (every kernel has
        # one; mixing chained and unchained regimes would let a
        # dispatch-amortized pallas beat an unchained-but-faster xla)
        r = results.get(k, {})
        return r.get("histories_per_sec", -1.0)

    best_key = max(("xla", "pallas", "pallas16"), key=_rate)
    best = results[best_key]
    # steady-state (dispatch-amortized) rate is the headline when the
    # chained run exists; the per-dispatch rate stays in "kernels".
    # batch_rebuild_ms is derived from the SAME regime as the headline
    # rate — mixing the chained rate with the unchained latency made
    # the record self-contradictory (recomputing histories/s from the
    # *_ms fields disagreed with "value"; ADVICE r5)
    headline_rate = best.get(
        "histories_per_sec_chained", best["histories_per_sec"]
    )
    out = {
        "histories_per_sec": headline_rate,
        "kernel": best_key,
        "baseline_cpp_per_sec": round(cpp_rate, 2),
        "vs_baseline": round(headline_rate / cpp_rate, 2),
        "mean_depth": round(mean_depth, 1),
        "batch_rebuild_ms": round(batch / headline_rate * 1000, 3),
        "batch_rebuild_ms_unchained": best["batch_rebuild_ms"],
        "batch": batch,
        # padded steps ÷ real events: the per-lane padding waste the
        # lane-packed configs eliminate (one history per lane here)
        "padding_frac": round(
            float(batch * T - lengths.sum()) / max(int(lengths.sum()), 1),
            4),
        "lanes_per_history": 1.0,
        "kernels": results,
    }
    return out


def main(device: dict) -> None:
    from cadence_tpu import native
    from cadence_tpu.ops import schema as S
    from cadence_tpu.utils.compile_cache import configure_compile_cache

    if native._load() is None:
        _emit(_fail_record("native baseline unavailable (no g++)"))
        return

    wall_s = float(os.environ.get("BENCH_WALL_S", "2100"))
    _watchdog(wall_s)
    configure_compile_cache()

    on_cpu = device["platform"] == "cpu"
    # the Pallas kernel needs the real chip; interpret mode is a test
    # vehicle, not a benchmark
    use_pallas = not on_cpu
    scale = 1 if on_cpu else 128
    iters = 3 if on_cpu else 5
    bt, tb = 8192, 16
    if SMOKE:
        scale, iters = 1, 1

    # per-config capacities: sized to the workload (slot tables directly
    # set HBM bytes/step for the XLA kernel and VMEM rows for Pallas)
    CONFIGS = {
        # echo rides the lane-packed path: ~23 whole 11-event histories
        # per 256-step lane instead of one 11-event history per 16-step
        # lane — the scan replays ~16/11x more real events per step and
        # the type-specialized step body skips the transition blocks an
        # echo storm never touches
        "echo": dict(
            caps=S.Capacities(max_events=16, max_activities=2, max_timers=2,
                              max_children=2, max_request_cancels=2,
                              max_signals_ext=2, max_version_items=2),
            batch=512 * scale, baseline=2048,
            # column-layout per-step cost grows sublinearly in lanes, so
            # the packed grid uses the widest batch that still fits the
            # bench wall (~47k whole histories per 256-step scan)
            packed=dict(lanes=min(2048 * scale, 8192), lane_len=256)),
        # 90% depth-16 / 10% depth-1k: the depth-bucketed dispatch
        # configuration — without bucketing+packing every lane pads to
        # the 1k stragglers (unpacked_histories_per_sec reports that)
        "mixed_depth": dict(
            caps=S.Capacities(max_events=1024, max_activities=4,
                              max_timers=2, max_children=2,
                              max_request_cancels=2, max_signals_ext=2,
                              max_version_items=2),
            batch=512 * scale, baseline=512,
            packed=dict(lanes=min(512 * scale, 4096), lane_len=1024)),
        "signal": dict(
            caps=S.Capacities(max_events=512, max_activities=2, max_timers=2,
                              max_children=2, max_request_cancels=2,
                              max_signals_ext=4, max_version_items=2),
            batch=512 * scale, baseline=512),
        "timer_storm": dict(
            caps=S.Capacities(max_events=512, max_activities=2, max_timers=16,
                              max_children=2, max_request_cancels=2,
                              max_signals_ext=2, max_version_items=2),
            batch=512 * scale, baseline=512),
        "retry_deep": dict(
            caps=S.Capacities(max_events=1024, max_activities=4, max_timers=2,
                              max_children=2, max_request_cancels=2,
                              max_signals_ext=2, max_version_items=2),
            batch=512 * scale, baseline=256),
        "ndc_storm": dict(
            caps=S.Capacities(max_events=1024),  # full default tables
            batch=256 * scale, baseline=256),
        # checkpointed incremental replay: rebuild the same retry_deep
        # cohort twice — the second pass resumes from prefix snapshots
        # and replays only the tail (cadence_tpu/checkpoint/). Host-loop
        # bound (full rebuild_many pipeline), so the cohort stays modest
        "rebuild_warm": dict(
            warm=dict(n=96 if on_cpu else 256, depth=1000, iters=2)),
        # elastic resharding under live traffic: shard split mid-run,
        # decision-latency probes through the fenced window
        # (runtime/resharding.py; README "Elastic resharding")
        "reshard_live": dict(reshard=dict(duration_s=16.0)),
        # geo-replication catch-up on a throttled WAN link: event-ship
        # vs snapshot-ship vs adaptive (runtime/replication/transport.py;
        # README "Adaptive geo-replication")
        "replication_lag": dict(lag=dict(
            workflows=12, signals_each=48, bytes_per_s=131072.0)),
        # domain failover drills: managed handover, forced region-loss
        # promotion with a conflict storm, failback — per-scenario
        # unavailability + replication-lag SLOs
        # (runtime/replication/failover.py; README "Domain failover")
        "failover_drill": dict(failover=dict(
            workflows=6, signals_each=24, bytes_per_s=131072.0)),
        # continuous-batching serving under open-loop load: resident
        # O(Δ) appends at sustained QPS, p50/p99 decision-latency SLOs
        # (cadence_tpu/serving/; README "Continuous-batching serving")
        "serve_continuous": dict(serve=dict(
            workflows=48, qps=300.0, lanes=64)),
        # graceful degradation under sustained 2x overload: fair
        # admission + retry budgets + the tick pump's staleness bound
        # (ISSUE 15; README "Overload control")
        "serve_overload": dict(overload=dict(
            workflows=24, qps=400.0, lanes=8, capacity_frac=0.5)),
        # closed-loop capacity autopilot under a diurnal load curve:
        # the admission setpoint must track offered load BOTH ways
        # with zero operator calls and zero guardrail freezes
        # (ISSUE 16; README "Capacity autopilot")
        "capacity_diurnal": dict(diurnal=dict(
            workflows_per_chunk=8, qps_low=60.0, qps_high=600.0,
            chunks_low=3, chunks_high=4, chunks_trough=4, lanes=16)),
        # unsampled telemetry cost on the instrumented serving path:
        # the ≤3% guard tests/test_bench_smoke.py pins (utils/tracing)
        "telemetry_overhead": dict(telemetry=dict(
            calls=20000, rounds=5)),
        # conflict-keyed wave executor vs the sequential pump over an
        # identical mixed transfer/timer storm (runtime/queues/
        # parallel.py; README "Parallel queue execution") — the >=2x
        # tasks/sec acceptance bar rides this record
        "queue_drain": dict(qdrain=dict(
            tasks_per_queue=2000, n_wf=48, parallelism=12,
            stall_us=250)),
    }

    if SMOKE:
        # harness-coverage shapes: tiny tensors, seconds on CPU — one
        # unpacked config plus one lane-packed/bucketed config so the
        # packer's padding_frac contract stays under tier-1 coverage
        smoke_caps = S.Capacities(
            max_events=64, max_activities=4, max_timers=2,
            max_children=2, max_request_cancels=2,
            max_signals_ext=2, max_version_items=2)
        CONFIGS = {
            "retry_deep": dict(caps=smoke_caps, batch=32, baseline=32),
            "mixed_depth": dict(
                caps=smoke_caps, batch=32, baseline=32,
                packed=dict(lanes=8, lane_len=64)),
            # lane-packed echo at smoke scale: pins the histogram
            # latency contract (Registry.timer_stats-backed p50/p99 in
            # the record) on the serving-shaped config
            "echo": dict(
                caps=smoke_caps, batch=32, baseline=32,
                packed=dict(lanes=8, lane_len=64)),
            # checkpoint-resume contract coverage (suffix_frac < 1.0,
            # checkpoint_hit_rate reported) at seconds-scale shapes
            "rebuild_warm": dict(warm=dict(n=24, depth=40, iters=1)),
            # reshard JSON contract at seconds-scale load
            "reshard_live": dict(
                reshard=dict(duration_s=2.0, probe_interval_s=0.02)),
            # adaptive-replication contract: tiny backlog, link slow
            # enough that the byte asymmetry (compressed snapshot <<
            # hydrated event backlog) dominates host-load noise
            "replication_lag": dict(lag=dict(
                workflows=3, signals_each=20, bytes_per_s=24576.0)),
            # failover-drill JSON contract at seconds-scale load
            "failover_drill": dict(failover=dict(
                workflows=2, signals_each=8, bytes_per_s=131072.0)),
            # open-loop serving SLO contract at seconds-scale load
            "serve_continuous": dict(serve=dict(
                workflows=6, qps=120.0, lanes=8,
                min_events=20, max_events=48)),
            # overload JSON contract: 2x offered load over a tiny
            # capacity bucket — shed_frac > 0, every domain progresses,
            # staleness stays bounded, all at seconds scale
            "serve_overload": dict(overload=dict(
                workflows=9, qps=150.0, lanes=4, capacity_frac=0.5,
                min_events=16, max_events=32)),
            # capacity-autopilot JSON contract at seconds scale: the
            # setpoint tracks low->high->low, zero guardrail freezes,
            # zero operator calls
            # (4 trough chunks: the demand EWMA needs the extra epoch
            # to decay visibly below the peak on a slow/contended CPU,
            # where compute bounds the offered rate and compresses the
            # low-vs-high dynamic range)
            "capacity_diurnal": dict(diurnal=dict(
                workflows_per_chunk=4, qps_low=30.0, qps_high=300.0,
                chunks_low=2, chunks_high=3, chunks_trough=4, lanes=8,
                min_events=10, max_events=16, initial_rps=100.0)),
            # the ≤3% unsampled-tracing guard at smoke scale. The
            # min-over-paired-rounds estimator needs ONE clean pair;
            # shorter rounds shrink the per-pair window a host stall
            # can land in and more rounds multiply the chances of a
            # clean one — 9x1500 costs ~the same 12k paired calls as
            # the original 3x4000 with 3x the chances, after false
            # >3% readings were observed on the loaded single-core CI
            # host right after heavy suites
            "telemetry_overhead": dict(telemetry=dict(
                calls=1500, rounds=9)),
            # queue-drain JSON contract at seconds scale: shape + the
            # sequential/parallel state-equality and non-degraded
            # matrix-gate bits (speedup itself is noise-bound at this
            # scale and is only pinned > 0)
            "queue_drain": dict(qdrain=dict(
                tasks_per_queue=250, n_wf=16, parallelism=4,
                batch_size=64)),
        }

    copy_bw = measure_copy_bw_gbps() if not on_cpu else None

    # headline first; if the wall-clock budget runs out (cold compile
    # cache), the JSON line still carries the metric that matters and
    # marks the rest skipped
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    # never *start* a non-headline config that could straddle the
    # watchdog wall: a cold-compile config can eat the whole slack and
    # turn an otherwise-healthy run into an error record
    wall_margin_s = 480.0
    # rebuild_warm right after the headline: the checkpoint-resume
    # record (hit rate / suffix_frac / vs_cold) must not fall to the
    # wall-clock budget skip that trims trailing configs
    front = [k for k in ("retry_deep", "rebuild_warm") if k in CONFIGS]
    order = front + [k for k in CONFIGS if k not in front]
    t_start = time.perf_counter()
    results = _PARTIAL
    for config in order:
        cfg = CONFIGS[config]
        elapsed = time.perf_counter() - t_start
        if config != "retry_deep" and (
            elapsed > budget_s or elapsed > wall_s - wall_margin_s
        ):
            results[config] = {"skipped": "bench budget exhausted"}
            continue
        if "reshard" in cfg:
            try:
                results[config] = _bench_reshard_live(**cfg["reshard"])
            except Exception as e:  # a wedged box must not eat the record
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "lag" in cfg:
            try:
                results[config] = _bench_replication_lag(**cfg["lag"])
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "failover" in cfg:
            try:
                results[config] = _bench_failover_drill(**cfg["failover"])
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "serve" in cfg:
            try:
                results[config] = _bench_serve_continuous(**cfg["serve"])
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "overload" in cfg:
            try:
                results[config] = _bench_serve_overload(**cfg["overload"])
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "diurnal" in cfg:
            try:
                results[config] = _bench_capacity_diurnal(
                    **cfg["diurnal"]
                )
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "telemetry" in cfg:
            try:
                results[config] = _bench_telemetry_overhead(
                    **cfg["telemetry"]
                )
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "qdrain" in cfg:
            try:
                results[config] = _bench_queue_drain(**cfg["qdrain"])
            except Exception as e:
                results[config] = {
                    "error": f"{type(e).__name__}: {str(e)[:200]}"
                }
        elif "warm" in cfg:
            results[config] = _bench_rebuild_warm(
                cfg["warm"]["n"], cfg["warm"]["depth"],
                cfg["warm"]["iters"])
        elif "packed" in cfg:
            results[config] = _bench_config_packed(
                config, cfg["caps"], cfg["packed"]["lanes"],
                cfg["packed"]["lane_len"], iters, cfg["baseline"])
        else:
            results[config] = _bench_config(
                config, cfg["caps"], cfg["batch"], iters, cfg["baseline"],
                bt, tb, use_pallas,
                chain=int(os.environ.get(
                    "BENCH_CHAIN",
                    "4" if (config == "retry_deep" and use_pallas) else "1",
                )))

    head = results["retry_deep"]
    out = {
        "metric": "histories_replayed_per_sec_at_1k_depth",
        "value": head["histories_per_sec"],
        "unit": "histories/s",
        "vs_baseline": head["vs_baseline"],
        "baseline": "native C++ -O3 sequential replayer (same semantics, same data)",
        "kernel": head["kernel"],
        "batch_rebuild_ms_per_1k_history": round(
            head["batch_rebuild_ms"] / head["batch"], 4),
        "on_cpu": on_cpu,
        "configs": results,
    }
    out["device"] = device
    if SMOKE:
        out["smoke"] = True
    if copy_bw is not None:
        out["copy_bw_gbps"] = round(copy_bw, 1)
    _emit(out)


if __name__ == "__main__":
    device = _device_record()
    try:
        main(device)
    except BaseException as exc:  # the record must exist no matter what
        _emit(_fail_record(f"{type(exc).__name__}: {str(exc)[:300]}"))
    # the record is out (flushed); skip interpreter teardown — XLA:CPU's
    # executable destructors can segfault at exit under memory pressure,
    # which would turn a perfectly good record into returncode -11
    os._exit(0)

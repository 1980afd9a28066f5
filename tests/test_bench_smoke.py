"""CI coverage for bench.py itself.

The driver records bench.py's stdout as the perf record. These tests
pin the contract: a run asked for the CPU (``BENCH_SMOKE=1``) exits 0
and prints exactly one parseable JSON line carrying the metric keys
and the device it ran on; a run that was not asked for the CPU and
finds no TPU exits non-zero and prints no record."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra):
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, r.stdout
    return json.loads(lines[0])


def test_smoke_emits_one_json_record():
    out = _run({"BENCH_SMOKE": "1"})
    for key in ("metric", "value", "unit", "vs_baseline", "configs"):
        assert key in out, out
    assert out["metric"] == "histories_replayed_per_sec_at_1k_depth"
    assert out["smoke"] is True and out["on_cpu"] is True
    head = out["configs"]["retry_deep"]
    assert head["histories_per_sec"] > 0
    assert head["baseline_cpp_per_sec"] > 0
    # every record names the device it ran on, as JAX reports it
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1 and out["device"]["kind"]
    # the lane-packing contract: every config reports its padding waste,
    # and packed configs keep it < 1.0 (padded steps < real events) —
    # a packer regression (fragmenting lanes, over-rounding) fails here
    packed_seen = 0
    for name, cfg in out["configs"].items():
        if "histories_per_sec" not in cfg or "suffix_frac" in cfg:
            continue  # rebuild_warm has its own contract below
        assert "padding_frac" in cfg, f"{name} lacks padding_frac"
        assert "lanes_per_history" in cfg, f"{name} lacks lanes_per_history"
        if cfg.get("packed"):
            packed_seen += 1
            assert cfg["padding_frac"] < 1.0, (name, cfg["padding_frac"])
            assert 0 < cfg["lanes_per_history"] < 1.0, name
            # the waste the packer removes must be visible in-record
            # (throughput ratios are host-load noise at smoke scale, so
            # only the padding contract is asserted)
            assert cfg["unpacked_padding_frac"] > cfg["padding_frac"], name
    assert packed_seen >= 1, "smoke must cover a lane-packed config"
    # the checkpointed-incremental-replay contract: the warm pass
    # resumes from snapshots (hit rate reported) and replays strictly
    # less than the full event stream (suffix_frac < 1.0); a resume
    # regression (lookups missing, suffixes not trimmed) fails here
    warm = out["configs"]["rebuild_warm"]
    for key in ("histories_per_sec", "cold_histories_per_sec", "vs_cold",
                "checkpoint_hit_rate", "suffix_frac"):
        assert key in warm, f"rebuild_warm lacks {key}"
    assert warm["suffix_frac"] < 1.0, warm["suffix_frac"]
    assert warm["checkpoint_hit_rate"] > 0, warm["checkpoint_hit_rate"]
    # the elastic-resharding contract: a live split committed mid-load,
    # with the handoff pause (write-unavailability window) and the
    # decision-latency probe percentiles as explicit record fields —
    # absolute latencies are host-load noise at smoke scale, so only
    # the record shape + commit + a nonzero sustained rate are pinned
    live = out["configs"]["reshard_live"]
    for key in ("steady_rate_wf_per_sec", "workflows_completed",
                "start_p50_ms", "start_p99_ms", "during_handoff",
                "handoff"):
        assert key in live, f"reshard_live lacks {key}"
    assert live["steady_rate_wf_per_sec"] > 0, live
    assert live["handoff"]["state"] == "COMMITTED", live["handoff"]
    assert live["handoff"]["epoch"] >= 1
    assert live["handoff"]["pause_ms"] >= 0
    assert live["handoff"]["moved_workflows"] > 0
    for key in ("samples", "p50_ms", "p99_ms", "max_ms"):
        assert key in live["during_handoff"], live["during_handoff"]
    # the adaptive geo-replication contract: all three transport arms
    # converge byte-identical over the throttled link, the snapshot
    # arms prove suffix-only installs via events_replayed_saved, the
    # adaptive controller demonstrably switches modes, and adaptive
    # catch-up never loses to pure event shipping (the sleeps of the
    # simulated link dominate host-load noise, so the ratio holds even
    # at smoke scale — margin for the scheduler)
    lag = out["configs"]["replication_lag"]
    for arm in ("events", "snapshot", "adaptive"):
        rec = lag[arm]
        for key in ("catch_up_s", "converged_s", "bytes_shipped",
                    "backlog_events", "converged"):
            assert key in rec, f"replication_lag.{arm} lacks {key}"
        assert rec["converged"] is True, (arm, rec)
    assert lag["snapshot"]["snapshots_shipped"] > 0, lag["snapshot"]
    assert lag["snapshot"]["events_replayed_saved"] > 0, lag["snapshot"]
    assert lag["adaptive"]["mode_switches"] >= 1, lag["adaptive"]
    assert lag["adaptive"]["catch_up_s"] <= \
        lag["events"]["catch_up_s"] * 1.25, lag
    # the failover-drill contract (ISSUE 13): all three drill shapes
    # report their unavailability window + replication lag at promote
    # time, the forced+failback sequence resolves a real version-branch
    # conflict storm, replication lag drains to zero after the final
    # convergence, and the worst unavailability window sits inside the
    # SLO bound (metadata flip + cache observation — never a drain)
    fo = out["configs"]["failover_drill"]
    for drill in ("managed", "forced", "failback"):
        rec = fo[drill]
        for key in ("handover_ms", "unavailability_ms",
                    "lag_at_promote_events", "conflicts_resolved"):
            assert key in rec, f"failover_drill.{drill} lacks {key}"
        assert rec["unavailability_ms"] >= 0
    assert fo["managed"]["lag_at_promote_events"] == 0, fo["managed"]
    assert fo["failback"]["conflicts_resolved"] >= 1, fo["failback"]
    assert fo["replication_lag_events_final"] == 0, fo
    assert fo["slo"]["met"] is True, fo["slo"]
    assert fo["slo"]["unavailability_ms_worst"] < \
        fo["slo"]["unavailability_ms_bound"], fo["slo"]
    # the telemetry contract (ISSUE 10): headline latency lines are
    # Registry.timer_stats-backed histogram p50/p99 (echo — the
    # serving-shaped config — and rebuild_warm both carry them), and
    # the unsampled tracing path costs <= 3% vs the metrics-only
    # wrapper (min over paired interleaved rounds — strictly-additive
    # timing noise makes every observed ratio an upper bound, so the
    # guard is stable on loaded CI hosts)
    for name in ("echo", "rebuild_warm"):
        cfg = out["configs"][name]
        assert cfg["latency_p50_ms"] > 0, (name, cfg)
        assert cfg["latency_p99_ms"] >= cfg["latency_p50_ms"], (name, cfg)
    tel = out["configs"]["telemetry_overhead"]
    for key in ("untraced_calls_per_sec", "unsampled_calls_per_sec",
                "sampled_calls_per_sec", "overhead_unsampled_frac"):
        assert key in tel, f"telemetry_overhead lacks {key}"
    assert tel["untraced_calls_per_sec"] > 0
    assert tel["overhead_unsampled_frac"] <= 0.03, tel
    # the continuous-batching serving contract (ISSUE 14): open-loop
    # decision-latency SLOs come off the PR 9 histogram plane
    # (Registry.timer_stats — p99 >= p50 > 0), the warm phase answers
    # from resident lanes (hit rate > 0), and the O(Δ) pin holds —
    # events the engine composed are the appended Δs (never more; shed
    # arrivals skip their append), a small fraction of what a cold
    # per-arrival rebuild of the same cohort would replay, and the
    # shutdown drain flushes every lane cleanly
    srv = out["configs"]["serve_continuous"]
    for key in ("latency_p50_ms", "latency_p99_ms", "resident_hit_rate",
                "qps_sustained", "events_appended", "events_replayed",
                "events_per_append", "suffix_frac", "cold_events_equiv",
                "drain_flush_failed"):
        assert key in srv, f"serve_continuous lacks {key}"
    assert srv["completed"] > 0, srv
    assert srv["latency_p50_ms"] > 0, srv
    assert srv["latency_p99_ms"] >= srv["latency_p50_ms"], srv
    assert srv["resident_hit_rate"] > 0, srv
    assert 0 < srv["events_replayed"] <= srv["events_appended"], srv
    assert srv["suffix_frac"] < 0.5, (
        "resident appends must be O(Δ), not a cold rebuild per arrival",
        srv["suffix_frac"],
    )
    assert srv["drain_flush_failed"] == 0, srv
    # the overload-control contract (ISSUE 15): at 2x offered load the
    # degradation ladder engages — a real shed fraction (excess load is
    # rejected, not queued into the p99), per-domain progress counters
    # prove zero starvation under weighted fair admission, the retry
    # budget keeps offered-load amplification bounded, and the tick
    # pump holds resident staleness under the configured bound
    ovl = out["configs"]["serve_overload"]
    for key in ("shed_frac", "offered_amplification", "goodput_qps",
                "latency_p50_ms", "latency_p99_ms", "per_domain",
                "staleness_p99_ms", "staleness_bound_ms",
                "staleness_in_bound", "retries",
                "retry_budget_exhausted", "drain_flush_failed"):
        assert key in ovl, f"serve_overload lacks {key}"
    assert ovl["shed_frac"] > 0, (
        "2x offered load must shed", ovl,
    )
    for dom, rec in ovl["per_domain"].items():
        assert rec["completed"] > 0, (
            f"domain {dom} starved under overload", ovl["per_domain"],
        )
    # budget boundedness: offered = arrivals + budgeted retries only
    assert ovl["offered"] == ovl["requests"] + ovl["retries"], ovl
    assert ovl["staleness_in_bound"] is True, ovl
    assert ovl["drain_flush_failed"] == 0, ovl
    # the capacity-autopilot contract (ISSUE 16): over a low->high->low
    # diurnal curve the closed loop retunes the live admission setpoint
    # to track offered demand BOTH directions — hands off (zero
    # operator verbs), do-no-harm (zero guardrail freezes), and every
    # phase reports its own p99/shed/rate/demand fields
    dr = out["configs"]["capacity_diurnal"]
    for key in ("phases", "rate_low_rps", "rate_high_rps",
                "rate_final_rps", "rate_tracks_load", "retunes",
                "guardrail_freezes", "gate_switches", "operator_calls",
                "epochs", "p99_overall_ms", "shed_frac_overall",
                "drain_flush_failed"):
        assert key in dr, f"capacity_diurnal lacks {key}"
    for phase in ("low", "high", "trough"):
        rec = dr["phases"][phase]
        for key in ("offered_qps_target", "admitted", "shed_frac",
                    "p99_ms", "rate_rps", "demand_rps"):
            assert key in rec, f"capacity_diurnal.{phase} lacks {key}"
        assert rec["admitted"] > 0, (phase, rec)
    assert dr["rate_tracks_load"] is True, dr
    assert dr["retunes"] >= 3, dr
    assert dr["guardrail_freezes"] == 0, dr
    assert dr["operator_calls"] == 0, dr
    assert dr["drain_flush_failed"] == 0, dr
    # the parallel-queue-drain contract (ISSUE 20): both drain arms run
    # the identical mixed transfer/timer storm to completion, the
    # commutative final state matches byte-for-byte, the wave executor
    # schedules through a FRESH conflict-matrix artifact (a degraded
    # gate would silently bench sequential-vs-sequential), and the wave
    # observables (width / conflict_frac) land in the record. The >=2x
    # speedup bar binds on real runs — at smoke scale and on a loaded
    # single-core host the ratio is scheduling noise, so only
    # directionality (speedup > 0) is pinned here
    qd = out["configs"]["queue_drain"]
    for key in ("tasks", "queues", "parallelism", "seq_tasks_per_sec",
                "par_tasks_per_sec", "speedup", "wave_width_mean",
                "conflict_frac", "cycles", "stale_skipped", "degraded",
                "drained", "state_identical"):
        assert key in qd, f"queue_drain lacks {key}"
    assert qd["drained"] is True, qd
    assert qd["state_identical"] is True, (
        "parallel drain diverged from the sequential drain", qd,
    )
    assert qd["degraded"] is False, (
        "wave executor degraded: conflict-matrix artifact stale", qd,
    )
    assert qd["seq_tasks_per_sec"] > 0 and qd["par_tasks_per_sec"] > 0
    assert qd["speedup"] > 0, qd
    assert qd["wave_width_mean"] > 1.0, (
        "no cycle ever split into concurrent conflict groups", qd,
    )
    assert 0.0 <= qd["conflict_frac"] < 1.0, qd
    assert qd["cycles"] > 0, qd


def test_watchdog_still_yields_parseable_record():
    # wall budget so small the watchdog fires mid-run: the record must
    # still be one JSON line with the metric keys and an error field
    out = _run({"BENCH_SMOKE": "1", "BENCH_WALL_S": "0.01"})
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in out, out
    assert "error" in out


def test_no_tpu_without_cpu_flag_exits_nonzero_with_no_record():
    """No chip, no ``--cpu``, no smoke flag: bench.py must fail loudly
    before measuring anything — never fall back to the CPU and report
    its numbers under the device metric's name."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_SMOKE"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=300)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "histories_per_sec" not in r.stdout
    assert "no TPU" in r.stderr

"""Tests of the storm cells that ``fanout_wide`` and ``retry_deep``
bring, on the CPU at tiny sizes, against the benchmark as
``BENCHMARK.json`` declares it: the rehearsal of each cell, the faults
and the control for the fan-out cell, the traced run's new layers, and
the fan-out generator at the configuration's own widths.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import copy
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

import test_benchmark as TB  # noqa: E402  (this directory's helpers)

from benchmark import harness  # noqa: E402

SEED = TB.SEED

FANOUT = "fanout_wide.rebuild_storm"
TINY_FANOUT = {"generator": "fanout", "min_width": 33, "max_width": 40,
               "min_depth": 33, "max_depth": 40, "close_share": [0.0, 0.75],
               "decision_every": [8, 32], "completed_share": 0.9,
               "version": 10}
TINY = {
    FANOUT: {"traffic": {"per_call": 4, "pool": 8},
             "config": {"histories": TINY_FANOUT}},
    "retry_deep.rebuild_storm": {
        "traffic": {"per_call": 4, "pool": 8},
        "config": {"histories": {"generator": "retry_deep",
                                 "min_depth": 60, "max_depth": 60,
                                 "version": 10}}},
}


def run(workload, trace=False, seconds=0.5):
    return harness.run_cell(workload, SEED, seconds, trace,
                            time.perf_counter(), require_chip=False,
                            overrides=copy.deepcopy(TINY[workload]))


@pytest.fixture(autouse=True)
def cpu_only():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("CPU rehearsal")


@pytest.mark.parametrize("workload", sorted(TINY))
def test_storm_cell_rehearsal(workload, capsys):
    got = run(workload)
    out = capsys.readouterr()
    info = json.loads(out.out.strip().splitlines()[-2])["info"]
    assert info["compiles_in_window"] == 0
    pool = TINY[workload]["traffic"]["pool"]
    assert info["check"]["compared"] == min(info["histories"], pool)
    assert got["correct"] is True, out.err[-2000:]
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["checks"]["host_fallbacks"]["value"] == 0
    assert set(got["metrics"]) == {"rebuild_rate", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_makes_the_fanout_cell_incorrect(fault, monkeypatch):
    TB.plant_rebuild(monkeypatch, fault)
    assert run(FANOUT)["correct"] is False


def test_fanout_control_is_not_correct():
    from benchmark import control

    (r,) = control.readings(FANOUT, [SEED], seconds=0.5,
                            require_chip=False,
                            overrides=copy.deepcopy(TINY[FANOUT]))
    assert all(v == 0 for v, _ in r["program"].values())
    assert any(v > lim for v, lim in r["control"].values())


def test_traced_fanout_reads_the_bucket_and_slot_layers(monkeypatch):
    from benchmark import trace_reduce

    real = trace_reduce.reduce_trace
    monkeypatch.setattr(trace_reduce, "reduce_trace",
                        lambda path, top=10: real(TB.SMALL_TRACE, top))
    got = run(FANOUT, trace=True)
    assert got["correct"] is True
    m = got["metrics"]
    assert m["dispatch.bucket.self_ms"]["value"] > 0
    # every fan-out parent fills part of its bucket; the default floor
    # of the other tables pads the rest
    assert 0 < m["pallas_slot_fill"]["value"] < 100


def test_traced_notes_count_wide_histories_and_fallbacks(capsys,
                                                         monkeypatch):
    from benchmark import trace_reduce

    real = trace_reduce.reduce_trace
    monkeypatch.setattr(trace_reduce, "reduce_trace",
                        lambda path, top=10: real(TB.SMALL_TRACE, top))
    run(FANOUT, trace=True)
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-2])
    notes = info["info"]["layer_notes"]["rebuild_many"]
    assert notes["wide_histories"] == notes["device_histories"] > 0
    assert notes["host_fallbacks"] == 0
    assert notes["requests"] == info["info"]["histories"]


def test_fanout_generator_at_the_configured_widths():
    """Every group holds the same widths, stratified over 33-300; every
    history fits the device's 1,024-event window, replays on the plain
    reference, peaks at its drawn width and is cut mid-fan-in."""
    from benchmark import compare as C, gen
    from cadence_tpu.core.enums import EventType as E
    from cadence_tpu.ops import schema as S

    _, config, _ = harness.resolve(harness.load_spec(), FANOUT)
    spec = config["histories"]
    groups = gen.grouped(spec, S.Capacities(), SEED, 2, 16)
    widths = [sorted(len(b[3]) - 1 for _, b in g) for g in groups]
    assert widths[0] == widths[1]
    assert min(widths[0]) >= 33 and max(widths[0]) <= 300
    assert max(widths[0]) - min(widths[0]) > 200
    for idx, batches in groups[0]:
        k = len(batches[3]) - 1  # the wave rides the DecisionTaskCompleted
        assert sum(len(b) for b in batches) <= 1000
        now = peak = 0
        for b in batches:
            for ev in b:
                if ev.event_type == E.ActivityTaskScheduled:
                    now += 1
                elif ev.event_type in (E.ActivityTaskCompleted,
                                       E.ActivityTaskFailed):
                    now -= 1
                peak = max(peak, now)
        assert peak == k
        ms, _, _ = C.reference_rebuild(batches, "d", f"wf-{idx}",
                                       f"run-{idx}")
        assert k // 4 <= len(ms.pending_activities) <= k

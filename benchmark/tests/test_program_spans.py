"""The per-layer metrics read from the program's own spans
(benchmark/program_spans.py), on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import copy
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, load, program_spans  # noqa: E402

from test_benchmark import SEED, SMALL_TRACE, TINY  # noqa: E402

NEW = {
    "ndc_mixed.rebuild_storm": [
        "rebuild.read.self_ms", "rebuild.unpack.self_ms",
        "rebuild.refresh.self_ms", "rebuild.await.self_ms",
        "dispatch.pack.self_ms", "pallas_packed_fill"],
    "retry_deep.replay_bulk": [
        "replay.layout.self_ms", "replay.h2d.self_ms",
        "replay.fetch.self_ms", "pallas_teb_fill"],
}


@pytest.fixture(autouse=True)
def cpu_only():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("CPU rehearsal")


def traced(workload, monkeypatch):
    """A --trace 1 run of one cell; the committed chip trace stands in
    for the CPU's, which has no device plane."""
    from benchmark import trace_reduce

    real = trace_reduce.reduce_trace
    monkeypatch.setattr(trace_reduce, "reduce_trace",
                        lambda path, top=10: real(SMALL_TRACE, top))
    return harness.run_cell(workload, SEED, 0.5, True, time.perf_counter(),
                            require_chip=False,
                            overrides=copy.deepcopy(TINY[workload]))


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_run_reads_every_span_metric(workload, monkeypatch, capsys):
    import json

    from cadence_tpu.utils.tracing import TRACER

    got = traced(workload, monkeypatch)
    assert got["correct"] is True
    for name in NEW[workload]:
        assert got["metrics"][name]["value"] > 0, name
    fills = [n for n in NEW[workload] if n.endswith("_fill")]
    for name in fills:
        assert got["metrics"][name]["value"] <= 100.0
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-2])
    notes = info["info"]["layer_notes"]
    spans = notes["program_spans"]
    assert spans["dropped"] == 0 and spans["roots"] > 0
    # the roots' same-thread children cover most of each call (a tiny
    # call here lasts milliseconds, so the spans' own cost shows)
    assert spans["root_cover_min"] > 0.5
    assert spans["h2d_bytes"] > 0
    assert set(spans["self_s"]) >= {n.replace(".self_ms", "")
                                    for n in NEW[workload]
                                    if n.endswith(".self_ms")}
    if workload.endswith("replay_bulk"):
        assert spans["d2h_bytes"] > 0
    for name in NEW[workload]:
        if name not in fills:
            n = notes[name]
            assert n["spans"] > 0
            assert 0 < n["call_median_s"] <= n["call_max_s"] <= n["total_s"]
    # the tracer's settings are back as they were
    assert TRACER.sample_rate == 0.0 and TRACER.profiler_prefix is None
    assert TRACER.capacity == 4096


class Ctx(harness.LayerContext):
    def __init__(self):
        super().__init__(driver=None, peaks=None)
        self.histories = 4


def test_a_dropped_span_makes_the_reader_read_nothing():
    from cadence_tpu.utils.tracing import TRACER

    ctx = Ctx()
    undo = program_spans.install(ctx)
    assert program_spans.install(ctx) == []  # idempotent
    TRACER.configure(capacity=2)  # three spans into a ring of two
    for name in ("rebuild.read", "rebuild.read", "rebuild.read"):
        with TRACER.trace(name):
            pass
    for u in undo:
        u()
    reader = load("layers", "rebuild.read.self_ms")
    assert ctx.store["program_spans"]["dropped"] == 1
    assert reader.read(ctx) is None


def test_self_time_leaves_out_same_thread_children_only():
    from cadence_tpu.utils.tracing import TRACER

    ctx = Ctx()
    undo = program_spans.install(ctx)
    with TRACER.trace("rebuild_many"):
        with TRACER.span("rebuild.read"):
            time.sleep(0.02)
        time.sleep(0.01)
    for u in undo:
        u()
    spans = {s.name: s for s in ctx.store["program_spans"]["spans"]}
    got = program_spans.self_ms(ctx, "rebuild_many", "m")
    root, read = spans["rebuild_many"], spans["rebuild.read"]
    want = (root.dur_us - read.dur_us) / 1e3 / ctx.histories
    assert got == pytest.approx(want)
    assert ctx.notes["program_spans"]["root_cover_min"] == pytest.approx(
        read.dur_us / root.dur_us)


def test_a_program_without_the_spans_gives_no_reading(monkeypatch):
    """The parent of the change that brought the spans: its tracer has
    no profiler seam, so install sets nothing and readers read None."""
    from cadence_tpu.utils.tracing import Tracer

    monkeypatch.delattr(Tracer, "set_profiler_prefix")
    ctx = Ctx()
    assert program_spans.install(ctx) == []
    for name in NEW["ndc_mixed.rebuild_storm"]:
        assert load("layers", name).read(ctx) is None

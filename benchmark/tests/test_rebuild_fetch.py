"""rebuild.fetch.self_ms, read from the program's ``rebuild.fetch`` spans,
on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from benchmark import load, program_spans  # noqa: E402

from test_program_spans import Ctx, traced  # noqa: E402

METRIC = "rebuild.fetch.self_ms"


@pytest.fixture(autouse=True)
def cpu_only():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("CPU rehearsal")


def test_traced_storm_reads_one_fetch_per_device_batch(monkeypatch, capsys):
    got = traced("ndc_mixed.rebuild_storm", monkeypatch)
    assert got["correct"] is True
    assert got["metrics"][METRIC]["value"] > 0
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-2])
    notes = info["info"]["layer_notes"]
    assert notes[METRIC]["spans"] == notes["pallas_packed_fill"]["launches"]


def test_a_program_without_the_fetch_span_gives_no_reading():
    """A program from before the span records the other spans but no
    ``rebuild.fetch``: the reader reads None."""
    from cadence_tpu.utils.tracing import TRACER

    ctx = Ctx()
    undo = program_spans.install(ctx)
    with TRACER.trace("rebuild_many"):
        with TRACER.span("rebuild.unpack"):
            pass
    for u in undo:
        u()
    assert load("layers", METRIC).read(ctx) is None

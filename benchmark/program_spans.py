"""The program's own spans (``cadence_tpu/utils/tracing.py``), read by the
per-layer metrics whose source is ``program_span``.

``install`` samples every trace the window's calls root and turns the
tracer's profiler annotation on with the prefix ``bench.span.``, so that
each span is also a host span of the profiler's trace and names the
device's idle gaps there (``trace_reduce.py`` labels a gap by the
innermost ``bench.*`` span). When the window closes, its undo keeps the
recorded spans for the readers and restores the tracer's settings.

Readers compute a span's self time: its duration less the union of its
children on the same thread (a pump thread's span is its own row). A
reader returns None where the program has no such span (a program from
before the spans), where none was recorded, or where any span fell off
the tracer's ring.
"""

from __future__ import annotations

import collections
import statistics
from typing import Dict, List, Optional, Tuple

KEY = "program_spans"
PREFIX = "bench.span."
# spans the ring may hold: a storm window records ~300 a call
CAPACITY = 1 << 20
ROOTS = ("rebuild_many", "replay_packed")
H2D = ("dispatch.h2d", "replay.h2d")
D2H = ("replay.fetch",)


def install(ctx):
    """Idempotent: the first metric that reads spans sets the tracer up,
    the others share it. Returns the undo."""
    if KEY in ctx.store:
        return []
    from cadence_tpu.utils import tracing

    tracer = tracing.TRACER
    if not hasattr(tracer, "set_profiler_prefix"):
        ctx.store[KEY] = None
        return []
    saved = (tracer.sample_rate, tracer.capacity)
    tracer.clear()
    tracer.configure(sample_rate=1.0, capacity=CAPACITY)
    saved_prefix = tracer.set_profiler_prefix(PREFIX)
    kept = ctx.store[KEY] = {}

    def undo():
        tracer.configure(sample_rate=saved[0])
        tracer.set_profiler_prefix(saved_prefix)
        kept["spans"] = tracer.spans()
        kept["dropped"] = tracer.dropped
        tracer.configure(capacity=saved[1])

    return [undo]


def _union_s(iv: List[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(iv):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _interval(s) -> Tuple[float, float]:
    return s.start_s, s.start_s + s.dur_us / 1e6


def _analysed(ctx) -> Optional[Dict]:
    """The window's spans with each one's self and child-covered
    seconds, computed once; None where there is nothing to read."""
    kept = ctx.store.get(KEY)
    if not kept or "spans" not in kept:
        return None
    if "analysed" in kept:
        return kept["analysed"]
    spans = kept["spans"]
    kids = collections.defaultdict(list)
    for s in spans:
        if s.parent_id:
            kids[(s.parent_id, s.thread)].append(_interval(s))
    covered = {}
    for s in spans:
        lo, hi = _interval(s)
        covered[s.span_id] = _union_s(
            [(max(a, lo), min(b, hi))
             for a, b in kids.get((s.span_id, s.thread), ())
             if b > lo and a < hi])
    got = kept["analysed"] = {"spans": spans, "covered": covered}
    roots = [s for s in spans if s.name in ROOTS and not s.parent_id]
    cover = [covered[s.span_id] / (s.dur_us / 1e6) for s in roots]

    def tagged_sum(names):
        return sum(s.tags.get("bytes", 0) for s in spans
                   if s.name in names)

    self_s: Dict[str, float] = collections.defaultdict(float)
    for s in spans:
        self_s[s.name] += s.dur_us / 1e6 - covered[s.span_id]
    ctx.notes[KEY] = {
        "spans": len(spans), "dropped": kept["dropped"],
        "roots": len(roots),
        "root_cover_min": min(cover) if cover else None,
        "root_cover_median": statistics.median(cover) if cover else None,
        "h2d_bytes": tagged_sum(H2D), "d2h_bytes": tagged_sum(D2H),
        # every span's self seconds in the window, by name (the pump
        # threads' spans overlap the caller's)
        "self_s": dict(self_s)}
    return got


def self_ms(ctx, span: str, metric: str) -> Optional[float]:
    """Milliseconds of ``span``'s self time per history completed in the
    window; notes the span's count, its total, and its median and
    maximum per call (a call being one root's trace)."""
    got = _analysed(ctx)
    if got is None or ctx.store[KEY]["dropped"] or not ctx.histories:
        return None
    per_call: Dict[str, float] = collections.defaultdict(float)
    n = 0
    for s in got["spans"]:
        if s.name == span:
            n += 1
            per_call[s.trace_id] += (s.dur_us / 1e6
                                     - got["covered"][s.span_id])
    if not n:
        return None
    total = sum(per_call.values())
    ctx.notes[metric] = {
        "spans": n, "total_s": total,
        "call_median_s": statistics.median(per_call.values()),
        "call_max_s": max(per_call.values())}
    return 1000.0 * total / ctx.histories


def fill_pct(ctx, span: str, metric: str) -> Optional[float]:
    """Real events over the event cells the kernel streamed, tile
    padding in, summed over the ``span`` launches that carry both
    counts, in %."""
    got = _analysed(ctx)
    if got is None or ctx.store[KEY]["dropped"]:
        return None
    launches = [s for s in got["spans"] if s.name == span
                and "events" in s.tags and "cells" in s.tags]
    cells = sum(s.tags["cells"] for s in launches)
    if not cells:
        return None
    events = sum(s.tags["events"] for s in launches)
    ctx.notes[metric] = {"launches": len(launches), "events": events,
                         "cells": cells}
    return 100.0 * events / cells

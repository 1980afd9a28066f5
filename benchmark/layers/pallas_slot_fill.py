"""pallas_slot_fill: share of the slot-table state that the rebuilt
histories' peaks fill, in %: the ``slots_used`` over the ``slots`` tags
of the program's ``dispatch.bucket`` spans (``slots``: each history's
bucket capacity summed over the five slot tables; ``slots_used``: the
sum of their peak occupancies). How much of the state the capacity
buckets pad. Read from the program's spans
(benchmark/program_spans.py); None for a program without the tags."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    got = program_spans._analysed(ctx)
    if got is None or ctx.store[program_spans.KEY]["dropped"]:
        return None
    spans = [s for s in got["spans"] if s.name == "dispatch.bucket"
             and "slots" in s.tags and "slots_used" in s.tags]
    slots = sum(s.tags["slots"] for s in spans)
    if not slots:
        return None
    used = sum(s.tags["slots_used"] for s in spans)
    ctx.notes["pallas_slot_fill"] = {"calls": len(spans), "slots": slots,
                                     "slots_used": used}
    return 100.0 * used / slots

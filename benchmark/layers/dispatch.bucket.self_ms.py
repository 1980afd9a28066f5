"""dispatch.bucket.self_ms: host milliseconds per rebuilt history in the
program's ``dispatch.bucket`` spans: the measure of each history's
slot-table widths and the grouping by width and depth, on the caller's
thread before the first submit. Read from the program's spans
(benchmark/program_spans.py); None for a program without the span.

Notes, beside it, the window's ``rebuild_many`` roots: their requests,
device histories, ``wide_histories`` (rebuilt in a bucket wider than the
default) and ``host_fallbacks``, summed."""

from benchmark import program_spans

install = program_spans.install

ROOT_TAGS = ("requests", "device_histories", "wide_histories",
             "host_fallbacks")


def read(ctx):
    got = program_spans._analysed(ctx)
    if got is not None:
        roots = [s for s in got["spans"] if s.name == "rebuild_many"]
        if roots:
            ctx.notes["rebuild_many"] = {
                tag: sum(s.tags.get(tag, 0) for s in roots)
                for tag in ROOT_TAGS}
    return program_spans.self_ms(
        ctx, "dispatch.bucket", "dispatch.bucket.self_ms")

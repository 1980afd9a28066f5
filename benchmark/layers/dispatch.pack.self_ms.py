"""dispatch.pack.self_ms: host milliseconds per rebuilt history in the
dispatcher's ``dispatch.pack`` spans on its pack thread: lane packing,
the field-major layout and the int16 narrowing of every batch. Read from
the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "dispatch.pack", "dispatch.pack.self_ms")

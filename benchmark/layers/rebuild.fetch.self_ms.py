"""rebuild.fetch.self_ms: host milliseconds per rebuilt history in the
program's ``rebuild.fetch`` spans: one fetch of each device batch's final
state to the host, before its rows unpack. Read from the program's spans
(benchmark/program_spans.py); None for a program without the span."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(
        ctx, "rebuild.fetch", "rebuild.fetch.self_ms")

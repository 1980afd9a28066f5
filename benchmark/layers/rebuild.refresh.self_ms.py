"""rebuild.refresh.self_ms: host milliseconds per rebuilt history in the
program's ``rebuild.refresh`` spans: ``refresh_tasks`` regenerating each
rebuilt state's transfer and timer tasks. Read from the program's spans
(benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(
        ctx, "rebuild.refresh", "rebuild.refresh.self_ms")

"""rebuild.read.self_ms: host milliseconds per rebuilt history in the
program's ``rebuild.read`` span: ``StateRebuilder.rebuild_many``
consulting and reading every request's history from the store. Read from
the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "rebuild.read", "rebuild.read.self_ms")

"""rebuild.await.self_ms: host milliseconds per rebuilt history that
``rebuild_many`` waits in its ``rebuild.await`` spans for the
dispatcher's next batch (packing, transfer and launch on the pump
threads). Read from the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "rebuild.await", "rebuild.await.self_ms")

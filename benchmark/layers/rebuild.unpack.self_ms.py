"""rebuild.unpack.self_ms: host milliseconds per rebuilt history in the
program's ``rebuild.unpack`` spans: ``state_row_to_mutable_state``
turning each row of the device's final state into a MutableState. Read
from the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(
        ctx, "rebuild.unpack", "rebuild.unpack.self_ms")

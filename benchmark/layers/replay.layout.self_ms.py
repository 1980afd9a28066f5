"""replay.layout.self_ms: host milliseconds per replayed history in
``replay_packed``'s ``replay.layout`` span: the kernel's field-major
event layout and its presence masks, built on the host. Read from the
program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "replay.layout", "replay.layout.self_ms")

"""replay.h2d.self_ms: host milliseconds per replayed history in
``replay_packed``'s ``replay.h2d`` span: the transfer of the kernel's
operands to the device, as far as the host waits for it. Read from the
program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "replay.h2d", "replay.h2d.self_ms")

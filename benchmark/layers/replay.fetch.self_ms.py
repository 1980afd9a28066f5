"""replay.fetch.self_ms: host milliseconds per replayed history in
``replay_packed``'s ``replay.fetch`` span: the wait for the kernel and
the copy of the final state back to the host. Read from the program's
spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.self_ms(ctx, "replay.fetch", "replay.fetch.self_ms")

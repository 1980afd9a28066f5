"""pallas_teb_fill: share of the event cells that the field-major kernel
streams which hold a real event, in %: the ``events`` over the ``cells``
tags of ``replay_packed``'s ``replay.launch`` spans (cells counted where
``replay_scan_pallas_teb`` pads rows and steps to whole tiles). Read
from the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.fill_pct(ctx, "replay.launch", "pallas_teb_fill")

"""pallas_packed_fill: share of the event cells that the lane-packed kernel
streams which hold a real event, in %: the ``events`` over the ``cells``
tags of the dispatcher's ``dispatch.launch`` spans (cells counted where
``replay_scan_pallas_packed`` pads its lanes to whole tiles). Read from
the program's spans (benchmark/program_spans.py)."""

from benchmark import program_spans

install = program_spans.install


def read(ctx):
    return program_spans.fill_pct(ctx, "dispatch.launch", "pallas_packed_fill")

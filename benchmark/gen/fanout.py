"""Fan-out / fan-in parents (split-merge), as upstream canary's
concurrentExec workflow and the split-merge recipe of
uber-common/cadence-samples run them: one decision schedules a wave of K
activities in a single DecisionTaskCompleted batch, the activities start
and close in a seeded interleaved order, and a fan-in decision runs
between runs of completions. The history is cut mid-fan-in, as a parent
stranded by a failover, an NDC conflict or a shard move is, so its final
state holds a wide pending-activity table; K is its peak.

The stratified value that ``gen.grouped`` hands each history (its
"depth") is K here, inside ``[min_width, max_width]``.
"""

from __future__ import annotations

import random
from typing import List

from cadence_tpu.core import history_factory as F
from cadence_tpu.core.events import HistoryEvent

SECOND = 1_000_000_000
T0 = 1_700_000_000 * SECOND

Batches = List[List[HistoryEvent]]


class _Ids:
    def __init__(self) -> None:
        self.eid = 0
        self.t = T0

    def next(self) -> int:
        self.eid += 1
        return self.eid

    def tick(self) -> int:
        self.t += SECOND
        return self.t


def _decision(ids: _Ids, v: int) -> Batches:
    """A decision cycle: scheduled, started, and the batch that opens
    with its DecisionTaskCompleted (the caller appends the commands)."""
    sch = ids.next()
    out = [[F.decision_task_scheduled(sch, v, ids.t)]]
    sta = ids.next()
    out.append([F.decision_task_started(sta, v, ids.tick(),
                                        scheduled_event_id=sch)])
    out.append([F.decision_task_completed(
        ids.next(), v, ids.tick(), scheduled_event_id=sch,
        started_event_id=sta)])
    return out


def fanout_history(rng: random.Random, width: int, spec) -> Batches:
    v = spec["version"]
    ids = _Ids()
    out: Batches = [[F.workflow_execution_started(
        ids.next(), v, ids.t, task_list="tl", workflow_type="fanout",
        execution_start_to_close_timeout_seconds=3600,
        task_start_to_close_timeout_seconds=10)]]
    # the split: one decision schedules the whole wave in its batch
    out += _decision(ids, v)
    dtc = out[-1][0].event_id
    sched = []
    for k in range(width):
        sched.append(ids.next())
        out[-1].append(F.activity_task_scheduled(
            sched[-1], v, ids.t, activity_id=f"a{k}",
            decision_task_completed_event_id=dtc,
            schedule_to_close_timeout_seconds=600,
            start_to_close_timeout_seconds=300))
    # cut mid-fan-in: a drawn share of the wave has closed, about half
    # of the rest has started
    lo, hi = spec["close_share"]
    closed = int(round(rng.uniform(lo, hi) * width))
    order = list(range(width))
    rng.shuffle(order)
    to_close = order[:closed]
    only_started = [a for a in order[closed:] if rng.random() < 0.5]
    # a seeded interleaving: an activity's first token starts it, its
    # second closes it
    tokens = to_close * 2 + only_started
    rng.shuffle(tokens)
    started = {}
    closes = 0
    every_lo, every_hi = spec["decision_every"]
    next_decision = rng.randint(every_lo, every_hi)
    for a in tokens:
        if a not in started:
            started[a] = ids.next()
            out.append([F.activity_task_started(
                started[a], v, ids.tick(), scheduled_event_id=sched[a])])
            continue
        if rng.random() < spec["completed_share"]:
            out.append([F.activity_task_completed(
                ids.next(), v, ids.tick(), scheduled_event_id=sched[a],
                started_event_id=started[a])])
        else:
            out.append([F.activity_task_failed(
                ids.next(), v, ids.tick(), scheduled_event_id=sched[a],
                started_event_id=started[a], reason="failed")])
        closes += 1
        if closes == next_decision:
            # fan-in: a decision with nothing to schedule yet
            out += _decision(ids, v)
            closes = 0
            next_decision = rng.randint(every_lo, every_hi)
    return out


def make_history(spec, caps, seed: int, index: int, depth: int) -> Batches:
    """The history ``index`` of a ``fanout`` configuration, drawn from
    ``(seed, index)``, with a wave of ``depth`` activities."""
    if not spec["min_width"] <= depth <= spec["max_width"]:
        raise ValueError(f"wave width {depth} outside "
                         f"[{spec['min_width']}, {spec['max_width']}]")
    return fanout_history(random.Random(f"{seed}:{index}"), depth, spec)

"""Persistent megabatch serving engine: device-resident hot state with
O(Δ) replay-on-append.

At millions-of-users scale the dominant operation is "N new events
arrived on a live workflow", not "rebuild 1k events from zero" — yet
every rebuild path replays from a checkpoint or from scratch per
request. This engine keeps hot workflows' state rows RESIDENT in a
fixed-shape [S]-lane state tensor and converts each append into an
O(Δ) suffix composition:

* ``admit()`` seats a workflow into a free lane by rehydrating its
  ``ReplayCheckpoint`` (suffix-only resume through the packer's
  ResumeState seam) or cold-replaying the prefix through the existing
  double-buffered dispatcher (``ops.dispatch.replay_stream``);
* ``append()`` stages just the Δ suffix against the workflow's lane;
* ``tick()`` runs ONE fused device step replaying every pending suffix
  from its lane's row as one lane-packed batch
  (``ops.replay.replay_packed_lanes``);
* ``read()`` answers decision/query requests straight from the
  resident row — no replay, no history read;
* eviction (LRU-idle + on-close) flushes a lane's row back through
  ``CheckpointManager.flush`` and refills the slot from the admission
  queue — the finished-chain/slot-refill discipline of vectorized-MCMC
  continuous batching.

Correctness invariants (tests/test_serving.py):

* **differential**: resident state after K appends is byte-identical
  to a cold ``rebuild_many``/``replay_packed`` of the full history —
  for appended Δs, recycle-then-readmit, and checkpoint-resume seeding;
* **generation stamp**: every lane slot carries a generation bumped on
  recycle; a stale in-flight append (ticket from a previous tenancy)
  can never land on a recycled slot;
* **compiled-shape discipline**: every tick/seat shape comes off the
  shared ``ops.grid`` policy, so the serving tick and the storm
  rebuild path cannot drift on executable selection.

Concurrency discipline (the sanitizer gates): the single engine lock is
constructed via ``utils/locks.make_lock``, the hot shared containers
are declared via ``make_guarded`` + ``testing/race_witness.
GUARDED_FIELDS``, and NOTHING blocking runs under the lock — packing,
device steps, checkpoint flushes, and metric emissions all happen
outside it (lane state is snapshotted/committed under the lock in
plain-python critical sections).
"""

from __future__ import annotations

import dataclasses
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple

from cadence_tpu.core.events import HistoryEvent
from cadence_tpu.ops import schema as S
from cadence_tpu.ops.grid import round_scan_len
from cadence_tpu.ops.pack import ResumeState, pack_lanes
from cadence_tpu.serving.admission import (
    AdmissionPolicy,
    FairAdmissionQueue,
)
from cadence_tpu.utils import locks
from cadence_tpu.utils.log import get_logger
from cadence_tpu.utils.metrics import NOOP, Scope

Batches = Sequence[Sequence[HistoryEvent]]


@dataclasses.dataclass(frozen=True)
class LaneTicket:
    """A seat handle: (slot, generation) at seat time. The generation
    is the stale-append guard — a ticket outlives its tenancy only as a
    rejected append, never as a write onto a recycled slot."""

    workflow_id: str
    run_id: str
    lane: int
    generation: int


@dataclasses.dataclass
class ResidentRead:
    """One resident-row read: the canonical snapshot plus everything
    needed to rehydrate a full MutableState lazily."""

    snapshot: Dict
    side: object
    epoch_s: int
    domain_id: str
    resident: bool
    state_row: Dict
    branch_token: bytes = b""

    def mutable_state(self):
        from cadence_tpu.ops.unpack import state_row_to_mutable_state

        one = S.empty_state(1, _caps_of_row(self.state_row))
        S.set_state_row(one, 0, self.state_row)
        return state_row_to_mutable_state(
            one, 0, self.side, domain_id=self.domain_id,
            epoch_s=self.epoch_s,
        )


def _caps_of_row(row: Dict) -> S.Capacities:
    return S.Capacities(
        max_events=1,  # not represented in a state row
        max_activities=row["activities"].shape[0],
        max_timers=row["timers"].shape[0],
        max_children=row["children"].shape[0],
        max_request_cancels=row["cancels"].shape[0],
        max_signals_ext=row["signals"].shape[0],
        max_version_items=row["vh_items"].shape[0],
    )


@dataclasses.dataclass
class _Lane:
    """One seated workflow's lane bookkeeping (the resident state row
    itself lives in the engine's [S] StateTensors at this slot)."""

    domain_id: str
    workflow_id: str
    run_id: str
    branch_token: bytes
    side: object                 # WorkflowSideTable; .resume at the tip
    epoch_s: int
    generation: int
    last_used: int               # tick number
    seated: bool = False         # False while the seat replay is in flight
    closed: bool = False
    pending: List[List[HistoryEvent]] = dataclasses.field(
        default_factory=list
    )
    pending_events: int = 0
    # staged tip: the next event id NOT yet staged into this lane —
    # committed row tip + every pending Δ. The append-idempotence
    # watermark: a duplicate/overlapping batch is dropped here
    next_staged: int = 0
    # persist feed high-water mark (``on_persisted``): history has
    # advanced to this next_event_id; the next tick fetches the
    # [next_staged, behind_through) suffix — O(Δ) — and stages it
    behind_through: int = 0
    # wall time the lane FIRST went dirty (staged Δ or persist debt)
    # since its last compose — the ``serving_staleness_ms`` input the
    # tick pump's bounded-staleness contract is asserted against
    dirty_since: float = 0.0

    @property
    def key(self) -> Tuple[str, str]:
        return (self.workflow_id, self.run_id)


@dataclasses.dataclass
class _Admission:
    domain_id: str
    workflow_id: str
    run_id: str
    branch_token: bytes
    batches: List
    resume: Optional[ResumeState]

    @property
    def key(self) -> Tuple[str, str]:
        return (self.workflow_id, self.run_id)


class ResidentEngine:
    """Fixed-S-lane resident serving megabatch (module docstring)."""

    def __init__(
        self,
        lanes: int = 64,
        caps: Optional[S.Capacities] = None,
        checkpoints=None,
        history=None,
        metrics: Optional[Scope] = None,
        idle_ticks: int = 256,
        admission: Optional[AdmissionPolicy] = None,
        tick_interval_s: float = 0.0,
    ) -> None:
        if lanes < 1:
            raise ValueError("serving: lanes must be >= 1")
        if idle_ticks < 1:
            raise ValueError("serving: idle_ticks must be >= 1")
        self.caps = caps or S.Capacities()
        self.lanes = int(lanes)
        # checkpoint.CheckpointManager: eviction flush target + the
        # resume source for admits; None = cold admits, flush-less
        # evictions (the history store stays the source of truth)
        self.checkpoints = checkpoints
        # persistence HistoryManager for admit_from_store / read-through
        self.history = history
        self.idle_ticks = int(idle_ticks)
        self._metrics = (
            metrics if metrics is not None else NOOP
        ).tagged(layer="serving")
        self._log = get_logger("cadence_tpu.serving")
        # -- guarded state (everything below is touched ONLY under
        # _lock; blocking work never runs while it is held) -----------
        self._lock = locks.make_lock("ResidentEngine._lock")
        # tick serialization: the snapshot → compose → commit cycle of
        # one tick must be atomic w.r.t. other ticks, or two concurrent
        # ticks could compose disjoint pending Δs from the SAME base
        # row snapshot and the later commit would silently discard the
        # earlier Δ. Strict order: _tick_lock is taken first, _lock
        # only inside it (no path holds _lock while acquiring this)
        self._tick_lock = locks.make_lock("ResidentEngine._tick_lock")
        self._slots = locks.make_guarded(
            [None] * self.lanes, "ResidentEngine._slots", self._lock
        )
        self._by_key = locks.make_guarded(
            {}, "ResidentEngine._by_key", self._lock
        )
        # fair admission (serving/admission.py): weighted + deadline-
        # aged + per-domain-quota'd refill, replacing the PR 14 FIFO
        # list; the queue's parked table is guarded by THIS engine lock
        self._admit_queue = FairAdmissionQueue(admission, self._lock)
        # the tick pump's cadence (serving/pump.py; 0 = no pump): the
        # engine just carries the configured value for whoever owns the
        # pump thread (HistoryService.start)
        self.tick_interval_s = float(tick_interval_s)
        self._slot_gen = [0] * self.lanes
        self._tick_no = 0
        # the resident store: one [S]-row StateTensors, rows scattered
        # in place under the lock (device-resident on TPU deployments;
        # host numpy on the CPU backend — same O(Δ) discipline)
        self._state = S.empty_state(self.lanes, self.caps)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def admit(
        self,
        domain_id: str,
        workflow_id: str,
        run_id: str,
        branch_token: bytes = b"",
        batches: Optional[Batches] = None,
        checkpoint=None,
    ) -> Optional[LaneTicket]:
        """Seat one workflow; returns its ticket, or None when every
        lane is occupied (the admission queued for the next recycle).

        ``batches`` is the FULL history prefix (cold admit). With a
        ``checkpoint`` (ReplayCheckpoint) the engine seats from the
        snapshot and ``batches`` — when given — is filtered down to the
        suffix past it; with a CheckpointManager attached, admits
        consult the store the same way ``rebuild_many`` does."""
        out = self.admit_many([
            dict(domain_id=domain_id, workflow_id=workflow_id,
                 run_id=run_id, branch_token=branch_token,
                 batches=batches, checkpoint=checkpoint)
        ])
        return out.get((workflow_id, run_id))

    def admit_from_store(
        self, domain_id: str, workflow_id: str, run_id: str,
        branch_token: bytes,
    ) -> Optional[LaneTicket]:
        """Production admission: full history from the attached history
        manager (checkpoint consult inside ``admit`` trims it to the
        suffix when a snapshot resumes)."""
        if self.history is None:
            raise RuntimeError("admit_from_store needs a history manager")
        return self.admit(
            domain_id, workflow_id, run_id, branch_token=branch_token,
            batches=self._read_batches(branch_token),
        )

    def admit_many(self, requests: Sequence[Dict], _requeued=None) -> Dict:
        """Bulk admission; returns {(workflow_id, run_id): ticket|None}.

        Free lanes are reserved under the lock, then every seat replay
        runs as ONE batch through the existing dispatcher
        (``replay_stream`` — pack overlap, depth bucketing, grid
        shapes), and the rows commit back under the lock. ``_requeued``
        (internal, the refill path): key → the original parked entry,
        so an admission that fails to seat re-parks at its ORIGINAL
        age — re-queueing must never reset the starvation clock."""
        admissions = [self._prepare_admission(r) for r in requests]
        out: Dict = {}
        seat: List[Tuple[int, int, _Admission]] = []
        queued = resumed = cold = 0
        with self._lock:
            for adm in admissions:
                slot = self._by_key.get(adm.key)
                if slot is not None:
                    lane = self._slots[slot]
                    lane.last_used = self._tick_no
                    out[adm.key] = LaneTicket(
                        adm.workflow_id, adm.run_id, slot,
                        lane.generation,
                    )
                    continue
                free = self._free_slot()
                if free is None:
                    self._admit_queue.park(
                        adm,
                        requeued_from=(_requeued or {}).get(adm.key),
                    )
                    queued += 1
                    out[adm.key] = None
                    continue
                gen = self._slot_gen[free]
                lane = _Lane(
                    domain_id=adm.domain_id,
                    workflow_id=adm.workflow_id, run_id=adm.run_id,
                    branch_token=adm.branch_token, side=None,
                    epoch_s=0, generation=gen,
                    last_used=self._tick_no, seated=False,
                )
                self._slots[free] = lane
                self._by_key[adm.key] = free
                seat.append((free, gen, adm))
                if adm.resume is not None:
                    resumed += 1
                else:
                    cold += 1
        if seat:
            seated = self._seat(seat)
            out.update(seated)
        scope = self._metrics
        if queued:
            scope.inc("serving_admit_queued", queued)
        if resumed:
            scope.inc("serving_admit_resume", resumed)
        if cold:
            scope.inc("serving_admit_cold", cold)
        return out

    def _prepare_admission(self, r: Dict) -> _Admission:
        """Resolve one admit request's seeding (checkpoint consult +
        suffix trim) — store I/O, so it runs before the lock."""
        batches = list(r.get("batches") or [])
        ckpt = r.get("checkpoint")
        branch_token = r.get("branch_token") or b""
        if ckpt is None and self.checkpoints is not None and branch_token:
            try:
                from cadence_tpu.checkpoint.manager import HIT

                cand, status = self.checkpoints.lookup(
                    branch_token, caps=self.caps
                )
                if status == HIT:
                    ckpt = cand
            except Exception:
                ckpt = None
        resume = None
        if ckpt is not None:
            suffix = [
                b for b in batches if b and b[0].event_id > ckpt.event_id
            ]
            straddles = any(
                b and b[0].event_id <= ckpt.event_id < b[-1].event_id
                for b in batches
            )
            if not straddles:
                try:
                    resume = ckpt.resume_state()
                    batches = suffix
                except Exception:
                    resume = None  # corrupt snapshot: cold admit
        return _Admission(
            domain_id=r.get("domain_id", ""),
            workflow_id=r["workflow_id"], run_id=r["run_id"],
            branch_token=branch_token, batches=batches, resume=resume,
        )

    def _seat(self, seat: List[Tuple[int, int, _Admission]]) -> Dict:
        """Replay the reserved admissions (outside the lock) and commit
        the rows; per-admission fallback isolates one bad history."""
        from cadence_tpu.ops.dispatch import replay_stream

        histories = [
            (adm.workflow_id, adm.run_id, adm.batches)
            for _, _, adm in seat
        ]
        resumes = [adm.resume for _, _, adm in seat]
        out: Dict = {}
        failures = 0
        try:
            results = replay_stream(
                histories, caps=self.caps, lane_pack=True,
                resume=resumes,
            )
            rows: List[Optional[Tuple]] = []
            for packed, final in results:
                rows.extend(
                    (packed, final, j)
                    for j in range(packed.n_histories)
                )
        except Exception:
            # group poisoned (one malformed history fails the strict
            # stream): seat individually, drop only the bad ones
            rows = []
            for hist, rs in zip(histories, resumes):
                try:
                    packed = pack_lanes(
                        [hist], caps=self.caps, resume=[rs]
                    )
                    final = self._replay(packed)
                    rows.append((packed, final, 0))
                except Exception:
                    rows.append(None)
        admitted = 0
        with self._lock:
            for (slot, gen, adm), row in zip(seat, rows):
                if row is None:
                    failures += 1
                    # release ONLY our own reservation: the slot may
                    # have been recycled + re-seated while the replay
                    # ran (drain/evict bump the generation)
                    if self._slot_gen[slot] == gen:
                        self._release_slot(slot, adm.key)
                    out[adm.key] = None
                    continue
                packed, final, j = row
                if self._slot_gen[slot] != gen:
                    failures += 1  # recycled mid-seat (drain/shutdown)
                    out[adm.key] = None
                    continue
                lane = self._slots[slot]
                self._commit_row(slot, lane, packed, final, j)
                lane.seated = True
                admitted += 1
                out[adm.key] = LaneTicket(
                    adm.workflow_id, adm.run_id, slot, gen
                )
        if admitted:
            self._metrics.inc("serving_admits", admitted)
        if failures:
            self._metrics.inc("serving_admit_failures", failures)
        return out

    def _free_slot(self) -> Optional[int]:
        for i in range(self.lanes):
            if self._slots[i] is None:
                return i
        return None

    def _release_slot(self, slot: int, key) -> None:
        self._slot_gen[slot] += 1
        self._slots[slot] = None
        if self._by_key.get(key) == slot:
            del self._by_key[key]

    def _commit_row(self, slot, lane, packed, final, j) -> None:
        """Install one replay-result row into its lane (under _lock)."""
        row = S.state_row(final, j)
        S.set_state_row(self._state, slot, row)
        lane.side = packed.side[j]
        lane.epoch_s = packed.epoch_s
        lane.closed = bool(row["exec_info"][S.X_CLOSE_STATUS] != 0)
        lane.last_used = self._tick_no
        lane.next_staged = max(
            lane.next_staged, int(row["exec_info"][S.X_NEXT_EVENT_ID])
        )

    # ------------------------------------------------------------------
    # append + the fused tick
    # ------------------------------------------------------------------

    def append(self, ticket, batches: Batches) -> bool:
        """Stage a Δ suffix against a seated lane.

        ``ticket``: a LaneTicket (generation-checked — the stale-append
        guard) or a (workflow_id, run_id) key. Returns False (and
        counts ``serving_stale_appends``) when the ticket's tenancy is
        gone; the caller re-admits and retries. At-least-once feeds
        (the persist catch-up and an explicit append may overlap, with
        arbitrary re-chunking): events at or below the staged tip are
        trimmed, a batch that STRADDLES the tip keeps its unseen tail.
        A batch past the tip (a GAP — events between the tip and the
        batch never arrived here) is never composed over: lanes with a
        history feed record the debt and the next tick's catch-up
        fetches the whole span; bare lanes refuse the append (False,
        ``serving_gapped_appends``) so divergent state can never be
        served as resident truth — the caller evicts/re-admits."""
        batches = [list(b) for b in batches if b]
        stale = gapped = False
        n_events = 0
        with self._lock:
            lane = self._resolve_lane(ticket)
            if lane is None:
                stale = True
            else:
                for b in batches:
                    if b[0].event_id < lane.next_staged:
                        b = [
                            e for e in b
                            if e.event_id >= lane.next_staged
                        ]
                        if not b:
                            continue  # duplicate delivery, whole
                    if b[0].event_id > lane.next_staged:
                        if self.history is not None and lane.branch_token:
                            lane.behind_through = max(
                                lane.behind_through,
                                b[-1].event_id + 1,
                            )
                            if not lane.dirty_since:
                                lane.dirty_since = _time.monotonic()
                            continue
                        gapped = True
                        break
                    lane.pending.append(b)
                    lane.pending_events += len(b)
                    n_events += len(b)
                    lane.next_staged = b[-1].event_id + 1
                    if not lane.dirty_since:
                        lane.dirty_since = _time.monotonic()
        if stale:
            self._metrics.inc("serving_stale_appends")
            return False
        if gapped:
            self._metrics.inc("serving_gapped_appends")
            return False
        self._metrics.inc("serving_appends")
        if n_events:
            self._metrics.inc("serving_append_events", n_events)
        return True

    def on_persisted(
        self, domain_id: str, workflow_id: str, run_id: str,
        next_event_id: int, running: bool = True,
    ) -> None:
        """The persist-path feed (HistoryEngine fires this after every
        durable write): O(1) — records that the workflow's history
        advanced to ``next_event_id``. The NEXT tick fetches just the
        [staged_tip, next_event_id) suffix from the history manager and
        composes it — the O(Δ) append, without any I/O on the persist
        caller's thread. Unseated workflows are a dict miss (admission
        stays read-driven)."""
        with self._lock:
            slot = self._by_key.get((workflow_id, run_id))
            if slot is None:
                return
            lane = self._slots[slot]
            if lane is None:
                return
            # reserved-but-unseated lanes record the debt too: events
            # persisted during the seating window would otherwise be
            # dropped and the fresh lane would serve a stale tip until
            # the workflow's NEXT durable write (possibly never); the
            # post-seat catch-up heals the recorded span instead
            lane.behind_through = max(lane.behind_through, next_event_id)
            if not lane.dirty_since:
                lane.dirty_since = _time.monotonic()
            if not running:
                # close hint: once the debt composes (the close events
                # are in it), the committed row confirms and the
                # on-close eviction recycles the lane
                lane.closed = True

    def _catch_up(self) -> None:
        """Fetch + stage the persist-feed suffixes of behind lanes
        (tick phase 0). History reads run OUTSIDE the lock; a failed
        read leaves the lane behind — retried next tick."""
        if self.history is None:
            return
        fetch: List[Tuple[int, int, Tuple, bytes, int, int]] = []
        with self._lock:
            for slot in range(self.lanes):
                lane = self._slots[slot]
                if (lane is None or not lane.seated
                        or not lane.branch_token
                        or lane.behind_through <= lane.next_staged):
                    continue
                fetch.append((
                    slot, lane.generation, lane.key, lane.branch_token,
                    lane.next_staged, lane.behind_through,
                ))
        for slot, gen, key, token, lo, hi in fetch:
            try:
                batches = self._read_batches(
                    token, min_event_id=lo, max_event_id=hi
                )
                first = next((b for b in batches if b), None)
                if first is None or first[0].event_id > lo:
                    # the node containing ``lo`` starts below it (the
                    # store pages by node id, and an explicit append's
                    # re-chunking can leave the tip mid-node): refetch
                    # from the start; the staging trim below drops the
                    # already-staged prefix
                    batches = self._read_batches(
                        token, max_event_id=hi
                    )
            except Exception:
                continue  # still behind; next tick retries
            released = 0
            with self._lock:
                if self._slot_gen[slot] != gen:
                    continue  # recycled mid-fetch: never lands
                lane = self._slots[slot]
                if lane is None or lane.key != key:
                    continue
                for b in batches:
                    if not b:
                        continue
                    if b[0].event_id < lane.next_staged:
                        b = [
                            e for e in b
                            if e.event_id >= lane.next_staged
                        ]
                        if not b:
                            continue
                    if b[0].event_id > lane.next_staged:
                        # even the start-of-branch refetch cannot
                        # provide [next_staged, b[0]) — the span is
                        # gone from the store (pruned/torn history).
                        # The lane can never heal: composing over the
                        # hole would serve divergent state as resident
                        # truth, so free it — readmit-from-store
                        # recovers whatever the store still has
                        self._release_slot(slot, lane.key)
                        released = 1
                        break
                    lane.pending.append(list(b))
                    lane.pending_events += len(b)
                    lane.next_staged = b[-1].event_id + 1
                if not released and (
                    lane.behind_through <= lane.next_staged
                ):
                    lane.behind_through = 0
            if released:
                self._metrics.inc("serving_compose_failures")

    def _resolve_lane(self, ticket) -> Optional[_Lane]:
        """Under _lock: the live lane a ticket/key addresses, or None.
        Tickets check slot + generation — the recycled-slot guard."""
        if isinstance(ticket, LaneTicket):
            if not 0 <= ticket.lane < self.lanes:
                return None
            if self._slot_gen[ticket.lane] != ticket.generation:
                return None
            lane = self._slots[ticket.lane]
            return lane if lane is not None and lane.seated else None
        slot = self._by_key.get(tuple(ticket))
        if slot is None:
            return None
        lane = self._slots[slot]
        return lane if lane is not None and lane.seated else None

    def tick(self) -> Dict:
        """One serving tick: ONE fused device step composes every
        pending Δ against its lane, then eviction/recycle and admission refill. Returns tick
        stats. Ticks SERIALIZE (``_tick_lock``): concurrent callers
        (every dirty read composes-first) queue behind the running
        tick instead of racing its base-row snapshots; Δs staged while
        a tick composes stay pending and ride the next one."""
        with self._tick_lock:
            return self._tick_locked()

    def _tick_locked(self) -> Dict:
        t0 = _time.perf_counter()
        self._catch_up()
        work: List[Tuple[int, int, _Lane, List, ResumeState]] = []
        with self._lock:
            self._tick_no += 1
            tick_no = self._tick_no
            for slot in range(self.lanes):
                lane = self._slots[slot]
                if lane is None or not lane.seated or not lane.pending:
                    continue
                rs = ResumeState(
                    pack=lane.side.resume, side=lane.side,
                    state_row=S.state_row(self._state, slot),
                )
                work.append(
                    (slot, lane.generation, lane, lane.pending, rs)
                )
                lane.pending = []
                lane.pending_events = 0
                lane.last_used = tick_no
        composed, replayed, failures, stale = self._compose(work)
        evicted, recycled, flush_failed = self._evict_and_refill(tick_no)
        dt = _time.perf_counter() - t0
        scope = self._metrics
        scope.inc("serving_ticks")
        scope.record("serving_tick_seconds", dt)
        if composed:
            # batches counted per grid-rounded width, like the
            # dispatcher's batch_width (bounded tag cardinality)
            scope.tagged(width=str(round_scan_len(composed))).inc(
                "serving_append_width"
            )
        if replayed:
            scope.inc("serving_events_replayed", replayed)
        if stale:
            scope.inc("serving_stale_appends", stale)
        if failures:
            scope.inc("serving_compose_failures", failures)
        if evicted:
            scope.inc("serving_evictions", evicted)
        if recycled:
            scope.inc("serving_recycles", recycled)
        if flush_failed:
            scope.inc("serving_flush_failures", flush_failed)
        scope.gauge("serving_lane_occupancy", self.occupancy())
        return {
            "tick": tick_no, "composed": composed,
            "events_replayed": replayed, "evicted": evicted,
            "recycled": recycled, "tick_seconds": dt,
        }

    def _replay(self, packed):
        from cadence_tpu.ops.replay import replay_packed_lanes

        # unspecialized: the event types of a tick's Δs change from tick
        # to tick, and each new type signature would compile the scan
        # again, a stall the next reads wait out; one executable a shape
        return replay_packed_lanes(packed, specialize=False)

    def _compose(self, work) -> Tuple[int, int, int, int]:
        """The fused step: pack every pending lane's Δ, run them as one
        device batch, commit rows under the lock."""
        if not work:
            return 0, 0, 0, 0
        composed = replayed = failures = stale = 0
        staleness_ms: List[float] = []
        histories = [
            (lane.workflow_id, lane.run_id, batches)
            for _, _, lane, batches, _ in work
        ]
        resumes = [rs for *_, rs in work]
        results: List[Optional[Tuple]] = []
        try:
            packed = pack_lanes(
                histories, caps=self.caps, resume=resumes
            )
            final = self._replay(packed)
            results = [(packed, final, j) for j in range(len(work))]
        except Exception:
            # one malformed Δ must not poison the whole tick:
            # degrade to per-lane composition, fail only the bad one
            for hist, rs in zip(histories, resumes):
                try:
                    pk = pack_lanes(
                        [hist], caps=self.caps, resume=[rs]
                    )
                    results.append(
                        (pk, self._replay(pk), 0)
                    )
                except Exception:
                    results.append(None)
        with self._lock:
            for (slot, gen, lane, batches, _), row in zip(
                work, results
            ):
                if row is None:
                    # the Δ is unreplayable: free the lane; the
                    # history store remains the source of truth and
                    # a readmit-from-store recovers the workflow.
                    # Generation-checked like the commit branch — a
                    # slot recycled + re-seated mid-step must not
                    # be clobbered (its tenant's _by_key entry
                    # would dangle onto the next occupant)
                    failures += 1
                    if (self._slot_gen[slot] == gen
                            and self._slots[slot] is lane):
                        self._release_slot(slot, lane.key)
                    continue
                if (self._slot_gen[slot] != gen
                        or self._slots[slot] is not lane):
                    stale += 1  # recycled mid-step: never lands
                    continue
                packed, final, j = row
                self._commit_row(slot, lane, packed, final, j)
                composed += 1
                replayed += sum(len(b) for b in batches)
                if lane.dirty_since:
                    # staleness: first-dirty → composed. Reset to
                    # "now" (not 0) when Δs staged mid-compose —
                    # their clock started while this step ran
                    now = _time.monotonic()
                    staleness_ms.append(
                        (now - lane.dirty_since) * 1e3
                    )
                    lane.dirty_since = now if (
                        lane.pending
                        or lane.behind_through > lane.next_staged
                    ) else 0.0
        for ms in staleness_ms:
            self._metrics.record("serving_staleness_ms", ms)
        return composed, replayed, failures, stale

    # ------------------------------------------------------------------
    # eviction / recycle
    # ------------------------------------------------------------------

    def _evict_and_refill(self, tick_no: int) -> Tuple[int, int, int]:
        """LRU-idle + on-close eviction, then admission-queue refill.
        Slots are freed (generation bumped) UNDER the lock; the flush
        itself — store I/O — runs after release."""
        flush: List[Tuple[_Lane, Dict]] = []
        with self._lock:
            for slot in range(self.lanes):
                lane = self._slots[slot]
                if (lane is None or not lane.seated or lane.pending
                        or lane.behind_through > lane.next_staged):
                    continue  # dirty lanes compose before they evict
                idle = tick_no - lane.last_used
                if not lane.closed and idle < self.idle_ticks:
                    continue
                flush.append((lane, S.state_row(self._state, slot)))
                self._release_slot(slot, lane.key)
        flush_failed = 0
        for lane, row in flush:
            if not self._flush_row(lane, row):
                flush_failed += 1
        recycled = 0
        # refill whenever a free slot exists — slots freed by seat/
        # compose failures or an explicit evict() (not just this tick's
        # evictions) must not starve parked admissions. The refill
        # order is the fair scheduler's (weighted + deadline-aged +
        # per-domain quotas): only as many admissions as there are free
        # slots are taken, and a take that fails to seat re-parks at
        # its original age
        with self._lock:
            n_free = sum(1 for s in self._slots if s is None)
            backlog = (
                self._admit_queue.take(n_free) if n_free else []
            )
            ages_ms = [
                self._admit_queue.parked_age_s(e) * 1e3 for e in backlog
            ]
        if backlog:
            # store reads + the bulk admission run OUTSIDE the lock
            reqs = []
            for entry in backlog:
                a = entry.adm
                batches = a.batches
                if self.history is not None and a.branch_token:
                    try:
                        # queue-time batches go stale while the
                        # admission waits (on_persisted is a dict
                        # miss for unseated workflows) — re-read
                        # the tip so a refilled lane never serves
                        # a stale row as resident truth
                        batches = self._read_batches(a.branch_token)
                    except Exception:
                        pass  # queue-time prefix: still consistent
                reqs.append(dict(
                    domain_id=a.domain_id,
                    workflow_id=a.workflow_id, run_id=a.run_id,
                    branch_token=a.branch_token, batches=batches,
                ))
            readmitted = self.admit_many(
                reqs,
                _requeued={e.adm.key: e for e in backlog},
            )
            recycled = sum(
                1 for t in readmitted.values() if t is not None
            )
            # a taken admission whose SEAT REPLAY failed was dropped by
            # admit_many (only the no-free-slot branch re-parks): put
            # it back at its original age so a transient fault storm
            # cannot eat a parked admission's starvation guarantee —
            # bounded attempts so a genuinely poisoned history drops
            # after 3 tries (readmit-from-read stays its recovery path)
            with self._lock:
                for entry in backlog:
                    if (readmitted.get(entry.adm.key) is None
                            and entry.attempts < 3
                            and not self._admit_queue.has_key(
                                entry.adm.key)):
                        self._admit_queue.park(
                            entry.adm, requeued_from=entry
                        )
            # the parked-age distribution at seat time: the starvation
            # observable TestOverloadChaos bounds (aging guarantees a
            # seat within K recycles for any weight assignment)
            for ms, entry in zip(ages_ms, backlog):
                if readmitted.get(entry.adm.key) is not None:
                    self._metrics.record(
                        "serving_admit_starvation_age_ms", ms
                    )
        return len(flush), recycled, flush_failed

    def _flush_row(self, lane: _Lane, row: Dict) -> bool:
        """Flush one evicted lane's row back through the checkpoint
        plane (policy-free write). True when durable — False counts as
        a flush failure but is never fatal: the history store is still
        the source of truth and a readmit cold-replays."""
        if self.checkpoints is None or not lane.branch_token:
            return True
        one = S.empty_state(1, self.caps)
        S.set_state_row(one, 0, row)
        return self.checkpoints.flush(
            lane.branch_token, one, 0, lane.side, epoch_s=lane.epoch_s,
            caps=self.caps, domain_id=lane.domain_id,
            workflow_id=lane.workflow_id, run_id=lane.run_id,
        )

    # ------------------------------------------------------------------
    # reads
    # ------------------------------------------------------------------

    def read(
        self,
        workflow_id: str,
        run_id: str,
        domain_id: str = "",
        branch_token: Optional[bytes] = None,
    ) -> Optional[ResidentRead]:
        """Answer a decision/query read.

        Resident lanes answer straight from the row — no replay, no
        history read. A lane with staged Δs composes first (one tick)
        so reads always reflect acknowledged appends. A miss falls
        through to a cold single-history rebuild when the engine has a
        history manager and the caller names the branch (and counts as
        ``serving_cold_misses``); otherwise None."""
        t0 = _time.perf_counter()
        scope = self._metrics
        out = self.resident_row(workflow_id, run_id, domain_id=domain_id)
        if out is not None:
            scope.inc("serving_resident_hits")
            scope.record(
                "serving_read_seconds", _time.perf_counter() - t0
            )
            return out
        scope.inc("serving_cold_misses")
        return self._cold_read(
            workflow_id, run_id, domain_id, branch_token, t0
        )

    def _cold_read(
        self, workflow_id: str, run_id: str, domain_id: str,
        branch_token: Optional[bytes], t0: float,
    ) -> Optional[ResidentRead]:
        """One-shot cold replay of the full history — the miss path
        shared by ``read`` and ``read_through`` (no lane is touched).
        A history the serving caps cannot pack (capacity overflow /
        malformed stream) returns None — counted
        ``serving_cold_read_failures``, never an exception out of the
        read verb; the rebuild verbs stay the recovery path."""
        from cadence_tpu.ops.unpack import state_row_to_snapshot

        if self.history is None or not branch_token:
            self._metrics.record(
                "serving_read_seconds", _time.perf_counter() - t0
            )
            return None
        try:
            batches = self._read_batches(branch_token)
            packed = pack_lanes(
                [(workflow_id, run_id, batches)], caps=self.caps
            )
            final = self._replay(packed)
        except Exception as e:
            self._log.warn(f"serving cold read failed ({e}); miss")
            self._metrics.inc("serving_cold_read_failures")
            self._metrics.record(
                "serving_read_seconds", _time.perf_counter() - t0
            )
            return None
        row = S.state_row(final, 0)
        one = S.empty_state(1, self.caps)
        S.set_state_row(one, 0, row)
        out = ResidentRead(
            snapshot=state_row_to_snapshot(one, 0, packed.epoch_s),
            side=packed.side[0], epoch_s=packed.epoch_s,
            domain_id=domain_id, resident=False, state_row=row,
            branch_token=branch_token or b"",
        )
        self._metrics.record(
            "serving_read_seconds", _time.perf_counter() - t0
        )
        return out

    def resident_row(
        self, workflow_id: str, run_id: str, domain_id: str = "",
    ) -> Optional[ResidentRead]:
        """The resident view of one seated lane, or None — NO cold
        fallback and no hit/miss accounting (``read`` adds both; the
        rebuilder's serving consult counts its own hits). A dirty lane
        (staged Δs or a persist-feed debt) composes first so the row
        always reflects acknowledged appends."""
        from cadence_tpu.ops.unpack import state_row_to_snapshot

        key = (workflow_id, run_id)
        got = None
        for _ in range(4):
            dirty = False
            with self._lock:
                slot = self._by_key.get(key)
                if slot is not None:
                    lane = self._slots[slot]
                    if lane is not None and lane.seated:
                        if (lane.pending
                                or lane.behind_through > lane.next_staged):
                            dirty = True
                        else:
                            lane.last_used = self._tick_no
                            got = (
                                S.state_row(self._state, slot),
                                lane.side, lane.epoch_s,
                                lane.domain_id, lane.branch_token,
                            )
            if got is not None or not dirty:
                break
            self.tick()
        if got is None:
            return None
        row, side, epoch_s, dom, token = got
        one = S.empty_state(1, self.caps)
        S.set_state_row(one, 0, row)
        return ResidentRead(
            snapshot=state_row_to_snapshot(one, 0, epoch_s),
            side=side, epoch_s=epoch_s,
            domain_id=domain_id or dom, resident=True,
            state_row=row, branch_token=token,
        )

    def read_through(
        self, domain_id: str, workflow_id: str, run_id: str,
        branch_token: bytes,
    ) -> Optional[ResidentRead]:
        """The serving-plane read verb: resident hit, else ADMIT the
        workflow (full-history seat through the dispatcher, suffix-only
        when a checkpoint resumes) and answer from the fresh lane —
        the next read is resident. Falls back to a one-shot cold replay
        when every lane is occupied (the admission queued)."""
        t0 = _time.perf_counter()
        got = self.resident_row(workflow_id, run_id, domain_id=domain_id)
        scope = self._metrics
        if got is not None:
            scope.inc("serving_resident_hits")
            scope.record(
                "serving_read_seconds", _time.perf_counter() - t0
            )
            return got
        scope.inc("serving_cold_misses")
        try:
            batches = self._read_batches(branch_token)
        except Exception:
            batches = None  # unreadable branch: the cold path misses
        ticket = None
        if batches is not None:
            ticket = self.admit(
                domain_id, workflow_id, run_id,
                branch_token=branch_token, batches=batches,
            )
        if ticket is not None:
            got = self.resident_row(
                workflow_id, run_id, domain_id=domain_id
            )
        if got is not None:
            scope.record(
                "serving_read_seconds", _time.perf_counter() - t0
            )
            return got
        return self._cold_read(
            workflow_id, run_id, domain_id, branch_token, t0
        )

    def _read_batches(
        self, branch_token: bytes, min_event_id: int = 1,
        max_event_id: int = 1 << 60,
    ) -> List:
        from cadence_tpu.runtime.persistence.records import BranchToken

        branch = BranchToken.from_json(
            branch_token.decode()
            if isinstance(branch_token, bytes) else str(branch_token)
        )
        out: List = []
        token = 0
        while True:
            batches, token = self.history.read_history_branch(
                branch, max(1, min_event_id), max_event_id,
                page_size=256, next_token=token,
            )
            out.extend(batches)
            if not token:
                return out

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------

    def evict(self, workflow_id: str, run_id: str) -> bool:
        """Explicit eviction (operator/test entry): compose pending,
        flush, free the lane."""
        key = (workflow_id, run_id)
        with self._lock:
            slot = self._by_key.get(key)
            lane = self._slots[slot] if slot is not None else None
            has_pending = lane is not None and bool(
                lane.pending
                or lane.behind_through > lane.next_staged
            )
        if slot is None:
            return False
        if has_pending:
            self.tick()
        flush = None
        with self._lock:
            slot = self._by_key.get(key)
            if slot is None:
                return False
            lane = self._slots[slot]
            flush = (lane, S.state_row(self._state, slot))
            self._release_slot(slot, key)
        ok = self._flush_row(*flush)
        self._metrics.inc("serving_evictions")
        if not ok:
            self._metrics.inc("serving_flush_failures")
        return True

    def drain(self) -> Dict:
        """Shutdown: compose everything pending, flush + free every
        lane. Returns {"flushed", "flush_failed", "queued_dropped"};
        clean means flush_failed == 0 and the engine is empty after."""
        # compose until quiescent (appends racing the drain get one
        # more tick; a live producer should be stopped first)
        for _ in range(8):
            with self._lock:
                dirty = any(
                    l is not None
                    and (l.pending or l.behind_through > l.next_staged)
                    for l in self._slots
                )
            if not dirty:
                break
            self.tick()
        flush: List[Tuple[_Lane, Dict]] = []
        with self._lock:
            for slot in range(self.lanes):
                lane = self._slots[slot]
                if lane is None:
                    continue
                flush.append((lane, S.state_row(self._state, slot)))
                self._release_slot(slot, lane.key)
            queued = self._admit_queue.drain()
        failed = 0
        for lane, row in flush:
            if not self._flush_row(lane, row):
                failed += 1
        if flush:
            self._metrics.inc("serving_evictions", len(flush))
        if failed:
            self._metrics.inc("serving_flush_failures", failed)
        return {
            "flushed": len(flush), "flush_failed": failed,
            "queued_dropped": queued,
        }

    def retune_admission(
        self, quota_rps: float, quota_burst=None
    ) -> None:
        """Live retune of the admission queue's per-domain quota (the
        capacity autopilot's serving-plane actuator)."""
        with self._lock:
            self._admit_queue.set_quota_rps(quota_rps, burst=quota_burst)

    def admission_quota_rps(self) -> float:
        with self._lock:
            return self._admit_queue.policy.quota_rps

    def occupancy(self) -> float:
        with self._lock:
            seated = sum(
                1 for l in self._slots if l is not None and l.seated
            )
        return seated / self.lanes

    def describe(self) -> Dict:
        with self._lock:
            seated = [
                {
                    "lane": i, "workflow_id": l.workflow_id,
                    "run_id": l.run_id, "generation": l.generation,
                    "pending_events": l.pending_events,
                    "closed": l.closed, "last_used": l.last_used,
                }
                for i, l in enumerate(self._slots)
                if l is not None
            ]
            queued = len(self._admit_queue)
            tick = self._tick_no
        return {
            "lanes": self.lanes, "seated": len(seated),
            "queued": queued, "tick": tick, "lanes_detail": seated,
        }

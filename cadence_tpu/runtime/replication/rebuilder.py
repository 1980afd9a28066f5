"""State rebuilder: (history branch) → fresh MutableState + tasks.

Reference: service/history/nDCStateRebuilder.go:92-160 — page through
ReadHistoryBranchByBatch, replay every batch through a fresh
stateBuilder, close as snapshot, refresh tasks.

TPU-native twist: ``rebuild_many`` is the batched path — it groups N
runs' histories by the slot-table widths they need and, at the default
widths, by depth (``ops.dispatch.buckets``: a fan-out parent with
hundreds of activities in flight gets a wide state of its own),
lane-packs each group and
replays it on device (the north-star replication-storm /
conflict-resolution-storm configuration). Only what the device cannot
hold at all — more than 1,024 events, a timestamp outside the packable
window, a slot table wider than the widest bucket — falls back,
per batch, to the host oracle.

Checkpointed incremental replay (cadence_tpu/checkpoint/): with a
``CheckpointManager`` attached, ``rebuild_many`` consults the store per
request, fetches only the event SUFFIX past the newest valid snapshot,
seeds the packed scan's per-segment carry from the snapshot row, and
writes fresh checkpoints from the rebuilt state — repeat rebuilds cost
O(new events) instead of O(depth). A checkpoint at the branch tip skips
the device entirely (rehydrate + task refresh). Any checkpoint-plane
failure degrades that request to a full replay.

Tracing: ``rebuild_many`` is a trace entry point (utils/tracing.py
``Tracer.entry``) — a child of the caller's span, else a root at the
tracer's sample rate — with spans on the caller's thread for the reads
(``rebuild.read``, tagged ``nodes`` and ``pages``: the batches read and
the store reads that fetched them), the width and depth grouping
(``dispatch.bucket``), each wait on the dispatcher (``rebuild.await``),
each device batch's one fetch of its final state to the host
(``rebuild.fetch``, tagged ``histories`` and ``bytes``), each row's
unpack from those host arrays (``rebuild.unpack``) and task refresh
(``rebuild.refresh``), and each host fallback (``rebuild.fallback``);
the dispatcher's pumps add theirs under the same trace.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

from cadence_tpu.core.events import HistoryEvent
from cadence_tpu.core.mutable_state import MutableState
from cadence_tpu.core.state_builder import StateBuilder
from cadence_tpu.core.task_refresher import refresh_tasks
from cadence_tpu.core.version_history import VersionHistories
from cadence_tpu.utils.metrics import NOOP
from cadence_tpu.utils.tracing import TRACER

from ..persistence.interfaces import HistoryManager
from ..persistence.records import BranchToken


class RebuildRequest:
    """One run to rebuild.

    ``version_history_items``: the target branch's (event_id, version)
    items when the caller knows them (the NDC conflict path does) —
    the checkpoint manager's divergence guard, and the key that lets a
    forked branch resume from a sibling's snapshot below the LCA.
    """

    def __init__(
        self,
        domain_id: str,
        workflow_id: str,
        run_id: str,
        branch_token: bytes,
        next_event_id: int = 0,
        request_id: str = "rebuild",
        version_history_items: Optional[Sequence[Tuple[int, int]]] = None,
    ) -> None:
        self.domain_id = domain_id
        self.workflow_id = workflow_id
        self.run_id = run_id
        self.branch_token = branch_token
        self.next_event_id = next_event_id
        self.request_id = request_id
        self.version_history_items = version_history_items


def _fits(batches, resume, caps) -> bool:
    """Does a suffix continuing ``resume`` stay inside ``caps``, the
    capacities its checkpoint row was recorded at?"""
    from cadence_tpu.ops.pack import (
        PackOverflowError, bucket_caps, slot_peaks,
    )

    try:
        return bucket_caps(slot_peaks(batches, resume.pack), caps) == caps
    except PackOverflowError:
        return False


class StateRebuilder:
    def __init__(self, history: HistoryManager,
                 domain_resolver=lambda name: name,
                 chunk_size=0, lane_len: int = 1024,
                 checkpoints=None, metrics=None, serving=None) -> None:
        self.history = history
        self.domain_resolver = domain_resolver
        # device-dispatch chunk for rebuild_many: an int, or a callable
        # re-read every resolve (dynamicconfig history.rebuildChunkSize
        # via bootstrap stays live-tunable); 0 = backend default
        self.chunk_size = chunk_size
        # lane capacity (events) for ragged lane packing in
        # rebuild_many: shallow histories pack back-to-back into lanes
        # of this length instead of each padding a lane to max(depth)
        self.lane_len = lane_len
        # checkpoint.CheckpointManager (or None: every rebuild is cold)
        self.checkpoints = checkpoints
        # serving.ResidentEngine (config `serving:` section, or None):
        # a rebuild whose target tip + branch + version histories match
        # a resident lane rehydrates from the row — no history read, no
        # replay (counted as serving_resident_hits)
        self.serving = serving
        # checkpoint_hit/miss/invalidated + events_replayed_saved land
        # here (utils/metrics_defs.py CHECKPOINT_METRICS); the raw scope
        # also feeds the dispatcher's device-step telemetry
        # (DEVICE_METRICS) — None disables both planes together
        self._raw_metrics = metrics
        self._metrics = (metrics if metrics is not None else NOOP).tagged(
            layer="checkpoint"
        )
        self._backend_chunk = 0

    def _resolve_chunk(self) -> int:
        configured = (
            self.chunk_size() if callable(self.chunk_size)
            else self.chunk_size
        )
        if configured and configured > 0:
            return int(configured)
        if self._backend_chunk:
            return self._backend_chunk
        # Dispatch overhead is per call, so the device chunk should be
        # as large as the chip comfortably holds (32k rows on TPU). CPU
        # test meshes keep the small chunk (compile time scales with B
        # there). A backend that fails to initialize raises here.
        from cadence_tpu.ops.replay_pallas import on_tpu

        self._backend_chunk = 32768 if on_tpu() else 4096
        return self._backend_chunk

    # -- history paging ------------------------------------------------

    def _read_batches(
        self, req: RebuildRequest, min_event_id: int = 1,
        tally: Optional[Counter] = None,
    ) -> List[List[HistoryEvent]]:
        """Page the run's batches out of the store; ``tally`` counts the
        ``pages`` read (one JSON parse each) and the ``nodes`` in them."""
        branch = BranchToken.from_json(req.branch_token.decode())
        out: List[List[HistoryEvent]] = []
        token = 0
        while True:
            batches, token = self.history.read_history_branch(
                branch, min_event_id, req.next_event_id or 1 << 60,
                page_size=256, next_token=token,
            )
            out.extend(batches)
            if tally is not None:
                tally["pages"] += 1
                tally["nodes"] += len(batches)
            if not token:
                return out

    # -- single rebuild (host oracle) ----------------------------------

    def rebuild(self, req: RebuildRequest) -> Tuple[MutableState, list, list]:
        """Replay one run from scratch; returns (ms, transfer, timer)."""
        batches = self._read_batches(req)
        if not batches:
            raise ValueError(
                f"rebuild: empty history for {req.workflow_id}/{req.run_id}"
            )
        ms = MutableState(domain_id=req.domain_id)
        ms.version_histories = VersionHistories.new_empty()
        sb = StateBuilder(ms, domain_resolver=self.domain_resolver)
        sb.apply_batches(
            req.domain_id, req.request_id, req.workflow_id, req.run_id,
            batches,
        )
        ms.execution_info.branch_token = req.branch_token
        transfer, timer = refresh_tasks(ms)
        return ms, transfer, timer

    # -- batched rebuild (device) --------------------------------------

    # -- checkpoint consult --------------------------------------------

    def _consult_serving(self, req: RebuildRequest):
        """Rehydrate one rebuild from a resident serving lane, or None.

        Sound only under exact-match guards: the caller pinned the
        target tip (``next_event_id``) and the lane is at it, the lane
        was seated from the SAME branch, and — when the caller supplied
        them (the NDC path) — the lane's version-history items equal
        the target's. Anything else (including a dirty lane the engine
        fails to compose) falls through to the checkpoint/cold path.
        Never raises."""
        if self.serving is None or not req.next_event_id:
            return None
        try:
            from cadence_tpu.ops import schema as S

            got = self.serving.resident_row(
                req.workflow_id, req.run_id, domain_id=req.domain_id
            )
            if got is None:
                return None
            if got.branch_token and got.branch_token != req.branch_token:
                return None
            row = got.state_row
            tip = int(row["exec_info"][S.X_NEXT_EVENT_ID])
            if tip != req.next_event_id:
                return None
            if req.version_history_items is not None:
                n = int(row["vh_len"])
                items = [
                    (int(e), int(v)) for e, v in row["vh_items"][:n]
                ]
                want = [
                    (int(e), int(v))
                    for e, v in req.version_history_items
                ]
                if items != want:
                    return None
            ms = got.mutable_state()
        except Exception:
            return None
        ms.execution_info.branch_token = req.branch_token
        transfer, timer = refresh_tasks(ms)
        (self._raw_metrics if self._raw_metrics is not None else NOOP
         ).tagged(layer="serving").inc("serving_resident_hits")
        return ms, transfer, timer

    def _consult_checkpoint(self, req: RebuildRequest, caps):
        """The resumable checkpoint for one request, or None; never
        raises. Misses/invalidations count here (they are final); a HIT
        counts only once the resume actually sticks
        (``_commit_hit``/``_degrade_hit``) so a degraded resume reports
        as the full replay it became, not as savings."""
        from cadence_tpu.checkpoint.manager import HIT

        if self.checkpoints is None:
            return None
        try:
            ckpt, status = self.checkpoints.lookup(
                req.branch_token, caps=caps,
                version_history_items=req.version_history_items,
                max_event_id=(
                    req.next_event_id - 1 if req.next_event_id else None
                ),
            )
        except Exception:
            self._metrics.inc("checkpoint_miss")
            return None
        if status == HIT and ckpt is not None:
            return ckpt
        self._metrics.inc(f"checkpoint_{status}")
        return None

    def _commit_hit(self, ckpt) -> None:
        self._metrics.inc("checkpoint_hit")
        # events before the snapshot are never read or replayed
        self._metrics.inc("events_replayed_saved", ckpt.event_id)

    def _degrade_hit(self) -> None:
        self._metrics.inc("checkpoint_miss")

    def _record_checkpoint(self, req, packed, final, row) -> None:
        if self.checkpoints is None:
            return
        self.checkpoints.maybe_record(
            req.branch_token, final, row, packed.side[row],
            epoch_s=packed.epoch_s, caps=packed.caps,
            domain_id=req.domain_id, workflow_id=req.workflow_id,
            run_id=req.run_id,
        )

    def rebuild_many(
        self, reqs: Sequence[RebuildRequest], use_device: bool = True,
    ) -> List[Tuple[MutableState, list, list]]:
        """Rebuild N runs at once. The device path groups the histories
        by the slot-table widths they need and, at the default widths, by
        depth, lane-packs and replays each group at its own
        ``Capacities``, and rehydrates
        MutableState per row; a batch the device cannot hold (over
        1,024 events, a timestamp outside the packable window, a slot
        table wider than the widest bucket) or the packer rejects falls
        back to the host oracle, and counts in the root span's
        ``host_fallbacks``. Histories replayed in a bucket wider than
        the default count in ``wide_histories`` (the root span's tag and
        the registry counter).

        With a checkpoint manager attached each request first looks up
        its newest valid snapshot: hits read + replay only the event
        suffix (the snapshot row seeds the segment carry), tip hits skip
        the device entirely, and the rebuilt tips of default-width
        buckets are written back as fresh checkpoints per the manager's
        policy."""
        span = TRACER.entry("rebuild_many", service="history")
        with span:
            if span:
                span.set_tag("requests", len(reqs))
            return self._rebuild_many(reqs, use_device, span)

    def _rebuild_many(self, reqs, use_device, span):
        if not use_device or len(reqs) == 0:
            return [self.rebuild(r) for r in reqs]

        try:
            import jax

            from cadence_tpu.ops.dispatch import (
                DeviceDispatcher,
                DispatchError,
                buckets,
            )
            from cadence_tpu.ops.unpack import state_row_to_mutable_state
        except ImportError:  # jax not installed — host path
            return [self.rebuild(r) for r in reqs]

        from cadence_tpu.ops import schema as S
        from cadence_tpu.ops.grid import staging_depth

        out: List[Optional[Tuple[MutableState, list, list]]] = (
            [None] * len(reqs)
        )
        caps = S.Capacities()

        # consult checkpoints, read only what must be replayed
        with TRACER.span("rebuild.read") as sp:
            tally: Counter = Counter()
            histories, resumes, pend_req = self._read_pending(
                reqs, caps, out, tally)
            if sp:
                sp.set_tag("histories", len(histories))
                sp.set_tag("events", sum(
                    len(b) for h in histories for b in h[2]))
                sp.set_tag("nodes", tally["nodes"])
                sp.set_tag("pages", tally["pages"])

        # storm drain: bucket the stream by slot-table width (default
        # caps are the floor: a fan-out parent's wide state pads no
        # narrow batch) and, at the floor, by depth (a few deep
        # stragglers must not stretch every lane; a resumed run buckets
        # by its SUFFIX depth), lane-pack each bucket (several whole histories per scan
        # lane), and pump the chunks through the double-buffered
        # host→device dispatcher (ops/dispatch.py) so packing batch k+1
        # overlaps replaying batch k; each failed chunk (over 1,024
        # events, a table wider than the widest bucket, etc.) falls back
        # per-workflow to the host oracle
        chunk = self._resolve_chunk()
        plan = []
        for idxs, hs, bcaps in buckets(histories, caps, resumes):
            for j in range(0, len(hs), chunk):
                plan.append((idxs[j : j + chunk], hs[j : j + chunk],
                             bcaps))
        if not plan:
            return out
        # the dispatcher is built only once the chunk plan exists, so
        # its staging buffer is sized per batch (staging_depth) — the
        # one-chunk serving/small-rebuild shape gets a one-slot queue
        d = DeviceDispatcher(
            caps=caps, depth=staging_depth(len(plan)),
            domain_resolver=self.domain_resolver, lane_pack=True,
            lane_len=self.lane_len, metrics=self._raw_metrics,
        )
        for sub, hs, bcaps in plan:
            d.submit(
                tuple(pend_req[i] for i in sub),
                hs,
                resume=[resumes[i] for i in sub],
                caps=bcaps,
            )
        d.finish()
        results = d.results(strict=False)
        on_device = fallbacks = wide = 0
        while True:
            with TRACER.span("rebuild.await"):
                item = next(results, None)
            if item is None:
                break
            if isinstance(item, DispatchError):
                with TRACER.span("rebuild.fallback") as sp:
                    if sp:
                        sp.set_tag("histories", len(item.batch_id))
                    for gi in item.batch_id:
                        out[gi] = self.rebuild(reqs[gi])
                fallbacks += len(item.batch_id)
                continue
            idxs, packed, final = item
            # one transfer for the batch: every leaf's copy starts, then
            # one wait; the rows below then index host arrays, where a
            # device array would cost a gather and a copy per table per row
            with TRACER.span("rebuild.fetch") as sp:
                final = jax.device_get(final)
                if sp:
                    sp.set_tag("histories", len(idxs))
                    sp.set_tag("bytes", sum(
                        int(x.nbytes)
                        for x in jax.tree_util.tree_leaves(final)))
            for j, gi in enumerate(idxs):
                r = reqs[gi]
                with TRACER.span("rebuild.unpack"):
                    ms = state_row_to_mutable_state(
                        final, j, packed.side[j],
                        domain_id=r.domain_id, epoch_s=packed.epoch_s,
                    )
                ms.execution_info.branch_token = r.branch_token
                with TRACER.span("rebuild.refresh"):
                    transfer, timer = refresh_tasks(ms)
                out[gi] = (ms, transfer, timer)
                if packed.caps == caps:
                    # lookups are at the default caps, where a wide
                    # bucket's row would never be read back
                    self._record_checkpoint(r, packed, final, j)
            on_device += len(idxs)
            if packed.caps != caps:
                wide += len(idxs)
        if wide:
            (self._raw_metrics if self._raw_metrics is not None else NOOP
             ).tagged(layer="device").inc("wide_histories", wide)
        if span:
            span.set_tag("device_histories", on_device)
            span.set_tag("wide_histories", wide)
            span.set_tag("host_fallbacks", fallbacks)
        return out

    def _read_pending(self, reqs, caps, out, tally):
        """Consult serving and checkpoints per request and read only
        what must be replayed. Fills ``out`` for requests answered
        without the device; returns the pending (wf, run, suffix
        batches), their Optional[ResumeState]s, and each one's request
        index. Every read counts in ``tally``."""
        histories = []           # pending (wf, run, suffix batches)
        resumes = []             # aligned Optional[ResumeState]
        pend_req: List[int] = []  # pending index -> request index
        for gi, r in enumerate(reqs):
            hit = self._consult_serving(r)
            if hit is not None:
                out[gi] = hit
                continue
            ckpt = self._consult_checkpoint(r, caps)
            if ckpt is None:
                batches = self._read_batches(r, tally=tally)
                resume = None
            else:
                try:
                    batches = self._read_batches(
                        r, min_event_id=ckpt.event_id + 1, tally=tally
                    )
                    resume = self.checkpoints.resume_state(ckpt)
                except Exception:  # degraded store/decode: full replay
                    batches, resume = self._read_batches(r, tally=tally), None
                    self._degrade_hit()
                if resume is not None and not _fits(batches, resume, caps):
                    # the suffix outgrows the snapshot's caps, and a
                    # wider bucket cannot take its row: full replay
                    batches, resume = self._read_batches(r, tally=tally), None
                    self._degrade_hit()
                if resume is not None and not batches:
                    # tip hit: nothing to replay — rehydrate directly
                    try:
                        ms = self.checkpoints.rehydrate(
                            ckpt, domain_id=r.domain_id
                        )
                        ms.execution_info.branch_token = r.branch_token
                        transfer, timer = refresh_tasks(ms)
                        out[gi] = (ms, transfer, timer)
                        self._commit_hit(ckpt)
                        continue
                    except Exception:
                        batches, resume = self._read_batches(r, tally=tally), None
                        self._degrade_hit()
                if resume is not None:
                    self._commit_hit(ckpt)
            histories.append((r.workflow_id, r.run_id, batches))
            resumes.append(resume)
            pend_req.append(gi)
        return histories, resumes, pend_req

"""Double-buffered host→device replay dispatch.

SURVEY §2.8 maps the reference's intra-shard pipelining (worker pools
draining queue tasks concurrently, replicationTaskProcessor.go's
sequential batch pump) to a host→device pipeline: while the device
replays batch k, the host packs batch k+1 (the C++ sidecar scatter,
native/sidecar.cpp) and stages its event tensor for transfer. JAX's
async dispatch makes a single extra thread sufficient: ``device_put``
and the jitted replay call return immediately, so pack(k+1) runs on the
CPU while replay(k) runs on the device, and the bounded stage queue
(``depth``) provides the double-buffer backpressure.

The kernel is the Pallas one on a TPU and the XLA scan elsewhere
(``replay_pallas.on_tpu``); on a TPU the events travel as an int16
stream where their values allow it (``_narrow``). Two storm levers ride
on top of the pipeline:

* **ragged lane packing** (``lane_pack=True``): the pack pump calls
  ops/pack.pack_lanes so several whole histories share each scan lane,
  and the run pump uses the packed scan (segment-end scatter + lane
  reset) — effective scan length per history is its own depth, not
  ``max(depth)`` over the chunk;
* **width and depth bucketing** (``replay_stream(bucket=True)`` /
  ``buckets``): histories sort by the slot-table widths they need
  (``pack.bucket_caps``), so a fan-out parent with hundreds of
  activities in flight gets a state as wide as it needs and no batch
  pays for it but its own; those at the default widths then sort into
  geometric depth classes, so a few deep stragglers don't stretch every
  lane. Each batch carries its ``Capacities`` (``submit(caps=...)``).

Batch width, scan length, and the packed scan's static event-type
signature are all rounded/grown monotonically (``round_scan_len``,
``_type_set``) so a storm of arbitrary chunk shapes compiles a bounded
set of executables.

Tracing: ``submit`` captures the caller's trace context
(utils/tracing.py) and each pump opens its spans under it on its own
thread — ``dispatch.pack`` (host packing and layout), ``dispatch.h2d``
(the transfers of one batch) and ``dispatch.launch`` (the kernel call,
tagged with the batch's real ``events``, and by the
Pallas kernels with the ``cells`` they stream and their ``state_rows``).
``buckets`` opens ``dispatch.bucket`` on its caller's thread, tagged
with the slots its buckets hold and use. Unsampled, each is one
thread-local read.

Used by the replication rebuild path for storm-sized request streams
(runtime/replication/rebuilder.py rebuild_many) and usable standalone::

    with DeviceDispatcher(caps) as d:
        for i, batch in enumerate(batches):
            d.submit(i, batch)
        d.finish()
        for batch_id, packed, final in d.results():
            ...  # final is a device StateTensors: fetch it once
                 # (jax.device_get), then unpack rows from the host copy
"""

from __future__ import annotations

import queue
import threading
import time as _time
from typing import Iterator, List, Optional, Sequence, Tuple

from cadence_tpu.utils.metrics import NOOP, Scope
from cadence_tpu.utils.tracing import TRACER

from . import schema as S
from .grid import round_scan_len, staging_depth


def _jit_cache_total() -> int:
    """Total compiled-executable count across the replay kernels a
    dispatcher can route to (jax keeps a per-jit cache; its growth IS a
    retrace). -1 when the introspection API is unavailable — telemetry
    must degrade, never break dispatch."""
    total = 0
    try:
        from .replay import replay_scan_jit, replay_scan_packed_jit

        for fn in (replay_scan_jit, replay_scan_packed_jit):
            size = getattr(fn, "_cache_size", None)
            if size is not None:
                total += int(size())
    except Exception:
        return -1
    return total


# retrace baseline at MODULE scope, matching the process-global jit
# caches it reads: serving builds a fresh dispatcher per rebuild_many
# call, and a per-dispatcher baseline would re-seed every call — a
# retrace storm crossing dispatcher lifetimes (the common one-batch
# serving shape) would never increment jit_retraces
_jit_baseline_lock = threading.Lock()
_jit_entries_prev: Optional[int] = None


def _jit_retrace_delta(entries: int) -> int:
    global _jit_entries_prev
    with _jit_baseline_lock:
        prev = _jit_entries_prev
        _jit_entries_prev = entries
    if prev is not None and entries > prev:
        return entries - prev
    return 0


class DispatchError(Exception):
    def __init__(self, batch_id, cause: BaseException) -> None:
        super().__init__(f"batch {batch_id}: {cause!r}")
        self.batch_id = batch_id
        self.cause = cause


def history_depth(batches) -> int:
    """Total event count of one history (its replay depth)."""
    return sum(len(b) for b in batches)


def depth_buckets(
    histories: Sequence[Tuple],
) -> List[Tuple[Tuple[int, ...], List[Tuple]]]:
    """Sort histories by depth and group them into geometric depth
    buckets (``round_scan_len`` grid), shallowest first.

    A handful of deep stragglers in a mixed batch no longer stretch
    every lane: each bucket packs lanes sized for its own depth class.
    Returns ``[(original_indices, bucket_histories), ...]`` so callers
    can reassemble results in submission order.
    """
    keyed = sorted(
        range(len(histories)),
        key=lambda i: (round_scan_len(history_depth(histories[i][2])), i),
    )
    out: List[Tuple[Tuple[int, ...], List[Tuple]]] = []
    cur_key = None
    for i in keyed:
        key = round_scan_len(history_depth(histories[i][2]))
        if key != cur_key:
            out.append(((), []))
            cur_key = key
        idxs, hs = out[-1]
        out[-1] = (idxs + (i,), hs)
        hs.append(histories[i])
    return out


def buckets(
    histories: Sequence[Tuple],
    floor: Optional[S.Capacities] = None,
    resume: Optional[Sequence] = None,
) -> List[Tuple[Tuple[int, ...], List[Tuple], Optional[S.Capacities]]]:
    """Group histories by the capacity bucket of their slot-table peaks
    (``pack.bucket_caps``, never below ``floor``, default
    ``Capacities()``). Histories at ``floor`` split into depth buckets
    (``depth_buckets``, shallowest first), exactly as a stream with no
    wide history does. Each wider bucket is one group, narrowest first:
    lane packing fills its lanes toward the lane length whatever their
    depths, so depth classes would only multiply its launches and the
    shapes they compile. Histories wider than the widest bucket come
    last, in one group with caps None, which packs at the dispatcher's
    caps, overflows and falls back.

    ``resume``: optional per-history Optional[ops.pack.ResumeState]
    aligned with ``histories`` (a resumed history's batches are its
    suffix; its peaks count from the snapshot's pending entries).
    Returns ``[(original_indices, bucket_histories, caps), ...]``,
    measured and grouped inside a ``dispatch.bucket`` span, tagged
    ``histories``, ``wide_histories`` (those above ``floor``),
    ``buckets``, and over the histories a bucket holds, ``slots`` (each
    one's bucket capacity summed over the five slot tables) and
    ``slots_used`` (their peak occupancies)."""
    from .pack import PackOverflowError, SLOT_TABLES, bucket_caps, slot_peaks

    floor = floor or S.Capacities()
    with TRACER.span("dispatch.bucket") as sp:
        groups: dict = {}
        slots = used = 0
        for i, h in enumerate(histories):
            r = resume[i] if resume is not None else None
            peaks = slot_peaks(h[2], r.pack if r is not None else None)
            try:
                caps = bucket_caps(peaks, floor)
            except PackOverflowError:
                caps = None
            else:
                slots += sum(getattr(caps, f) for f in SLOT_TABLES)
                used += sum(peaks)
            groups.setdefault(caps, []).append(i)
        narrow = groups.pop(floor, [])
        over = groups.pop(None, [])
        out = [(tuple(narrow[j] for j in idxs), hs, floor)
               for idxs, hs in depth_buckets([histories[i] for i in narrow])]
        for caps in sorted(groups, key=lambda c: tuple(
                getattr(c, f) for f in SLOT_TABLES)):
            out.append((tuple(groups[caps]),
                        [histories[i] for i in groups[caps]], caps))
        if over:
            out.append((tuple(over), [histories[i] for i in over], None))
        if sp:
            sp.set_tag("histories", len(histories))
            sp.set_tag("wide_histories", sum(map(len, groups.values())))
            sp.set_tag("buckets", len(out))
            sp.set_tag("slots", slots)
            sp.set_tag("slots_used", used)
    return out


class DeviceDispatcher:
    """Pipelines pack (host, C++ sidecar) → H2D → replay (device).

    depth bounds how many packed batches may be staged ahead of the
    device — 2 is classic double buffering. Results come back in
    submission order from :meth:`results`.
    """

    def __init__(
        self,
        caps: Optional[S.Capacities] = None,
        depth: int = 2,
        domain_resolver=None,
        bt: int = 4096,
        tb: int = 16,
        lane_pack: bool = False,
        lane_len: Optional[int] = None,
        metrics: Optional[Scope] = None,
    ) -> None:
        self.caps = caps or S.Capacities()
        # device telemetry (utils/metrics_defs.py DEVICE_METRICS):
        # per-batch staging time, real and staged event cells, lanes
        # and the histories packed into them, batch-width histogram and
        # jit-cache growth, tagged by kernel and staging mode. Nothing
        # here blocks on the device: kernel time comes from a device
        # profile, where the dispatch.launch span sits on the same
        # clock. None OR the shared NOOP sentinel (both mean "no
        # metrics wired") disables the whole plane — the pumps check
        # one bool and skip every measurement.
        self._telemetry = metrics is not None and metrics is not NOOP
        self._metrics = (metrics if metrics is not None else NOOP).tagged(
            layer="device"
        )
        # threaded into pack_workflow: side-table target domains must
        # be RESOLVED ids, matching the host oracle (StateBuilder)
        self.domain_resolver = domain_resolver
        # pallas tile shape (serving deployments set the measured-best;
        # tests shrink it for interpret mode)
        self.bt, self.tb = bt, tb
        # ragged lane packing (ops/pack.py pack_lanes): several whole
        # histories per scan lane; effective scan length becomes
        # ≈ total_events / lanes instead of max(depth). lane_len is the
        # lane capacity in events (None = one history per lane density,
        # i.e. the longest history in each batch)
        self.lane_pack = lane_pack
        self.lane_len = lane_len
        # the Pallas kernels' int16 event stream (_narrow): the union of
        # wide columns only GROWS across batches (passed as force_wide)
        # so the kernel specialization key stays stable mid-storm
        self._wide_set: set = set()
        # present-event-type union across batches: the packed scan's
        # static specialization key (replay.type_signature) — grows
        # monotonically like _wide_set so it can't recompile mid-storm
        self._type_set: set = set()
        self._in: "queue.Queue" = queue.Queue()
        self._staged: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._out: "queue.Queue" = queue.Queue()
        self._packer = threading.Thread(
            target=self._pack_pump, name="dispatch-pack", daemon=True
        )
        self._runner = threading.Thread(
            target=self._run_pump, name="dispatch-run", daemon=True
        )
        self._started = False
        self._finished = False
        self._drained = False

    # -- producer side --------------------------------------------------

    def submit(
        self, batch_id, histories: Sequence[Tuple], resume=None,
        caps: Optional[S.Capacities] = None,
    ) -> None:
        """Enqueue one batch of (workflow_id, run_id, event_batches).

        ``resume``: optional per-history sequence of
        Optional[ops.pack.ResumeState] — resumed histories' events are
        their SUFFIX from the snapshot; the packed scan seeds their
        segment carries from the snapshot rows (checkpointed
        incremental replay).

        ``caps``: the batch's slot-table capacities (its ``buckets``
        group's), default the dispatcher's; the packed batch carries
        them to its kernel."""
        if not self._started:
            self._packer.start()
            self._runner.start()
            self._started = True
        # the pumps' spans join the submitter's trace, if it has one
        self._in.put((batch_id, histories, resume, caps or self.caps,
                      TRACER.current_context()))

    def finish(self) -> None:
        """No more submits; results() ends after the queued work.
        Idempotent."""
        if not self._finished:
            self._finished = True
            self._in.put(None)

    # -- pipeline stages -------------------------------------------------

    def _pack_pump(self) -> None:
        try:
            from .replay_pallas import on_tpu

            use_pallas = on_tpu()
        except Exception as e:
            # no usable jax on this host: every queued batch fails fast
            # (the rebuilder falls back per batch) instead of the pump
            # dying silently and results() hanging forever
            while True:
                item = self._in.get()
                if item is None:
                    self._staged.put(None)
                    return
                self._staged.put(DispatchError(item[0], e))

        while True:
            item = self._in.get()
            if item is None:
                self._staged.put(None)
                return
            batch_id, histories, resume, caps, ctx = item
            try:
                t0 = _time.perf_counter()
                if self.lane_pack:
                    staged = self._pack_lanes_item(
                        batch_id, histories, use_pallas, caps,
                        resume=resume, ctx=ctx,
                    )
                else:
                    staged = self._pack_hist_item(
                        batch_id, histories, use_pallas, caps,
                        resume=resume, ctx=ctx,
                    )
                if self._telemetry:
                    self._emit_stage_telemetry(
                        staged, histories, use_pallas,
                        _time.perf_counter() - t0,
                    )
                # blocks when `depth` batches are already staged — the
                # double-buffer backpressure
                self._staged.put((staged, ctx))
            except Exception as e:
                self._staged.put(DispatchError(batch_id, e))

    def _device_scope(self, mode: str, use_pallas: bool) -> Scope:
        return self._metrics.tagged(
            kernel="pallas" if use_pallas else "xla", mode=mode,
        )

    def _emit_stage_telemetry(
        self, staged, histories, use_pallas: bool, stage_s: float,
    ) -> None:
        """Per-batch staging telemetry (pack + H2D build time, event
        cells real and staged, lanes and their histories, width
        histogram) — only reached when a metrics scope was wired
        (``self._telemetry``). Counters, so that any window's ratios
        come out of two reads: padding = staged ÷ real cells − 1,
        occupancy = lane_histories ÷ lanes."""
        mode, packed = staged[0], staged[2]
        scope = self._device_scope(mode, use_pallas)
        scope.inc("device_batches")
        scope.record("host_stage_seconds", stage_s)
        if mode == "lanes":
            real = packed.total_events
            width = packed.lanes
            scope.inc("lanes", packed.lanes)
            scope.inc("lane_histories", packed.n_histories)
        else:
            real = sum(history_depth(h[2]) for h in histories)
            width = packed.batch
        scope.inc("replay_event_cells", real)
        scope.inc("replay_staged_cells", width * packed.events.shape[1])
        # batches counted per grid-rounded width: the compiled-
        # executable set in action (width cardinality is bounded by the
        # round_scan_len geometric grid, so the tag can't explode)
        scope.tagged(width=str(width)).inc("batch_width")

    def _emit_step_telemetry(self) -> None:
        """Per-batch jit-cache growth, read without waiting for the
        device."""
        entries = _jit_cache_total()
        if entries >= 0:
            self._metrics.gauge("jit_cache_entries", entries)
            delta = _jit_retrace_delta(entries)
            if delta:
                self._metrics.inc("jit_retraces", delta)

    def _narrow(self, teb):
        """The int16 stream of a field-major event tensor
        (``replay_pallas.narrow_events_teb``): it halves both the H2D
        transfer and the HBM stream the kernel is bound by. Where a
        gating column is wide, the kernel takes ``teb`` as is. Returns
        (events, narrow_meta), narrow_meta None for ``teb`` itself."""
        from .replay_pallas import narrow_events_teb

        narrowed = narrow_events_teb(
            teb, force_wide=tuple(sorted(self._wide_set))
        )
        if narrowed is None:
            return teb, None
        ev16, nbase, nwide = narrowed
        self._wide_set.update(nwide)
        return ev16, (nbase, nwide)

    def _pack_hist_item(self, batch_id, histories, use_pallas, caps,
                        resume=None, ctx=None):
        from .pack import pack_histories
        from .replay import to_device

        b = len(histories)
        with TRACER.span("dispatch.pack", parent=ctx) as sp:
            # grid-rounded batch: distinct stream chunk sizes would
            # otherwise each compile a fresh replay executable mid-storm
            packed = pack_histories(
                histories, caps=caps, pad_batch_to=round_scan_len(b),
                domain_resolver=self.domain_resolver,
                resume=resume,
            )
            if sp:
                sp.set_tag("histories", b)
                sp.set_tag("events", int(packed.lengths.sum()))
                sp.set_tag("lanes", packed.batch)
            # checkpoint resume seeds the initial carries; padding rows
            # of packed.initial are empty_state, so the grid pad is
            # unchanged
            state0 = (packed.initial if packed.initial is not None
                      else S.empty_state(packed.batch, caps))
            if use_pallas:
                events, narrow_meta = self._narrow(packed.teb())
            else:
                events, narrow_meta = packed.time_major(), None
        operands = to_device((events, state0), "dispatch.h2d", ctx)
        return ("hist", batch_id, packed, operands, (narrow_meta, b))

    def _pack_lanes_item(self, batch_id, histories, use_pallas, caps,
                         resume=None, ctx=None):
        from .pack import pack_lanes
        from .replay import to_device, type_signature

        with TRACER.span("dispatch.pack", parent=ctx) as sp:
            packed = pack_lanes(
                histories, caps=caps, target_lane_len=self.lane_len,
                seg_align=self.tb if use_pallas else 1,
                domain_resolver=self.domain_resolver,
                resume=resume,
            )
            if sp:
                sp.set_tag("histories", packed.n_histories)
                sp.set_tag("events", packed.total_events)
                sp.set_tag("lanes", packed.lanes)
            self._type_set.update(packed.present_types)
            sig = type_signature(self._type_set)
            narrow_meta = None
            if use_pallas:
                events, narrow_meta = self._narrow(packed.teb())
                arrays = (events, packed.seg_end, packed.out_row)
            else:
                arrays = packed.time_major()
            # checkpoint resume: lanes whose first segment resumes seed
            # from the snapshot row; segment-end resets gather the NEXT
            # segment's initial row via the reset table
            # (ops/replay.replay_scan_packed)
            resume_extra = None
            if packed.initial is not None:
                import numpy as _np

                reset = packed.reset_rows()                       # [L, T]
                resume_extra = (
                    packed.initial, reset,
                    _np.ascontiguousarray(reset.T),               # [T, L]
                )
            out0 = S.empty_state(round_scan_len(packed.n_histories), caps)
            host = (arrays, packed.lane_state0(), out0, resume_extra)
        operands = to_device(host, "dispatch.h2d", ctx)
        return ("lanes", batch_id, packed, operands, (sig, narrow_meta))

    def _run_pump(self) -> None:
        from .replay_pallas import on_tpu

        use_pallas = on_tpu()
        while True:
            item = self._staged.get()
            if item is None:
                self._out.put(None)
                return
            if isinstance(item, DispatchError):
                self._out.put(item)
                continue
            (mode, batch_id, packed, operands, extra), ctx = item
            try:
                with TRACER.span("dispatch.launch", parent=ctx) as sp:
                    final, streamed = self._launch(
                        mode, packed, operands, extra, use_pallas)
                    if sp:
                        sp.set_tag("events", int(packed.lengths.sum()))
                        if streamed is not None:
                            sp.set_tag("cells", streamed)
                # async dispatch: the call returns while the device
                # works; the next H2D/pack proceeds immediately
                if self._telemetry:
                    self._emit_step_telemetry()
                self._out.put((batch_id, packed, final))
            except Exception as e:
                self._out.put(DispatchError(batch_id, e))

    def _launch(self, mode, packed, operands, extra, use_pallas):
        """Run one staged batch's kernel. Returns (final, streamed):
        ``streamed`` is the event cells an XLA kernel streams (its
        operand's shape), None where a Pallas kernel tags the current
        span with its own, tile padding included."""
        from .replay import first_rows

        if mode == "lanes":
            arrays, state0, out0, resume_extra = operands
            sig, narrow_meta = extra
            streamed = None
            if use_pallas:
                from .replay_pallas import replay_scan_pallas_packed

                nbase, nwide = (
                    narrow_meta if narrow_meta is not None else (None, ())
                )
                kw = {}
                if resume_extra is not None:
                    kw = dict(init=resume_extra[0],
                              reset_row=resume_extra[1])
                _, final = replay_scan_pallas_packed(
                    state0, out0, *arrays, packed.caps,
                    tb=self.tb, bt=self.bt, base=nbase,
                    wide_cols=nwide, **kw,
                )
            else:
                from .replay import replay_scan_packed_jit

                kw = {}
                if resume_extra is not None:
                    kw = dict(init=resume_extra[0],
                              reset_row_tm=resume_extra[2])
                _, final = replay_scan_packed_jit(
                    state0, out0, *arrays, types=sig, **kw
                )
                streamed = arrays[0].shape[0] * arrays[0].shape[1]
            return first_rows(final, packed.n_histories), streamed
        (events, state0), (narrow_meta, b) = operands, extra
        streamed = None
        if use_pallas:
            from .replay_pallas import replay_scan_pallas_teb

            nbase, nwide = (
                narrow_meta if narrow_meta is not None else (None, ())
            )
            final = replay_scan_pallas_teb(
                state0, events, packed.caps, base=nbase,
                wide_cols=nwide, bt=self.bt, tb=self.tb,
            )
        else:
            from .replay import replay_scan_jit

            # the jitted form donates state0's buffer and skips
            # per-batch retracing on this hot storm-drain path
            final = replay_scan_jit(state0, events)
            streamed = events.shape[0] * events.shape[1]
        if b < packed.batch:
            # grid padding is an implementation detail; the consumer
            # sees exactly its submitted batch
            final = first_rows(final, b)
        return final, streamed

    # -- consumer side ----------------------------------------------------

    def results(self, strict: bool = True) -> Iterator[Tuple]:
        """Yields (batch_id, packed, final_state) in submission order.

        A failed batch raises its DispatchError when its turn comes
        (strict, default) or is yielded as the DispatchError itself
        (strict=False) so the caller can fall back per batch and keep
        consuming. On a strict raise the remaining staged/out queues are
        drained in the background first — the consumer abandons the
        iterator at the raise, and without the drain the pack pump
        could block forever on a full ``_staged`` queue.
        """
        while True:
            item = self._out.get()
            if item is None:
                self._drained = True
                return
            if isinstance(item, DispatchError):
                if strict:
                    self._drain_async()
                    raise item
                yield item
                continue
            yield item

    def _drain_async(self) -> None:
        """Consume everything still in flight on a daemon thread so the
        pumps run to completion and exit; idempotent."""
        if self._drained:
            return
        self._drained = True
        self.finish()

        def _run() -> None:
            while self._out.get() is not None:
                pass

        threading.Thread(
            target=_run, name="dispatch-drain", daemon=True
        ).start()

    def __enter__(self) -> "DeviceDispatcher":
        return self

    def __exit__(self, *exc) -> None:
        if not self._started or self._drained:
            return
        self.finish()
        # drain so the pumps exit even on abnormal exit
        while self._out.get() is not None:
            pass
        self._drained = True


def replay_stream(
    histories: Sequence[Tuple],
    caps: Optional[S.Capacities] = None,
    batch_size: int = 4096,
    depth: int = 2,
    lane_pack: bool = False,
    lane_len: Optional[int] = None,
    bucket: bool = False,
    resume: Optional[Sequence] = None,
    metrics: Optional[Scope] = None,
) -> List[Tuple]:
    """Replay a large history stream through the pipelined dispatcher.

    Splits ``histories`` into ``batch_size`` chunks and returns
    [(packed, final_state), ...] in order — the storm-drain entry the
    replication rebuilder uses.

    ``bucket=True`` (implies lane packing) sorts the stream into
    capacity buckets no narrower than ``caps`` (``buckets``), so a
    history with more pending entries than ``caps`` holds still
    replays, and those at ``caps`` into geometric depth buckets, so
    mixed-depth storms don't pad every lane to the deepest straggler;
    the return value then
    carries the original indices per batch: [(indices, packed,
    final_state), ...] where row j of ``final_state`` is history
    ``indices[j]``, with ``packed.caps`` its batch's capacities.

    ``resume``: optional per-history Optional[ops.pack.ResumeState]
    aligned with ``histories`` — resumed entries carry their event
    SUFFIX and replay from the snapshot row (checkpointed incremental
    replay); a resumed run buckets by its suffix depth.
    """
    out: List[Tuple] = []
    resume = list(resume) if resume is not None else [None] * len(histories)
    if len(resume) != len(histories):
        raise ValueError("resume list must align with histories")
    any_resume = any(r is not None for r in resume)
    if bucket:
        # plan the chunking FIRST so the staging queue is sized to the
        # batches that exist (staging_depth) — a one-chunk stream (the
        # common serving / small-rebuild shape) must not allocate
        # double-buffer headroom it can never use
        plan: List[Tuple] = []
        for idxs, hs, bcaps in buckets(
                histories, caps, resume if any_resume else None):
            for j in range(0, len(hs), batch_size):
                plan.append((idxs[j : j + batch_size],
                             hs[j : j + batch_size], bcaps))
        if not plan:
            return out
        d = DeviceDispatcher(
            caps=caps, depth=staging_depth(len(plan), depth),
            lane_pack=True, lane_len=lane_len, metrics=metrics,
        )
        for sub, hs, bcaps in plan:
            d.submit(
                sub, hs,
                resume=[resume[i] for i in sub] if any_resume else None,
                caps=bcaps,
            )
        d.finish()
        for idxs, packed, final in d.results():
            out.append((idxs, packed, final))
        return out
    if not histories:
        return out
    n_batches = -(-len(histories) // batch_size)
    d = DeviceDispatcher(
        caps=caps, depth=staging_depth(n_batches, depth),
        lane_pack=lane_pack, lane_len=lane_len, metrics=metrics,
    )
    for i in range(0, len(histories), batch_size):
        d.submit(
            i, histories[i : i + batch_size],
            resume=(
                resume[i : i + batch_size] if any_resume else None
            ),
        )
    d.finish()
    for _, packed, final in d.results():
        out.append((packed, final))
    return out

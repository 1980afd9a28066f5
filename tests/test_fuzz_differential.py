"""Differential fuzzing: random valid histories, kernel vs oracle.

The event-graph fuzzer (cadence_tpu/testing/event_generator.py) plays the
role of the reference's model-based generator in its NDC tests
(host/ndc/nDC_integration_test.go:114-126): every generated walk is a
legal history, and the device kernel must agree with the host oracle on
all of them.
"""


from cadence_tpu.core.task_refresher import refresh_tasks
from cadence_tpu.ops.pack import pack_histories
from cadence_tpu.ops.refresh import (
    hydrate_tasks,
    refresh_tasks_device_jit,
    refreshed_to_numpy,
)
from cadence_tpu.ops.replay import replay_packed
from cadence_tpu.ops.schema import Capacities
from cadence_tpu.ops.unpack import mutable_state_to_snapshot, state_row_to_snapshot
from cadence_tpu.testing.event_generator import HistoryFuzzer

from test_replay_differential import oracle_replay

CAPS = Capacities(max_events=256)


def test_fuzz_parity_bulk():
    """One packed batch of 48 random histories — state + task parity."""
    n = 48
    histories = []
    for seed in range(n):
        fuzzer = HistoryFuzzer(seed=seed, caps=CAPS)
        batches = fuzzer.generate(
            target_events=30 + (seed % 5) * 30,
            close=seed % 3 != 0,  # a third stay open
        )
        histories.append((f"wf-{seed}", f"run-{seed}", batches))

    packed = pack_histories(histories, caps=CAPS)
    final = replay_packed(packed)
    refreshed = refreshed_to_numpy(refresh_tasks_device_jit(final))

    for i, (_, _, batches) in enumerate(histories):
        ms = oracle_replay(batches, workflow_id=f"wf-{i}", run_id=f"run-{i}")
        oracle_snap = mutable_state_to_snapshot(ms)
        kernel_snap = state_row_to_snapshot(final, i, packed.epoch_s)
        assert kernel_snap == oracle_snap, f"seed {i} state diverged"

        dev_transfer, dev_timer = hydrate_tasks(refreshed, i, packed, domain_id="dom")
        ms.execution_info.domain_id = "dom"
        host_transfer, host_timer = refresh_tasks(ms)
        assert [
            (t.task_type, t.schedule_id, t.initiated_id) for t in dev_transfer
        ] == [
            (t.task_type, t.schedule_id, t.initiated_id) for t in host_transfer
        ], f"seed {i} transfer tasks diverged"
        assert [
            (t.task_type, t.visibility_timestamp, t.timeout_type, t.event_id,
             t.schedule_attempt)
            for t in dev_timer
        ] == [
            (t.task_type, t.visibility_timestamp, t.timeout_type, t.event_id,
             t.schedule_attempt)
            for t in host_timer
        ], f"seed {i} timer tasks diverged"


def test_fuzz_checkpoint_resume_three_way_parity():
    """Checkpoint-resumed replay must be byte-identical across the host
    oracle, the XLA packed scan, and the Pallas packed scan (interpret),
    for fuzzed histories cut at every-other batch boundary — including
    cuts landing exactly on a seg_align segment boundary and a
    zero-suffix (checkpoint at tip) case."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cadence_tpu.checkpoint import checkpoint_from_replay
    from cadence_tpu.ops import schema as S
    from cadence_tpu.ops.pack import pack_lanes, round_scan_len
    from cadence_tpu.ops.replay import replay_packed
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed
    from cadence_tpu.ops.unpack import split_lane_snapshots
    from cadence_tpu.runtime.persistence.records import BranchToken

    n = 10
    histories = []
    for seed in range(n):
        fz = HistoryFuzzer(seed=100 + seed, caps=CAPS)
        histories.append((
            f"wf-{seed}", f"run-{seed}",
            fz.generate(target_events=24 + (seed % 4) * 24,
                        close=seed % 3 == 0),
        ))

    resume, suffixes = [], []
    for i, (wf, run, batches) in enumerate(histories):
        if i == n - 1:
            cut = len(batches)       # checkpoint at tip: empty suffix
        else:
            cut = max(1, (len(batches) * (1 + i % 3)) // 4)
        pk = pack_histories([(wf, run, batches[:cut])], caps=CAPS)
        pre = replay_packed(pk)
        ck = checkpoint_from_replay(
            BranchToken(tree_id=run, branch_id="b").to_json().encode(),
            pre, 0, pk.side[0], pk.epoch_s, CAPS,
        )
        resume.append(ck.resume_state())
        suffixes.append((wf, run, batches[cut:]))

    oracle_snaps = []
    for wf, run, batches in histories:
        ms = oracle_replay(batches, workflow_id=wf, run_id=run)
        oracle_snaps.append(mutable_state_to_snapshot(ms))

    # XLA packed (unaligned segments) — vs oracle
    lanes = pack_lanes(
        suffixes, caps=CAPS, target_lane_len=128, resume=resume
    )
    got = split_lane_snapshots(lanes, replay_packed(lanes))
    for i in range(n):
        assert got[i] == oracle_snaps[i], f"xla resume {i} != oracle"

    # Pallas packed (tb-aligned segments, interpret) — vs oracle
    lanes8 = pack_lanes(
        suffixes, caps=CAPS, target_lane_len=128, seg_align=8,
        resume=resume,
    )
    state0 = jax.tree_util.tree_map(jnp.asarray, lanes8.lane_state0())
    out0 = jax.tree_util.tree_map(
        jnp.asarray,
        S.empty_state(round_scan_len(lanes8.n_histories), CAPS),
    )
    _, out = replay_scan_pallas_packed(
        state0, out0, jnp.asarray(lanes8.teb()),
        jnp.asarray(lanes8.seg_end), jnp.asarray(lanes8.out_row),
        CAPS, tb=8, interpret=True, bt=1024,
        init=jax.tree_util.tree_map(jnp.asarray, lanes8.initial),
        reset_row=jnp.asarray(lanes8.reset_rows()),
    )
    got8 = split_lane_snapshots(
        lanes8, jax.tree_util.tree_map(np.asarray, out)
    )
    for i in range(n):
        assert got8[i] == oracle_snaps[i], f"pallas resume {i} != oracle"


def test_fuzzer_reproducible():
    a = HistoryFuzzer(seed=7, caps=CAPS).generate(target_events=50)
    b = HistoryFuzzer(seed=7, caps=CAPS).generate(target_events=50)
    assert a == b


def test_fuzzer_event_ids_contiguous():
    batches = HistoryFuzzer(seed=3, caps=CAPS).generate(target_events=60)
    flat = [e for batch in batches for e in batch]
    assert [e.event_id for e in flat] == list(range(1, len(flat) + 1))

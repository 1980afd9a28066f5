"""Pallas replay kernel parity: bit-for-bit vs the XLA scan kernel.

The XLA kernel (ops/replay.py) is itself differential-tested against the
host oracle (tests/test_replay_differential.py == the reference's
stateBuilder.applyEvents semantics,
/root/reference/service/history/stateBuilder.go:112-613), so parity here
closes the chain oracle == XLA == Pallas. Runs the kernel in interpret
mode (tests are pinned to the CPU backend by conftest); the same code
path compiles for TPU with interpret=False.
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import pack_histories
from cadence_tpu.ops.replay import replay_scan
from cadence_tpu.ops.replay_pallas import (
    RowMap,
    replay_scan_pallas,
    rows_to_state,
    state_to_rows,
)
from cadence_tpu.testing import workloads as W
from cadence_tpu.testing.event_generator import HistoryFuzzer

# Small capacities keep interpret-mode runtime reasonable; every slot
# table and the version-history ring are still exercised.
CAPS = S.Capacities(
    max_events=96, max_activities=4, max_timers=4, max_children=4,
    max_request_cancels=2, max_signals_ext=2, max_version_items=4,
)


# Interpret-mode cost scales with T x rows; the fast subset uses a tiny
# event budget so one parity case always runs in the default suite.
FAST_CAPS = S.Capacities(
    max_events=16, max_activities=2, max_timers=2, max_children=2,
    max_request_cancels=1, max_signals_ext=1, max_version_items=2,
)

slow = pytest.mark.slow


def _pack(histories, caps=CAPS):
    return pack_histories(histories, caps=caps)


def _assert_state_equal(a: S.StateTensors, b: S.StateTensors):
    for name in ("exec_info", "activities", "timers", "children",
                 "cancels", "signals", "vh_items", "vh_len"):
        av, bv = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        np.testing.assert_array_equal(
            av, bv, err_msg=f"field {name} diverged"
        )


def _parity(histories, tb=8, bt=1024, caps=CAPS, use_teb=False,
            pad_batch_to=None):
    packed = pack_histories(histories, caps=caps, pad_batch_to=pad_batch_to)
    b = packed.events.shape[0]
    ev_tm = jnp.asarray(
        np.ascontiguousarray(np.transpose(packed.events, (1, 0, 2)))
    )
    state0 = jax.tree_util.tree_map(jnp.asarray, S.empty_state(b, caps))
    want = replay_scan(state0, ev_tm)
    if use_teb:
        from cadence_tpu.ops.replay_pallas import replay_scan_pallas_teb

        pres = packed.presence(bt)
        if pad_batch_to is not None:
            assert pres is not None, "host presence path not exercised"
        got = replay_scan_pallas_teb(
            state0, jnp.asarray(packed.teb()), caps, tb=tb, interpret=True,
            bt=bt, presence=pres,
        )
    else:
        got = replay_scan_pallas(state0, ev_tm, caps, tb=tb,
                                 interpret=True, bt=bt)
    _assert_state_equal(got, want)


def test_rowmap_roundtrip():
    """state_to_rows / rows_to_state is lossless on a replayed state."""
    packed = _pack(
        [(f"wf-{i}", f"run-{i}", W.echo_history()) for i in range(5)]
    )
    ev_tm = jnp.asarray(
        np.ascontiguousarray(np.transpose(packed.events, (1, 0, 2)))
    )
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(packed.events.shape[0], CAPS)
    )
    final = replay_scan(state0, ev_tm)
    rm = RowMap(CAPS)
    back = rows_to_state(state_to_rows(final, rm), rm)
    _assert_state_equal(back, final)


def test_packed_lanes_parity_fast():
    """Chunked Pallas packed path (replay_scan_pallas_packed) ==
    XLA packed scan, bit for bit, on a tiny tb-aligned packing."""
    from cadence_tpu.ops.pack import pack_lanes, round_scan_len
    from cadence_tpu.ops.replay import replay_packed_lanes
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    tb = 8
    fz = HistoryFuzzer(seed=6, caps=FAST_CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=8))
        for i in range(4)
    ]
    lanes = pack_lanes(hs, caps=FAST_CAPS, target_lane_len=16, seg_align=tb)
    want = replay_packed_lanes(lanes)  # XLA packed path (numpy out)
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(lanes.lanes, FAST_CAPS)
    )
    out0 = jax.tree_util.tree_map(
        jnp.asarray,
        S.empty_state(round_scan_len(lanes.n_histories), FAST_CAPS),
    )
    _, got = replay_scan_pallas_packed(
        state0, out0, jnp.asarray(lanes.teb()),
        jnp.asarray(lanes.seg_end), jnp.asarray(lanes.out_row),
        FAST_CAPS, tb=tb, interpret=True, bt=1024,
    )
    got = jax.tree_util.tree_map(
        lambda x: np.asarray(x)[: lanes.n_histories], got
    )
    _assert_state_equal(got, want)


def test_packed_lanes_rejects_misaligned_segments():
    from cadence_tpu.ops.pack import pack_lanes
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    fz = HistoryFuzzer(seed=6, caps=FAST_CAPS)
    hs = [(f"wf-{i}", f"run-{i}", fz.generate(target_events=9))
          for i in range(3)]
    lanes = pack_lanes(hs, caps=FAST_CAPS, target_lane_len=24, seg_align=1)
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(lanes.lanes, FAST_CAPS)
    )
    out0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(8, FAST_CAPS)
    )
    with pytest.raises(ValueError, match="tb-aligned"):
        replay_scan_pallas_packed(
            state0, out0, jnp.asarray(lanes.teb()),
            jnp.asarray(lanes.seg_end), jnp.asarray(lanes.out_row),
            FAST_CAPS, tb=8, interpret=True, bt=1024,
        )


def test_packed_lanes_narrow_int16_parity():
    """Packed + int16 narrow stream == packed int32, bit for bit."""
    from cadence_tpu.ops.pack import pack_lanes, round_scan_len
    from cadence_tpu.ops.replay_pallas import (
        narrow_events_teb,
        replay_scan_pallas_packed,
    )

    tb = 8
    fz = HistoryFuzzer(seed=14, caps=FAST_CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=8))
        for i in range(4)
    ]
    lanes = pack_lanes(hs, caps=FAST_CAPS, target_lane_len=16, seg_align=tb)
    narrowed = narrow_events_teb(lanes.teb())
    assert narrowed is not None, "fuzzed batch should narrow"
    ev16, base, wide = narrowed
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(lanes.lanes, FAST_CAPS)
    )
    out0 = jax.tree_util.tree_map(
        jnp.asarray,
        S.empty_state(round_scan_len(lanes.n_histories), FAST_CAPS),
    )
    args = (jnp.asarray(lanes.seg_end), jnp.asarray(lanes.out_row))
    _, want = replay_scan_pallas_packed(
        state0, out0, jnp.asarray(lanes.teb()), *args,
        FAST_CAPS, tb=tb, interpret=True, bt=1024,
    )
    _, got = replay_scan_pallas_packed(
        state0, out0, jnp.asarray(ev16), *args,
        FAST_CAPS, tb=tb, interpret=True, bt=1024,
        base=base, wide_cols=wide,
    )
    _assert_state_equal(got, want)


@slow
def test_packed_lanes_parity_fuzzed():
    """Wider fuzzed packing through the chunked Pallas packed path."""
    from cadence_tpu.ops.pack import pack_lanes, round_scan_len
    from cadence_tpu.ops.replay import replay_scan_packed, type_signature
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    tb = 8
    fz = HistoryFuzzer(seed=19, caps=CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=10 + (i * 9) % 30))
        for i in range(11)
    ]
    lanes = pack_lanes(hs, caps=CAPS, target_lane_len=64, seg_align=tb)
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(lanes.lanes, CAPS)
    )
    out0 = jax.tree_util.tree_map(
        jnp.asarray,
        S.empty_state(round_scan_len(lanes.n_histories), CAPS),
    )
    ev_tm, seg_tm, row_tm = lanes.time_major()
    _, want = replay_scan_packed(
        state0, out0, jnp.asarray(ev_tm), jnp.asarray(seg_tm),
        jnp.asarray(row_tm), types=type_signature(lanes.present_types),
    )
    _, got = replay_scan_pallas_packed(
        state0, out0, jnp.asarray(lanes.teb()),
        jnp.asarray(lanes.seg_end), jnp.asarray(lanes.out_row),
        CAPS, tb=tb, interpret=True, bt=1024,
    )
    _assert_state_equal(got, want)


@slow
def test_parity_echo():
    _parity([(f"wf-{i}", f"run-{i}", W.echo_history()) for i in range(7)])


@slow
def test_parity_workloads():
    rng = random.Random(7)
    hs = [
        ("wf-sig", "run-sig", W.signal_history(rng, min_events=20,
                                               max_events=60)),
        ("wf-tim", "run-tim", W.timer_storm_history(rng, depth=60,
                                                    fanout=3)),
        ("wf-ret", "run-ret", W.retry_deep_history(rng, depth=60)),
    ]
    _parity(hs)


@slow
def test_parity_fuzzed():
    """Fuzzer histories: random valid walks over every event type."""
    fz = HistoryFuzzer(seed=11, caps=CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=60))
        for i in range(24)
    ]
    _parity(hs)


@slow
def test_parity_fuzzed_version_bumps():
    """Failover-version jumps exercise the version-history ring."""
    fz = HistoryFuzzer(seed=3, caps=CAPS, version_bump_prob=0.4)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=48))
        for i in range(12)
    ]
    _parity(hs)


@slow
def test_parity_padding():
    """B not a multiple of bt and T not a multiple of tb both pad."""
    fz = HistoryFuzzer(seed=5, caps=CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=33))
        for i in range(3)
    ]
    _parity(hs, tb=7, bt=1024)


@slow
def test_parity_larger_tile():
    """bt=2048 (SL=16) exercises the multi-register tile path."""
    fz = HistoryFuzzer(seed=9, caps=CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=40))
        for i in range(6)
    ]
    _parity(hs, tb=8, bt=2048)


def test_parity_fast():
    """Minimal always-on parity case: tiny caps + fuzzed walks, via the
    field-major (teb) path with host-computed presence masks — the
    configuration the serving path uses."""
    fz = HistoryFuzzer(seed=2, caps=FAST_CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=12))
        for i in range(4)
    ]
    # pad the batch to bt so PackedHistories.presence returns real host
    # masks (None would fall back to the on-device computation)
    _parity(hs, tb=8, bt=1024, caps=FAST_CAPS, use_teb=True,
            pad_batch_to=1024)


def test_parity_narrow_int16():
    """The affine int16 event stream must produce a BIT-IDENTICAL state
    to the int32 path (the kernel reconstructs exact values as
    stored16 + base[c]); the kernel is stream-bound, so this is the
    per-tile throughput lever (r5)."""
    from cadence_tpu.ops.replay_pallas import (
        narrow_events_teb,
        replay_scan_pallas_teb,
    )

    fz = HistoryFuzzer(seed=5, caps=FAST_CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=12))
        for i in range(4)
    ]
    packed = pack_histories(hs, caps=FAST_CAPS, pad_batch_to=1024)
    b = packed.events.shape[0]
    ev_tm = jnp.asarray(
        np.ascontiguousarray(np.transpose(packed.events, (1, 0, 2)))
    )
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(b, FAST_CAPS)
    )
    want = replay_scan(state0, ev_tm)

    teb = packed.teb()
    narrowed = narrow_events_teb(teb)
    assert narrowed is not None, "TYPE/SLOT unexpectedly wide"
    ev16, base, wide_cols = narrowed
    assert ev16.dtype == np.int16
    # the fuzzed workload carries at least one hash-valued attribute
    # column, so the two-half wide path is exercised
    assert wide_cols, "expected at least one wide column"
    got = replay_scan_pallas_teb(
        state0, jnp.asarray(ev16), FAST_CAPS, tb=8, interpret=True,
        bt=1024, presence=packed.presence(1024), base=base,
        wide_cols=wide_cols,
    )
    _assert_state_equal(got, want)


def test_parity_narrow_int16_with_padding():
    """Narrow path through the B/T padding branch (pad fill must
    reconstruct EV_TYPE == -1 through the base)."""
    from cadence_tpu.ops.replay_pallas import (
        narrow_events_teb,
        replay_scan_pallas_teb,
    )

    fz = HistoryFuzzer(seed=6, caps=FAST_CAPS)
    hs = [
        (f"wf-{i}", f"run-{i}", fz.generate(target_events=10))
        for i in range(3)
    ]
    packed = pack_histories(hs, caps=FAST_CAPS)
    b = packed.events.shape[0]
    ev_tm = jnp.asarray(
        np.ascontiguousarray(np.transpose(packed.events, (1, 0, 2)))
    )
    state0 = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(b, FAST_CAPS)
    )
    want = replay_scan(state0, ev_tm)
    ev16, base, wide_cols = narrow_events_teb(packed.teb())
    got = replay_scan_pallas_teb(
        state0, jnp.asarray(ev16), FAST_CAPS, tb=8, interpret=True,
        bt=1024, base=base, wide_cols=wide_cols,
    )
    _assert_state_equal(got, want)


def test_narrow_wide_columns_split_exactly():
    """A column whose value span exceeds int16 is stored as two exact
    halves, not refused; TYPE/SLOT going wide refuses narrowing."""
    from cadence_tpu.ops.replay_pallas import _phys_map, narrow_events_teb

    ev = np.zeros((4, S.EV_N, 8), np.int32)
    ev[:, S.EV_TYPE, :] = 1
    ev[1, S.EV_A0, 0] = 70000        # span > 65000 -> wide
    ev[2, S.EV_A0, 1] = -123456789   # negative wide value
    ev16, base, wide_cols = narrow_events_teb(ev)
    assert S.EV_A0 in wide_cols
    phys, P = _phys_map(wide_cols)
    assert ev16.shape[1] == P
    p = phys[S.EV_A0]
    lo = ev16[:, p, :].astype(np.int64) & 0xFFFF
    rebuilt = (lo | (ev16[:, p + 1, :].astype(np.int64) << 16)).astype(
        np.int32)
    np.testing.assert_array_equal(rebuilt, ev[:, S.EV_A0, :])

    # TYPE wide -> refuse
    ev2 = np.zeros((2, S.EV_N, 4), np.int32)
    ev2[0, S.EV_TYPE, 0] = 100000
    assert narrow_events_teb(ev2) is None

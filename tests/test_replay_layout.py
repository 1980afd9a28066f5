"""The bulk replay's field-major event layout, made on the device.

On TPU ``replay_packed`` ships a ``PackedHistories``' batch-major events
as one [B, T·EV_N] matrix and lays them out with ``teb_of_rows``, in
place of the host scatter ``PackedHistories.teb()``. The two must agree
bit for bit, type pad included, and the device program must compile
once a (B, T), however many events a batch holds.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cadence_tpu.native import scatter_batch_major
from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import PackedHistories, pack_histories
from cadence_tpu.ops.replay import replay_packed, teb_of_rows
from cadence_tpu.testing import workloads as W
from cadence_tpu.utils.tracing import TRACER

RETRY_CAPS = S.Capacities(
    max_events=1024, max_activities=4, max_timers=2, max_children=2,
    max_request_cancels=2, max_signals_ext=2, max_version_items=2)


def _ragged(caps, batch, seed):
    """A PackedHistories of random event rows, as the packer holds them
    (``rows_concat`` kept, so ``teb()`` takes the C++ scatter), whose
    first row is empty and whose second fills all T steps."""
    rng = np.random.default_rng(seed)
    T = caps.max_events
    lengths = rng.integers(0, T + 1, batch).astype(np.int32)
    lengths[:2] = [0, T][:batch]
    rows = rng.integers(-2**31, 2**31, (int(lengths.sum()), S.EV_N),
                        dtype=np.int64).astype(np.int32)
    rows[:, S.EV_TYPE] = rng.integers(0, 40, len(rows))
    return PackedHistories(
        events=scatter_batch_major(rows, lengths, T), lengths=lengths,
        side=[None] * batch, caps=caps, rows_concat=rows)


def _histories(caps, n, depth, seed=3):
    rng = random.Random(seed)
    return pack_histories(
        [(f"wf-{i}", f"run-{i}",
          W.retry_deep_history(rng, depth=rng.randint(1, depth)))
         for i in range(n)], caps=caps)


def _on_device(packed):
    rows = jnp.asarray(packed.events.reshape(packed.batch, -1))
    return np.asarray(teb_of_rows(rows))


@pytest.mark.parametrize("make", [
    lambda: _ragged(RETRY_CAPS, 130, seed=1),       # B not a multiple of 128
    lambda: _ragged(S.Capacities(), 256, seed=2),
    lambda: _ragged(S.Capacities(), 1, seed=3),     # the one row is empty
    lambda: _histories(RETRY_CAPS, 5, 1000),
    lambda: _histories(S.Capacities(), 7, 300),
], ids=["retry_deep-ragged-130", "default-ragged-256", "default-one-empty",
        "retry_deep-histories", "default-histories"])
def test_device_layout_equals_the_host_scatter(make):
    packed = make()
    want = packed.teb()
    got = _on_device(packed)
    assert got.dtype == want.dtype == np.int32
    assert got.shape == want.shape == (
        packed.caps.max_events, S.EV_N, packed.batch)
    np.testing.assert_array_equal(got, want)
    # the pad past each row's end: type -1, every other field 0
    for b in np.flatnonzero(packed.lengths < packed.caps.max_events)[:4]:
        pad = got[packed.lengths[b]:, :, b]
        assert (pad[:, S.EV_TYPE] == -1).all()
        assert not np.delete(pad, S.EV_TYPE, axis=1).any()


def test_device_layout_compiles_once_a_batch_shape():
    """Batches of one (B, T) holding different numbers of events share
    one executable; only a new (B, T) compiles again."""
    caps = S.Capacities(max_events=48)
    a, b = _ragged(caps, 136, seed=4), _ragged(caps, 136, seed=5)
    assert a.lengths.sum() != b.lengths.sum()
    n0 = teb_of_rows._cache_size()
    np.testing.assert_array_equal(_on_device(a), a.teb())
    assert teb_of_rows._cache_size() == n0 + 1
    np.testing.assert_array_equal(_on_device(b), b.teb())
    assert teb_of_rows._cache_size() == n0 + 1
    _on_device(_ragged(caps, 137, seed=6))
    assert teb_of_rows._cache_size() == n0 + 2


def test_tpu_branch_hands_the_kernel_the_host_scatters_operand(
        request, monkeypatch):
    """``replay_packed``'s TPU branch, steered onto the CPU: the kernel
    (run interpreted) receives exactly ``teb()``, the answers equal the
    XLA scan's, and the layout span counts the bytes laid out on the
    device."""
    from cadence_tpu.ops import replay_pallas

    caps = S.Capacities(   # small tables: the kernel runs interpreted
        max_events=32, max_activities=2, max_timers=2, max_children=2,
        max_request_cancels=1, max_signals_ext=1, max_version_items=2)
    packed = _histories(caps, 6, 24, seed=8)
    want = replay_packed(packed)

    request.getfixturevalue("tpu_branch_on_cpu")
    interpreted = replay_pallas.replay_scan_pallas_teb
    seen = []

    def recorded(state, events, caps, **kw):
        seen.append(np.asarray(events))
        return interpreted(state, events, caps, **kw)

    monkeypatch.setattr(replay_pallas, "replay_scan_pallas_teb", recorded)
    TRACER.configure(sample_rate=0.0)
    TRACER.clear()
    try:
        with TRACER.trace("caller", sampled=True):
            got = replay_packed(packed)
        layouts = [s for s in TRACER.spans() if s.name == "replay.layout"]
    finally:
        TRACER.clear()
    (operand,) = seen
    np.testing.assert_array_equal(operand, packed.teb())
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)
    T = caps.max_events
    assert sum(s.tags["device_bytes"] for s in layouts) == (
        packed.batch * T * S.EV_N * 4)
    assert layouts[0].tags == {"bytes": 0, "device_bytes": 0}

"""Mesh-sharded + pipelined replay vs. single-device replay.

Runs on the 8-device virtual CPU mesh (conftest.py), the device-level
analog of the reference's onebox multi-node harness
(/root/reference/host/onebox.go)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import pack_histories
from cadence_tpu.ops.replay import replay_packed
from cadence_tpu.parallel import (
    make_mesh,
    ndc_snapshot_exchange,
    replay_packed_sharded,
    replay_pipelined,
)
from cadence_tpu.parallel.mesh import shard_spec
from cadence_tpu.testing.event_generator import HistoryFuzzer

CAPS = S.Capacities(max_events=64)


@pytest.fixture(scope="module")
def packed():
    fuzzer = HistoryFuzzer(seed=11, caps=CAPS)
    histories = [
        (f"wf-{i}", f"run-{i}", fuzzer.generate(target_events=30))
        for i in range(16)
    ]
    return pack_histories(histories, caps=CAPS, pad_batch_to=16)


@pytest.fixture(scope="module")
def single_device_final(packed):
    return replay_packed(packed)


def assert_states_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_batch_sharded_matches_single_device(packed, single_device_final):
    mesh = make_mesh(jax.devices()[:8], seq=1)
    final, tasks = replay_packed_sharded(packed, mesh)
    assert_states_equal(final, single_device_final)
    assert tasks.close_transfer.shape == (16,)


def test_2d_mesh_batch_sharding(packed, single_device_final):
    mesh = make_mesh(jax.devices()[:8], seq=2)
    final, _ = replay_packed_sharded(packed, mesh)
    assert_states_equal(final, single_device_final)


@pytest.mark.parametrize("seq,n_micro", [(2, 2), (4, 2), (8, 1)])
def test_pipelined_matches_single_device(
    packed, single_device_final, seq, n_micro
):
    mesh = make_mesh(jax.devices()[:8], seq=seq)
    init = jax.tree_util.tree_map(
        jnp.asarray, S.empty_state(packed.batch, CAPS)
    )
    piped = replay_pipelined(
        init, jnp.asarray(packed.time_major()), mesh, n_micro=n_micro
    )
    assert_states_equal(piped, single_device_final)


def test_ndc_snapshot_exchange(packed, single_device_final):
    mesh = make_mesh(jax.devices()[:8], seq=1)
    state = jax.tree_util.tree_map(
        lambda x: jax.device_put(jnp.asarray(x), shard_spec(mesh)),
        single_device_final,
    )
    digests, vh, vh_len, replayed, max_version = ndc_snapshot_exchange(
        state, mesh
    )
    digests = np.asarray(digests)
    assert digests.shape == (16, 6)
    # digest col 2 == next_event_id from exec_info
    np.testing.assert_array_equal(
        digests[:, 2], single_device_final.exec_info[:, S.X_NEXT_EVENT_ID]
    )
    assert int(replayed) == 16
    assert int(max_version) == int(
        single_device_final.exec_info[:, S.X_CUR_VERSION].max()
    )

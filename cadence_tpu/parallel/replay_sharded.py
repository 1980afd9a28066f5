"""Mesh-sharded batched replay + NDC snapshot exchange.

Batch (shard-axis) sharding is plain SPMD: the replay scan is elementwise
over B, so `jit` with NamedSharding on the batch axis compiles to fully
local compute — zero collectives, matching the reference's
shared-nothing shard design (each history shard is single-writer,
/root/reference/service/history/shardContext.go:44).

The one genuinely cross-device step is the NDC replication storm
(BASELINE config 5): after a batched rebuild, every participant needs the
others' rebuilt snapshot digests — the reference ships these via
cross-cluster RPC/Kafka (/root/reference/service/history/
replicatorQueueProcessor.go, replicationTaskFetcher.go:167); here they
ride ICI as one `all_gather` + `psum` inside `shard_map`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cadence_tpu.ops import schema as S
from cadence_tpu.ops.pack import PackedHistories
from cadence_tpu.ops.refresh import RefreshedTasks, refresh_tasks_device
from cadence_tpu.ops.replay import replay_scan

from .mesh import SHARD_AXIS, events_spec, shard_spec


def _state_specs(sharding: NamedSharding) -> S.StateTensors:
    return jax.tree_util.tree_map(lambda _: sharding, S.empty_state(1, S.Capacities()))


@functools.lru_cache(maxsize=8)
def replay_sharded_fn(mesh: Mesh):
    """jit(replay+refresh) with batch-axis shardings over ``mesh``: the
    sequential scan over time-major [T, B, EV_N] events, elementwise
    over B, so batch sharding adds zero collectives.

    Returns fn(state, events) -> (final_state, refreshed_tasks); both
    outputs stay sharded on device.
    """
    st_spec = shard_spec(mesh)
    ev_spec = events_spec(mesh)

    def step(state: S.StateTensors, events_tm: jnp.ndarray):
        final = replay_scan(state, events_tm)
        tasks = refresh_tasks_device(final)
        return final, tasks

    return jax.jit(
        step,
        in_shardings=(_state_specs(st_spec), ev_spec),
        # pytree-prefix: one sharding covers every leaf of each output
        out_shardings=(st_spec, st_spec),
        donate_argnums=(0,),
    )


def replay_packed_sharded(
    packed: PackedHistories,
    mesh: Mesh,
    initial: Optional[S.StateTensors] = None,
) -> Tuple[S.StateTensors, RefreshedTasks]:
    """Replay a packed batch across the mesh; returns numpy pytrees.

    The batch must be padded to a multiple of the shard-axis size
    (``pack_histories(pad_batch_to=...)``).
    """
    n_shard = mesh.shape[SHARD_AXIS]
    if packed.batch % n_shard != 0:
        raise ValueError(
            f"batch {packed.batch} not divisible by shard axis {n_shard}; "
            "pack with pad_batch_to"
        )
    state = initial if initial is not None else S.empty_state(packed.batch, packed.caps)
    fn = replay_sharded_fn(mesh)
    final, tasks = fn(
        jax.device_put(state, shard_spec(mesh))
        if initial is not None
        else jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), shard_spec(mesh)), state
        ),
        jax.device_put(jnp.asarray(packed.time_major()), events_spec(mesh)),
    )
    to_np = lambda t: jax.tree_util.tree_map(np.asarray, t)
    return to_np(final), to_np(tasks)


# Snapshot digest columns gathered in the NDC exchange: enough for the
# receiving side's version-check + conflict detection (the fields
# nDCHistoryReplicator.ApplyEvents consults before accepting events:
# last event id/version, state/close status —
# /root/reference/service/history/nDCHistoryReplicator.go:259-340).
_DIGEST_COLS = (
    S.X_STATE,
    S.X_CLOSE_STATUS,
    S.X_NEXT_EVENT_ID,
    S.X_LAST_EVENT_TASK_ID,
    S.X_CUR_VERSION,
    S.X_DEC_VERSION,
)


@functools.lru_cache(maxsize=8)
def _ndc_exchange_fn(mesh: Mesh):
    spec_in = P(SHARD_AXIS)

    def exchange(exec_info: jnp.ndarray, vh_items: jnp.ndarray, vh_len: jnp.ndarray):
        digest = jnp.stack([exec_info[:, c] for c in _DIGEST_COLS], axis=-1)
        # every device sees every shard's digest + version histories
        all_digest = jax.lax.all_gather(digest, SHARD_AXIS, tiled=True)
        all_vh = jax.lax.all_gather(vh_items, SHARD_AXIS, tiled=True)
        all_vh_len = jax.lax.all_gather(vh_len, SHARD_AXIS, tiled=True)
        # global counters: replayed workflows + max failover version — the
        # cluster-metadata aggregate the replication storm needs. A row
        # is REPLAYED iff its history actually started (start_ts set):
        # X_STATE >= 0 is true for zero-initialized padding rows too
        replayed = jax.lax.psum(
            jnp.sum(exec_info[:, S.X_START_TS] > 0), SHARD_AXIS
        )
        max_version = jax.lax.pmax(
            jnp.max(exec_info[:, S.X_CUR_VERSION]), SHARD_AXIS
        )
        return all_digest, all_vh, all_vh_len, replayed, max_version

    return jax.jit(
        jax.shard_map(
            exchange,
            mesh=mesh,
            in_specs=(spec_in, spec_in, spec_in),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )
    )


def ndc_snapshot_exchange(state: S.StateTensors, mesh: Mesh):
    """All-gather rebuilt snapshot digests + psum storm counters over ICI.

    Returns (digests [B, len(_DIGEST_COLS)], vh_items [B, V, 2],
    vh_len [B], replayed_count, max_version) replicated on every device.
    """
    fn = _ndc_exchange_fn(mesh)
    return fn(state.exec_info, state.vh_items, state.vh_len)

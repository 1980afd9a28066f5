"""Time-pipelined replay for deep histories (sequence parallelism).

A workflow-history replay is an inherently sequential scan over time
(the reference replays strictly per-workflow, batch after batch:
/root/reference/service/history/nDCStateRebuilder.go:128-137). The FSM
transition is not associative, so the time axis cannot be parallelized
by a prefix-scan — but it CAN be pipelined: split T into contiguous
chunks over the ``seq`` mesh axis, split the batch into micro-batches,
and hand each micro-batch's carry state from device i to device i+1 over
ICI (`ppermute`) as soon as chunk i is done. With M micro-batches and S
seq devices, utilization is M/(M+S-1) — the classic GPipe schedule,
applied to FSM simulation instead of layers.

This is the TPU answer to the reference's paginated long-history reads
(ReadHistoryBranchByBatch, /root/reference/common/persistence/
dataInterfaces.go:1552-1556): a 64k-event history that would blow one
device's scan-depth/HBM budget streams through S devices at 1/S of the
per-device depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from cadence_tpu.ops import schema as S
from cadence_tpu.ops.replay import replay_scan

from .mesh import SEQ_AXIS, SHARD_AXIS


@functools.lru_cache(maxsize=8)
def _pipelined_fn(mesh: Mesh, n_micro: int):
    n_seq = mesh.shape[SEQ_AXIS]

    def pipe(events_local: jnp.ndarray, init_local: S.StateTensors):
        # events_local: [T/n_seq, B_local, EV_N]; init_local: [B_local, ...]
        b_local = events_local.shape[1]
        if b_local % n_micro != 0:
            raise ValueError(
                f"local batch {b_local} not divisible by n_micro={n_micro}"
            )
        mb = b_local // n_micro
        idx = lax.axis_index(SEQ_AXIS)
        is_first = idx == 0
        is_last = idx == n_seq - 1

        to_micro = lambda x: x.reshape((n_micro, mb) + x.shape[1:])
        init_mb = jax.tree_util.tree_map(to_micro, init_local)
        out0 = jax.tree_util.tree_map(jnp.zeros_like, init_mb)
        recv0 = jax.tree_util.tree_map(lambda x: x[0], init_mb)
        # forward ring, no wraparound: the last stage's output exits the
        # pipeline instead of feeding stage 0
        perm = tuple((p, p + 1) for p in range(n_seq - 1))

        def body(carry, k):
            recv, out = carry
            j = k - idx                      # micro-batch this stage works on
            active = (j >= 0) & (j < n_micro)
            jc = jnp.clip(j, 0, n_micro - 1)
            st_in = jax.tree_util.tree_map(
                lambda a, r: jnp.where(is_first, a[jc], r), init_mb, recv
            )
            ev = lax.dynamic_slice_in_dim(events_local, jc * mb, mb, axis=1)
            st_out = replay_scan(st_in, ev)
            recv_next = jax.tree_util.tree_map(
                lambda x: lax.ppermute(x, SEQ_AXIS, perm), st_out
            )
            out = jax.tree_util.tree_map(
                lambda o, s: o.at[jc].set(jnp.where(active & is_last, s, o[jc])),
                out,
                st_out,
            )
            return (recv_next, out), None

        n_steps = n_micro + n_seq - 1
        (_, out), _ = lax.scan(body, (recv0, out0), jnp.arange(n_steps))
        # only the last stage holds real results; psum replicates them
        out = jax.tree_util.tree_map(
            lambda x: lax.psum(jnp.where(is_last, x, jnp.zeros_like(x)), SEQ_AXIS),
            out,
        )
        from_micro = lambda x: x.reshape((b_local,) + x.shape[2:])
        return jax.tree_util.tree_map(from_micro, out)

    state_spec = jax.tree_util.tree_map(
        lambda _: P(SHARD_AXIS), S.empty_state(1, S.Capacities())
    )
    return jax.jit(
        jax.shard_map(
            pipe,
            mesh=mesh,
            in_specs=(P(SEQ_AXIS, SHARD_AXIS), state_spec),
            out_specs=state_spec,
            check_vma=False,
        )
    )


def replay_pipelined(
    state: S.StateTensors,
    events_tm: jnp.ndarray,
    mesh: Mesh,
    n_micro: int = 0,
) -> S.StateTensors:
    """Pipelined replay: T sharded over ``seq``, B over ``shard``.

    Requires T % n_seq == 0 and (B / n_shard) % n_micro == 0.
    ``n_micro`` defaults to the seq-axis size (balanced bubble).
    """
    n_micro = n_micro or mesh.shape[SEQ_AXIS]
    return _pipelined_fn(mesh, n_micro)(events_tm, state)

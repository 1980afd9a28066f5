"""Mesh construction for the replay fabric.

Two logical axes:

  * ``shard`` — the batch axis. Cadence shards (workflowID % numShards,
    /root/reference/common/util.go:249-251) are rows of the event tensor;
    sharding them over devices is the data-parallel dimension.
  * ``seq``   — the time axis for pipelined long-history replay
    (cadence_tpu/parallel/pipeline.py). The reference's analog is the
    paginated history-branch read + strictly sequential per-workflow
    replay (/root/reference/service/history/nDCStateRebuilder.go:103-137).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"
SEQ_AXIS = "seq"


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    seq: int = 1,
) -> Mesh:
    """Build a ("shard", "seq") mesh over ``devices``.

    ``seq`` devices are dedicated to the time-pipeline; the rest to the
    batch axis. seq=1 (default) is pure batch sharding.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % seq != 0:
        raise ValueError(f"{n} devices not divisible by seq={seq}")
    arr = np.array(devices).reshape(n // seq, seq)
    return Mesh(arr, (SHARD_AXIS, SEQ_AXIS))


def shard_spec(mesh: Mesh) -> NamedSharding:
    """Sharding for batch-leading state arrays: [B, ...] split on shard."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def events_spec(mesh: Mesh) -> NamedSharding:
    """Sharding for time-major event tensors: [T, B, EV_N], B split."""
    return NamedSharding(mesh, P(None, SHARD_AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())

"""Compile the main-path kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2). Interpret-mode
parity tests cannot see what Mosaic refuses (unsupported relayouts,
VMEM over budget); these compiles can, at the widths the chip smoke
(chip_smoke.py) and the bench run. Nothing runs: shapes only.

The topology is described inside a module fixture (never at import, in
a skipif or in conftest.py): only one process may hold the TPU library,
and every xdist worker imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cadence_tpu.ops import schema as S

B, T = 8192, 1024

RETRY_CAPS = S.Capacities(
    max_events=1024, max_activities=4, max_timers=2, max_children=2,
    max_request_cancels=2, max_signals_ext=2, max_version_items=2)
ECHO_CAPS = S.Capacities(
    max_events=16, max_activities=2, max_timers=2, max_children=2,
    max_request_cancels=2, max_signals_ext=2, max_version_items=2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from cadence_tpu.parallel import make_mesh

    return make_mesh(topo.devices)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state_sds(b, caps, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), S.empty_state(b, caps))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_replay_teb_int32_retry_deep(one_chip):
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_teb

    def f(state, ev, presence):
        return replay_scan_pallas_teb(state, ev, RETRY_CAPS,
                                      interpret=False, bt=B,
                                      presence=presence)

    text = _compile(
        f, _state_sds(2 * B, RETRY_CAPS, one_chip),
        _sds((T, S.EV_N, 2 * B), jnp.int32, one_chip),
        _sds((2, T, 4), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_replay_teb_laid_out_on_device_bulk(one_chip):
    """The bulk replay's operand at its real size: 16,384 rows of 1,024
    steps shipped batch-major, laid out field-major by ``teb_of_rows``,
    alone (the program each bulk call runs before the kernel) and
    feeding the teb kernel at the tile ``replay_packed`` picks."""
    from cadence_tpu.ops.replay import teb_of_rows
    from cadence_tpu.ops.replay_pallas import (
        BT, fit_tile, replay_scan_pallas_teb)

    rows_b = 2 * B
    rows = _sds((rows_b, T * S.EV_N), jnp.int32, one_chip)
    out = teb_of_rows.lower(rows).compile().out_info
    assert (out.shape, out.dtype) == ((T, S.EV_N, rows_b), jnp.int32)
    bt, _ = fit_tile(RETRY_CAPS, BT)

    def f(state, rows, presence):
        return replay_scan_pallas_teb(state, teb_of_rows(rows), RETRY_CAPS,
                                      interpret=False, bt=bt,
                                      presence=presence)

    text = _compile(
        f, _state_sds(rows_b, RETRY_CAPS, one_chip), rows,
        _sds((rows_b // bt, T, 4), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_replay_teb_int16_narrow_stream(one_chip):
    from cadence_tpu.ops.replay_pallas import _phys_map, replay_scan_pallas_teb

    wide = (S.EV_TS, S.EV_A0)
    _, width = _phys_map(wide)

    def f(state, ev, base):
        return replay_scan_pallas_teb(state, ev, RETRY_CAPS,
                                      interpret=False, bt=B, base=base,
                                      wide_cols=wide)

    text = _compile(
        f, _state_sds(B, RETRY_CAPS, one_chip),
        _sds((T, width, B), jnp.int16, one_chip),
        _sds((S.EV_N,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_replay_packed_echo_lanes(one_chip):
    from cadence_tpu.ops.replay_pallas import replay_scan_pallas_packed

    lanes, lane_len, tb = 8192, 256, 16
    n_out = lanes * (lane_len // tb)

    def f(state, out0, ev, seg_end, out_row):
        return replay_scan_pallas_packed(state, out0, ev, seg_end, out_row,
                                         ECHO_CAPS, tb=tb, interpret=False)

    text = _compile(
        f, _state_sds(lanes, ECHO_CAPS, one_chip),
        _state_sds(n_out, ECHO_CAPS, one_chip),
        _sds((lane_len, S.EV_N, lanes), jnp.int32, one_chip),
        _sds((lanes, lane_len), jnp.bool_, one_chip),
        _sds((lanes, lane_len), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("activities", [32, 48, 384])
def test_replay_packed_storm_buckets(one_chip, activities):
    """The rebuild storm's packed kernel at the default caps and at wide
    capacity buckets up to the widest: a narrow int16 stream of a few
    dozen lanes, whose state fits the kernel's VMEM at the tile
    ``fit_tile`` picks (single-buffered at the widest)."""
    from cadence_tpu.ops.replay_pallas import (
        _phys_map, fit_tile, replay_scan_pallas_packed)

    caps = S.Capacities(max_activities=activities)
    lanes, lane_len, tb, n_out = 24, 1024, 16, 48
    wide = (S.EV_TS, S.EV_A0)
    _, width = _phys_map(wide)
    assert fit_tile(caps, ev_bytes=2 * width)[0] >= 1024

    # the lane padding reads the base on the host, as the dispatcher
    # passes it
    base = np.zeros((S.EV_N,), np.int32)

    def f(state, out0, ev, seg_end, out_row):
        return replay_scan_pallas_packed(state, out0, ev, seg_end, out_row,
                                         caps, tb=tb, interpret=False,
                                         base=base, wide_cols=wide)

    text = _compile(
        f, _state_sds(lanes, caps, one_chip),
        _state_sds(n_out, caps, one_chip),
        _sds((lane_len, width, lanes), jnp.int16, one_chip),
        _sds((lanes, lane_len), jnp.bool_, one_chip),
        _sds((lanes, lane_len), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_replay_sharded_is_shard_local(mesh4):
    from cadence_tpu.parallel import replay_sharded_fn
    from cadence_tpu.parallel.mesh import events_spec, shard_spec

    caps = RETRY_CAPS
    fn = replay_sharded_fn(mesh4)
    text = fn.lower(
        _state_sds(4 * B, caps, shard_spec(mesh4)),
        _sds((T, 4 * B, S.EV_N), jnp.int32, events_spec(mesh4)),
    ).compile().as_text()
    # each device scans its own quarter of the batch, shared-nothing
    assert f"s32[{T},{B},{S.EV_N}]" in text
    for collective in ("all-gather", "all-reduce", "collective-permute"):
        assert collective not in text


def test_ndc_exchange_collectives(mesh4):
    from cadence_tpu.parallel.replay_sharded import _ndc_exchange_fn

    spec = NamedSharding(mesh4, P("shard"))
    v = S.Capacities().max_version_items
    text = _ndc_exchange_fn(mesh4).lower(
        _sds((4 * B, S.X_N), jnp.int32, spec),
        _sds((4 * B, v, 2), jnp.int32, spec),
        _sds((4 * B,), jnp.int32, spec),
    ).compile().as_text()
    assert "all-gather" in text and "all-reduce" in text
    assert np.prod(mesh4.devices.shape) == 4

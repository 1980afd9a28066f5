"""Test harness config: force an 8-device virtual CPU mesh before jax loads.

Mirrors the reference's onebox strategy (multi-"node" testing without a real
cluster, /root/reference/host/onebox.go) at the device level: multi-chip
sharding is validated on virtual CPU devices.
"""

import os
import sys

# Tests always run on the virtual 8-device CPU mesh. The env contract
# lives in testing/environment.py (the reference environment/env.go
# equivalent).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cadence_tpu.testing.environment import setup_env  # noqa: E402

setup_env()

import jax  # noqa: E402

# Pin through the config as well: if jax was imported before setup_env
# ran, it has already read JAX_PLATFORMS.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (interpret-mode Pallas parity etc.)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (interpret-mode kernels); opt in with --runslow",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection recovery suite (tests/test_chaos_recovery"
        ".py + tests/test_failover_drills.py); runs in tier-1, selectable "
        "via -m chaos (scripts/run_chaos.sh seeds CHAOS_SEED sweeps)",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: opt in with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def jax_devices():
    return jax.devices()


@pytest.fixture
def tpu_branch_on_cpu(monkeypatch):
    """Steer the replay paths' TPU branches onto the CPU: the kernel
    choice (``replay_pallas.on_tpu``) answers TPU, and the Pallas
    kernels, which compile only for a TPU, run interpreted, the
    dispatcher's at its smallest tiles (1,024 lanes, 8 steps). Callers
    reach the kernels through the module at call time, so the patches
    hold for the test's duration."""
    import functools

    from cadence_tpu.ops import replay_pallas
    from cadence_tpu.ops.dispatch import DeviceDispatcher

    init = DeviceDispatcher.__init__

    def small_tiles(self, *a, **k):
        init(self, *a, **dict(k, bt=1024, tb=8))

    monkeypatch.setattr(DeviceDispatcher, "__init__", small_tiles)
    monkeypatch.setattr(replay_pallas, "on_tpu", lambda: True)
    for name in ("replay_scan_pallas_teb", "replay_scan_pallas_packed"):
        kernel = getattr(replay_pallas, name)

        @functools.wraps(kernel)
        def interpreted(*a, _kernel=kernel, **kw):
            return _kernel(*a, **dict(kw, interpret=True))

        monkeypatch.setattr(replay_pallas, name, interpreted)

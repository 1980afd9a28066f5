"""Test/deploy environment resolution.

Reference: environment/env.go — every integration harness resolves its
backend endpoints (Cassandra/MySQL/Kafka/ES seeds + ports) from env
vars with local defaults, so the same suite runs against a laptop, a
docker-compose network, or CI. This build's equivalents:

  CADENCE_TPU_STORE          "memory" | "sqlite"        (default memory)
  CADENCE_TPU_SQLITE_PATH    sqlite file                (default tmp)
  CADENCE_TPU_NUM_SHARDS     history shard count        (default 4)
  CADENCE_TPU_JAX_PLATFORM   "cpu" | "tpu"              (default cpu —
                             tests always pin the virtual CPU mesh)
  CADENCE_TPU_MESH_DEVICES   virtual device count       (default 8)
  CADENCE_TPU_BIND_IP        service bind address       (default 127.0.0.1)

``setup_env()`` applies the JAX knobs exactly the way tests/conftest.py
does (it is the shared implementation), so standalone harnesses and
the docker entrypoint agree with the test suite.
"""

from __future__ import annotations

import os
import tempfile

LOCALHOST = "127.0.0.1"

STORE = "CADENCE_TPU_STORE"
SQLITE_PATH = "CADENCE_TPU_SQLITE_PATH"
NUM_SHARDS = "CADENCE_TPU_NUM_SHARDS"
JAX_PLATFORM = "CADENCE_TPU_JAX_PLATFORM"
MESH_DEVICES = "CADENCE_TPU_MESH_DEVICES"
BIND_IP = "CADENCE_TPU_BIND_IP"


def store() -> str:
    return os.environ.get(STORE, "memory")


def sqlite_path() -> str:
    path = os.environ.get(SQLITE_PATH, "")
    if path:
        return path
    return os.path.join(tempfile.gettempdir(), "cadence_tpu.db")


def num_shards() -> int:
    return int(os.environ.get(NUM_SHARDS, "4"))


def jax_platform() -> str:
    return os.environ.get(JAX_PLATFORM, "cpu")


def mesh_devices() -> int:
    return int(os.environ.get(MESH_DEVICES, "8"))


def bind_ip() -> str:
    return os.environ.get(BIND_IP, LOCALHOST)


def create_bundle():
    """A persistence bundle per the env (env.go's backend selection)."""
    if store() == "sqlite":
        from cadence_tpu.runtime.persistence.sqlite import (
            create_sqlite_bundle,
        )

        return create_sqlite_bundle(sqlite_path())
    from cadence_tpu.runtime.persistence.memory import create_memory_bundle

    return create_memory_bundle()


def setup_env(environ=os.environ) -> None:
    """Pin JAX to the configured platform/mesh BEFORE jax first loads.

    cpu (the default, and what tests/conftest.py applies): force the
    virtual ``mesh_devices()``-device CPU mesh. tpu: leave the platform
    alone so the real chip resolves.
    """
    if jax_platform() != "cpu":
        return
    environ["JAX_PLATFORMS"] = "cpu"
    flag = f"--xla_force_host_platform_device_count={mesh_devices()}"
    xla_flags = environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        environ["XLA_FLAGS"] = (xla_flags + " " + flag).strip()

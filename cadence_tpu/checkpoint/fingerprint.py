"""Transition-function fingerprint for checkpoint invalidation.

A checkpointed carry is only resumable if the transition semantics that
produced it are the transition semantics that will consume it. The
fingerprint hashes the source of every module that defines those
semantics — the tensor schema (state layout), the packer (event-row
encoding + slot assignment), and both kernels — so ANY change to the
replay contract flips the fingerprint and every stored checkpoint reads
as stale (full replay, never a silently-wrong resume).

Hashing file bytes via ``find_spec`` (not ``inspect.getsource`` on
imported modules) keeps this importable without pulling in jax/pallas.
"""

from __future__ import annotations

import hashlib
import importlib.util

# the replay-contract surface: schema (layout), pack (encoding + slots),
# kernels (transition semantics). Order is part of the fingerprint.
_CONTRACT_MODULES = (
    "cadence_tpu.ops.schema",
    "cadence_tpu.ops.pack",
    "cadence_tpu.ops.replay",
    "cadence_tpu.ops.replay_pallas",
)

_FINGERPRINT: str = ""


def transition_fingerprint() -> str:
    """Hex digest (16 chars) of the replay contract's source."""
    global _FINGERPRINT
    if not _FINGERPRINT:
        h = hashlib.sha256()
        for name in _CONTRACT_MODULES:
            spec = importlib.util.find_spec(name)
            if spec is None or spec.origin is None:
                raise RuntimeError(f"cannot locate module {name}")
            with open(spec.origin, "rb") as f:
                h.update(f.read())
            h.update(b"\x00")
        _FINGERPRINT = h.hexdigest()[:16]
    return _FINGERPRINT

"""ctypes bindings for the C++ packing/transport sidecar.

Builds native/sidecar.cpp on first use (g++ -O3 -shared, cached in the
source tree next to the .cpp as ``libctsidecar-<sha12>.so``, keyed on
the source's contents) and exposes:

- scatter_time_major / scatter_batch_major — fused pad+layout of ragged
  event rows into the dense tensors the replay scan consumes
- fnv1a32_batch — bulk id hashing for slot keys
- tensor_compress / tensor_decompress — varint+zigzag delta codec for
  shipping packed tensors across hosts

Every entry point has a pure-Python/numpy fallback (`HAVE_NATIVE` tells
which path is live), and the test suite runs both differentially.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
    "native", "sidecar.cpp",
)


def _lib_path() -> str:
    """The library built from THIS sidecar.cpp: the name carries a hash
    of the source, so a .so built from other source (a stale build, or
    one copied in from another tree) is never loaded."""
    with open(_SRC, "rb") as f:
        sha = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(os.path.dirname(_SRC), f"libctsidecar-{sha}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
HAVE_NATIVE = False


def _build() -> Optional[str]:
    lib_path = _lib_path()
    if os.path.exists(lib_path):
        return lib_path
    # compile to a temp path and rename atomically: a killed compile or
    # two processes racing must never leave a half-written .so that
    # every later process accepts and fails to dlopen
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, lib_path)
        return lib_path
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


_load_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, HAVE_NATIVE, _load_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            # don't re-run a 120s compile attempt on EVERY call while
            # holding the module lock; the fallback path serves
            return None
        path = _build()
        if path is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            _load_failed = True
            return None
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.ct_scatter_time_major.argtypes = [
            i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, i32p,
        ]
        lib.ct_scatter_batch_major.argtypes = (
            lib.ct_scatter_time_major.argtypes
        )
        lib.ct_scatter_teb.argtypes = lib.ct_scatter_time_major.argtypes
        lib.ct_presence.argtypes = [
            i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, i32p,
        ]
        lib.ct_fnv1a32_batch.argtypes = [
            ctypes.c_char_p, i64p, ctypes.c_int64, u32p,
        ]
        lib.ct_compress_bound.argtypes = [ctypes.c_int64]
        lib.ct_compress_bound.restype = ctypes.c_int64
        lib.ct_tensor_compress.argtypes = [i32p, ctypes.c_int64, u8p]
        lib.ct_tensor_compress.restype = ctypes.c_int64
        lib.ct_tensor_decompress.argtypes = [u8p, ctypes.c_int64, i32p]
        lib.ct_tensor_decompress.restype = ctypes.c_int64
        lib.ct_tensor_peek_count.argtypes = [u8p, ctypes.c_int64]
        lib.ct_tensor_peek_count.restype = ctypes.c_int64
        lib.ct_replay_sequential.argtypes = (
            [i32p, i64p] + [ctypes.c_int64] * 8 + [i32p] * 8
        )
        _lib = lib
        HAVE_NATIVE = True
        return lib


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


# -- scatter ---------------------------------------------------------------


def _check_scatter_args(
    rows: np.ndarray, lengths: np.ndarray, max_events: int
) -> None:
    """Bounds-check the public scatter API before handing buffers to C.

    The native scatter trusts its inputs (it clamps per-workflow copies
    to ``max_events`` but cannot detect a lengths/rows mismatch), so
    reject anything inconsistent here, matching the numpy fallback's
    broadcast errors.
    """
    if lengths.size and int(lengths.min()) < 0:
        raise ValueError("scatter: negative workflow length")
    if lengths.size and int(lengths.max()) > max_events:
        raise ValueError(
            f"scatter: workflow length {int(lengths.max())} exceeds "
            f"max_events={max_events}"
        )
    n_rows = rows.shape[0] if rows.ndim == 2 else 0
    if int(lengths.sum()) != n_rows:
        raise ValueError(
            f"scatter: sum(lengths)={int(lengths.sum())} != rows={n_rows}"
        )


def scatter_time_major(
    rows: np.ndarray, lengths: np.ndarray, max_events: int,
    type_pad: int = -1, force_python: bool = False,
) -> np.ndarray:
    """[sum(lengths), E] rows + [B] lengths → [T, B, E] dense tensor."""
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    lengths64 = np.ascontiguousarray(lengths, dtype=np.int64)
    _check_scatter_args(rows, lengths64, max_events)
    batch = len(lengths64)
    ev_n = rows.shape[1] if rows.ndim == 2 else 0
    lib = None if force_python else _load()
    if lib is not None and ev_n and rows.size:
        out = np.empty((max_events, batch, ev_n), dtype=np.int32)
        lib.ct_scatter_time_major(
            _i32p(rows), _i64p(lengths64), batch, ev_n, max_events,
            type_pad, _i32p(out),
        )
        return out
    # numpy fallback
    out = np.zeros((max_events, batch, ev_n), dtype=np.int32)
    if ev_n:
        out[:, :, 0] = type_pad
    start = 0
    for b, n in enumerate(lengths64):
        out[:n, b, :] = rows[start : start + n]
        start += n
    return out


def scatter_teb(
    rows: np.ndarray, lengths: np.ndarray, max_events: int,
    type_pad: int = -1, force_python: bool = False,
) -> np.ndarray:
    """[sum(lengths), E] rows + [B] lengths → [T, E, B] field-major tensor
    (the Pallas replay kernel's native operand layout)."""
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    lengths64 = np.ascontiguousarray(lengths, dtype=np.int64)
    _check_scatter_args(rows, lengths64, max_events)
    batch = len(lengths64)
    ev_n = rows.shape[1] if rows.ndim == 2 else 0
    lib = None if force_python else _load()
    if lib is not None and ev_n and rows.size:
        out = np.empty((max_events, ev_n, batch), dtype=np.int32)
        lib.ct_scatter_teb(
            _i32p(rows), _i64p(lengths64), batch, ev_n, max_events,
            type_pad, _i32p(out),
        )
        return out
    # numpy fallback
    out = np.zeros((max_events, ev_n, batch), dtype=np.int32)
    if ev_n:
        out[:, 0, :] = type_pad
    start = 0
    for b, n in enumerate(lengths64):
        out[:n, :, b] = rows[start : start + n]
        start += n
    return out


def presence_masks(
    rows: np.ndarray, lengths: np.ndarray, max_events: int, bt: int,
    force_python: bool = False,
) -> np.ndarray:
    """Per-(batch-tile, step) presence bitmasks for the Pallas replay
    kernel: [B/bt, T, 4] int32 (words 0-1 event-type bits, word 2 slot
    bits, word 3 zero). B must be a multiple of ``bt``."""
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    lengths64 = np.ascontiguousarray(lengths, dtype=np.int64)
    _check_scatter_args(rows, lengths64, max_events)
    batch = len(lengths64)
    if batch % bt:
        raise ValueError(f"presence: batch={batch} not a multiple of bt={bt}")
    ev_n = rows.shape[1] if rows.ndim == 2 else 0
    if rows.size and ev_n != 16:  # schema.EV_N; ct_presence reads cols 0 and 7
        raise ValueError(f"presence: ev_n={ev_n} != schema EV_N=16")
    lib = None if force_python else _load()
    if lib is not None and ev_n and rows.size:
        out = np.empty((batch // bt, max_events, 4), dtype=np.int32)
        lib.ct_presence(
            _i32p(rows), _i64p(lengths64), batch, ev_n, max_events, bt,
            _i32p(out),
        )
        return out
    # numpy fallback
    out = np.zeros((batch // bt, max_events, 4), dtype=np.int32)
    start = 0
    for b, n in enumerate(lengths64):
        n = min(int(n), max_events)
        g = b // bt
        ets = rows[start : start + n, 0]
        slots = rows[start : start + n, 7]
        ts = np.arange(n)
        ok = ets >= 0
        for w in (0, 1):
            sel = ok & (ets // 32 == w)
            np.bitwise_or.at(out[g, :, w], ts[sel],
                             np.int32(1) << (ets[sel] % 32))
        sel = ok & (slots >= 0)
        np.bitwise_or.at(out[g, :, 2], ts[sel],
                         np.int32(1) << (slots[sel] % 32))
        start += int(lengths64[b])
    return out


def scatter_batch_major(
    rows: np.ndarray, lengths: np.ndarray, max_events: int,
    type_pad: int = -1, force_python: bool = False,
) -> np.ndarray:
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    lengths64 = np.ascontiguousarray(lengths, dtype=np.int64)
    _check_scatter_args(rows, lengths64, max_events)
    batch = len(lengths64)
    ev_n = rows.shape[1] if rows.ndim == 2 else 0
    lib = None if force_python else _load()
    if lib is not None and ev_n and rows.size:
        out = np.empty((batch, max_events, ev_n), dtype=np.int32)
        lib.ct_scatter_batch_major(
            _i32p(rows), _i64p(lengths64), batch, ev_n, max_events,
            type_pad, _i32p(out),
        )
        return out
    out = np.zeros((batch, max_events, ev_n), dtype=np.int32)
    if ev_n:
        out[:, :, 0] = type_pad
    start = 0
    for b, n in enumerate(lengths64):
        out[b, :n, :] = rows[start : start + n]
        start += n
    return out


# -- hashing ---------------------------------------------------------------


def fnv1a32_batch(strings, force_python: bool = False) -> np.ndarray:
    """hash31 for a batch of strings (cadence_tpu.utils.hashing)."""
    lib = None if force_python else _load()
    if lib is None:
        from cadence_tpu.utils.hashing import hash31

        return np.array([hash31(s) for s in strings], dtype=np.uint32)
    encoded = [s.encode() for s in strings]
    data = b"".join(encoded)
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    out = np.empty(len(encoded), dtype=np.uint32)
    lib.ct_fnv1a32_batch(
        data, _i64p(offsets), len(encoded),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out


# -- transport codec -------------------------------------------------------


def tensor_compress(
    tensor: np.ndarray, force_python: bool = False
) -> Tuple[bytes, Tuple[int, ...]]:
    """int32 tensor → (blob, shape). Delta+zigzag+varint."""
    flat = np.ascontiguousarray(tensor, dtype=np.int32).reshape(-1)
    lib = None if force_python else _load()
    if lib is None:
        return _py_compress(flat), tensor.shape
    bound = lib.ct_compress_bound(flat.size)
    buf = np.empty(bound, dtype=np.uint8)
    n = lib.ct_tensor_compress(
        _i32p(flat), flat.size,
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return bytes(buf[:n]), tensor.shape


def tensor_decompress(
    blob: bytes, shape: Tuple[int, ...], force_python: bool = False
) -> np.ndarray:
    expected = int(np.prod(shape)) if shape else 1
    lib = None if force_python else _load()
    if lib is None:
        return _py_decompress(blob, expected).reshape(shape)
    raw = np.frombuffer(blob, dtype=np.uint8)
    u8 = raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    count = lib.ct_tensor_peek_count(u8, len(blob))
    if count < 0 or count != expected:
        raise ValueError(
            f"tensor_decompress: corrupt blob (count={count}, "
            f"expected {expected})"
        )
    out = np.empty(count, dtype=np.int32)
    decoded = lib.ct_tensor_decompress(u8, len(blob), _i32p(out))
    if decoded != count:
        raise ValueError(
            f"tensor_decompress: truncated blob (decoded {decoded} of "
            f"{count})"
        )
    return out.reshape(shape)


def _py_compress(flat: np.ndarray) -> bytes:
    out = bytearray()

    def put(v: int) -> None:
        while v >= 0x80:
            out.append((v & 0x7F) | 0x80)
            v >>= 7
        out.append(v)

    put(flat.size)
    prev = 0
    for v in flat.tolist():
        # wrap the delta to int32 first: Python ints are unbounded, so
        # a raw (d >> 31) sign probe is wrong for |d| >= 2^31 (e.g. a
        # -1 pad followed by a 2^31-1 hash31 slot key) and would break
        # encode/decode symmetry with the native codec
        d = ((v - prev + 0x80000000) & 0xFFFFFFFF) - 0x80000000
        prev = v
        put(((d << 1) ^ (d >> 31)) & 0xFFFFFFFF)
    return bytes(out)


def _py_decompress(blob: bytes, expected: Optional[int] = None) -> np.ndarray:
    pos = 0

    def get() -> int:
        nonlocal pos
        shift = 0
        v = 0
        while True:
            if pos >= len(blob) or shift > 28:
                raise ValueError("tensor_decompress: corrupt blob")
            b = blob[pos]
            pos += 1
            v |= (b & 0x7F) << shift
            if not (b & 0x80):
                return v & 0xFFFFFFFF
            shift += 7

    n = get()
    if expected is not None and n != expected:
        # validate the header BEFORE allocating: a forged count would
        # otherwise trigger a giant np.empty from a few corrupt bytes
        raise ValueError(
            f"tensor_decompress: corrupt blob (count={n}, "
            f"expected {expected})"
        )
    out = np.empty(n, dtype=np.int32)
    prev = 0
    for i in range(n):
        z = get()
        d = (z >> 1) ^ -(z & 1)
        prev = (prev + d) & 0xFFFFFFFF
        if prev >= 0x80000000:
            prev -= 0x100000000
        out[i] = prev
    return out


# -- sequential replayer (compiled-host baseline) --------------------------


def replay_sequential(packed, caps=None):
    """Replay packed histories with the C++ sequential loop.

    The compiled-host baseline for bench.py: identical transition
    semantics to the TPU kernel (ops/replay.py) applied one workflow,
    one event at a time — the shape of the reference's Go
    stateBuilder.applyEvents loop (service/history/stateBuilder.go:112-613).
    Returns StateTensors (numpy). Requires the native sidecar; raises
    RuntimeError when g++ is unavailable (the baseline must be compiled
    code, never interpreted Python).
    """
    from cadence_tpu.ops import schema as S

    lib = _load()
    if lib is None:
        raise RuntimeError("native sidecar unavailable: no compiled baseline")
    caps = caps or packed.caps
    events = np.ascontiguousarray(packed.events, dtype=np.int32)  # [B,T,E]
    batch, T, ev_n = events.shape
    if ev_n != S.EV_N:
        raise ValueError(f"event width {ev_n} != schema EV_N {S.EV_N}")
    lengths = np.ascontiguousarray(packed.lengths, dtype=np.int64)
    state = S.empty_state(batch, caps)
    lib.ct_replay_sequential(
        _i32p(events), _i64p(lengths), batch, T,
        caps.max_activities, caps.max_timers, caps.max_children,
        caps.max_request_cancels, caps.max_signals_ext,
        caps.max_version_items,
        _i32p(state.exec_info), _i32p(state.activities),
        _i32p(state.timers), _i32p(state.children),
        _i32p(state.cancels), _i32p(state.signals),
        _i32p(state.vh_items), _i32p(state.vh_len),
    )
    return state

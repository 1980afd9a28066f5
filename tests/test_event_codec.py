"""The stored-batch codec (core/events.py): ``decode_batches`` decodes a
page of blobs with one JSON parse and must give what decoding each blob
alone gave before it, event for event and exception for exception.

The oracle is the per-blob decoder as it was before the page decoder:
one ``json.loads`` a blob, then a recursive walk that restores the
``{"__bytes__": ...}`` tags in every event's attributes."""

from __future__ import annotations

import json
from typing import Any, Dict, List

import pytest

from cadence_tpu.core.enums import EventType
from cadence_tpu.core.events import (
    HistoryEvent,
    decode_batch,
    decode_batches,
    encode_batch,
)
from cadence_tpu.core.ids import EMPTY_EVENT_TASK_ID
from cadence_tpu.testing import workloads as W
from cadence_tpu.testing.event_generator import HistoryFuzzer

T0 = 1_700_000_000_000_000_000


def _old_unjsonable(obj: Any) -> Any:
    if isinstance(obj, dict):
        if set(obj.keys()) == {"__bytes__"}:
            return obj["__bytes__"].encode("latin-1")
        return {k: _old_unjsonable(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_old_unjsonable(v) for v in obj]
    return obj


def _old_from_dict(d: Dict[str, Any]) -> HistoryEvent:
    return HistoryEvent(
        event_id=d["event_id"],
        event_type=EventType(d["event_type"]),
        version=d["version"],
        timestamp=d["timestamp"],
        task_id=d.get("task_id", EMPTY_EVENT_TASK_ID),
        attributes=_old_unjsonable(d.get("attributes", {})),
    )


def old_decode_batch(blob: bytes) -> List[HistoryEvent]:
    return [_old_from_dict(d) for d in json.loads(blob.decode())]


def _ev(eid: int, attributes: Dict[str, Any], etype=EventType.MarkerRecorded,
        task_id: int = 7) -> HistoryEvent:
    return HistoryEvent(eid, etype, 10, T0 + eid, task_id, attributes)


def _blobs(batches) -> List[bytes]:
    return [encode_batch(b) for b in batches]


def _fuzzed() -> List[bytes]:
    fuzzer = HistoryFuzzer(seed=7)
    out: List[bytes] = []
    for depth in (12, 40, 200):
        out += _blobs(fuzzer.generate(target_events=depth))
    return out


def _retry_page() -> List[bytes]:
    import random

    return _blobs(W.retry_deep_history(random.Random(3), depth=600))[:256]


def _nested_bytes() -> List[bytes]:
    attrs = {
        "header": {"fields": {"trace": b"\x00\x01\xff", "empty": b""}},
        "memo": {"fields": {"k": b"memo-bytes", "n": [b"a", {"b": b"\xe9"}]}},
        "details": [b"d0", [b"d1", b"d2"], {"deep": [[b"\x7f"]]}],
        "input": b"payload",
    }
    return _blobs([[_ev(1, attrs)], [_ev(2, {"result": b"r"}), _ev(3, {})]])


def _raw(events: List[Dict[str, Any]]) -> bytes:
    """A blob written by hand, for shapes ``encode_batch`` cannot make."""
    return json.dumps(events, separators=(",", ":")).encode()


def _event_dict(eid: int, **extra) -> Dict[str, Any]:
    d = {"event_id": eid, "event_type": int(EventType.MarkerRecorded),
         "version": 10, "timestamp": T0, "task_id": 1}
    d.update(extra)
    return d


PAGES = {
    "fuzzed_histories": _fuzzed,
    "bytes_nested_in_dicts_and_lists": _nested_bytes,
    "string_holding_the_tag": lambda: _blobs([
        [_ev(1, {"marker_name": "__bytes__",
                 "details": '{"__bytes__":"x"}'})],
        [_ev(2, {"__bytes__": "a plain string key among others",
                 "other": 1})],
    ]),
    "tag_beside_another_key_stays_a_dict": lambda: [_raw([_event_dict(
        1, attributes={"v": {"__bytes__": "abc", "x": 1}})])],
    "escaped_tag_key": lambda: [
        b'[{"event_id":1,"event_type":26,"version":10,"timestamp":1,'
        b'"task_id":1,"attributes":{"v":{"\\u005f_bytes__":"q"}}}]',
        encode_batch([_ev(2, {"s": "café ☃"})]),
    ],
    "empty_attributes_and_missing_task_id": lambda: [
        _raw([_event_dict(1, attributes={})]),
        _raw([{"event_id": 2, "event_type": 26, "version": 10,
               "timestamp": T0}]),
        encode_batch([_ev(3, {}, task_id=EMPTY_EVENT_TASK_ID)]),
    ],
    "one_event_batches": lambda: _blobs(
        [[_ev(i, {"marker_name": f"m{i}"})] for i in range(1, 9)]),
    "full_256_node_page": _retry_page,
    "empty_page": lambda: [],
}


@pytest.mark.parametrize("case", sorted(PAGES))
def test_decode_batches_equals_the_per_blob_decoder(case):
    blobs = PAGES[case]()
    want = [old_decode_batch(b) for b in blobs]
    got = decode_batches(blobs)
    assert got == want
    # the tags come back as bytes, and the types as the enum
    for batch in got:
        for e in batch:
            assert type(e.event_type) is EventType
    assert [decode_batch(b) for b in blobs] == want
    if case == "full_256_node_page":
        assert len(blobs) == 256


def _good(eid=1):
    return encode_batch([_ev(eid, {"input": b"x"})])


CORRUPT = {
    "truncated_blob": lambda: [_good(1), _good(2)[:-7], _good(3)],
    "not_json": lambda: [_good(1), b"\x00garbage"],
    "two_arrays_in_one_blob": lambda: [_good(1), b"[],[]", _good(3)],
    "unknown_event_type": lambda: [_good(1), _raw([_event_dict(
        2, event_type=999)])],
    "event_type_not_a_number": lambda: [_raw([_event_dict(
        1, event_type=[1])]), _good(2)],
    "missing_event_id": lambda: [_good(1), _raw([{"event_type": 26}])],
    "batch_not_a_list_of_events": lambda: [_good(1), b"[1, 2]"],
    "bad_utf8": lambda: [_good(1), b'[{"a":"\xff"}]'],
    "tag_value_not_latin1": lambda: [_raw([_event_dict(
        1, attributes={"v": {"__bytes__": "☃"}})]), _good(2)],
}


@pytest.mark.parametrize("case", sorted(CORRUPT))
def test_a_corrupt_blob_raises_what_it_raised_alone(case):
    blobs = CORRUPT[case]()
    with pytest.raises(Exception) as old:
        [old_decode_batch(b) for b in blobs]
    with pytest.raises(Exception) as new:
        decode_batches(blobs)
    assert type(new.value) is type(old.value)
    assert str(new.value) == str(old.value)

"""The owner of each device op in a profiler trace: the JAX name stack
(``tf_op`` stat) of each event metadata entry of an XSpace's device
planes, read straight from the protobuf wire format.

``jax.profiler.ProfileData`` exposes an event's own stats only, not those
of its metadata, and TensorFlow's ``xplane_pb2`` is not a dependency of
this benchmark, so the few fields needed are decoded here by hand:

    XSpace.planes = 1
    XPlane.name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    map entry: key = 1, value = 2
    XEventMetadata.name = 2, display_name = 4, stats = 5
    XStatMetadata.name = 2
    XStat.metadata_id = 1, uint64_value = 3, str_value = 5, ref_value = 7

An entry without ``tf_op`` may name, in ``deduplicated_name``, the
instruction (``display_name``, same program) whose metadata it shares.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

DEVICE_PREFIX = "/device:"
TF_OP = "tf_op"
DEDUPLICATED = "deduplicated_name"
PROGRAM_ID = "program_id"

_VARINT, _FIXED64, _LENGTH, _FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf: bytes, start: int = 0, end: int = -1
           ) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of the message in
    ``buf[start:end]``: an int for a varint, ``(start, end)`` offsets into
    ``buf`` for a length-delimited field; fixed-width fields are
    skipped."""
    i, end = start, len(buf) if end < 0 else end
    while i < end:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == _LENGTH:
            size, i = _varint(buf, i)
            yield number, (i, i + size)
            i += size
        elif wire == _FIXED64:
            i += 8
        elif wire == _FIXED32:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]
               ) -> Tuple[int, Tuple[int, int]]:
    key, value = 0, (span[1], span[1])
    for number, v in fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf: bytes, span: Tuple[int, int]) -> Tuple[str, Dict[str, str]]:
    name = ""
    stat_names: Dict[int, str] = {}
    # (name, display_name, [(metadata id, str or ref id or uint)])
    events: List[Tuple[str, str, list]] = []
    for number, v in fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
            if not name.startswith(DEVICE_PREFIX):
                return name, {}
        elif number == 5:
            key, value = _map_entry(buf, v)
            for n, sv in fields(buf, *value):
                if n == 2:
                    stat_names[key] = _text(buf, sv)
        elif number == 4:
            _, value = _map_entry(buf, v)
            ev_name, display, stats = "", "", []
            for n, ev in fields(buf, *value):
                if n == 2:
                    ev_name = _text(buf, ev)
                elif n == 4:
                    display = _text(buf, ev)
                elif n == 5:
                    stats.append(_stat(buf, ev))
            events.append((ev_name, display, stats))
    return name, _resolve(events, stat_names)


def _stat(buf: bytes, span: Tuple[int, int]) -> Tuple[int, object]:
    """``(metadata id, value)``: a str for ``str_value``, ``("ref", id)``
    for ``ref_value``, an int for ``uint64_value``."""
    mid, value = 0, None
    for n, v in fields(buf, *span):
        if n == 1:
            mid = v
        elif n == 5:
            value = _text(buf, v)
        elif n == 7:
            value = ("ref", v)
        elif n == 3:
            value = v
    return mid, value


def _resolve(events, stat_names: Dict[int, str]) -> Dict[str, str]:
    def named(stats):
        out = {}
        for mid, value in stats:
            if isinstance(value, tuple):
                value = stat_names.get(value[1], "")
            out[stat_names.get(mid, "")] = value
        return out

    entries = [(ev_name, display, named(stats))
               for ev_name, display, stats in events]
    by_instruction = {(st.get(PROGRAM_ID), display): st[TF_OP]
                      for _, display, st in entries if TF_OP in st}
    out: Dict[str, str] = {}
    for ev_name, _, st in entries:
        op = st.get(TF_OP)
        if op is None and DEDUPLICATED in st:
            op = by_instruction.get((st.get(PROGRAM_ID), st[DEDUPLICATED]))
        if op and not out.get(ev_name):
            out[ev_name] = op_path(op)
    return out


def op_path(tf_op: str) -> str:
    """The name stack of a ``tf_op`` stat, ``"<name stack>:<op type>"``,
    without its type."""
    return tf_op.rpartition(":")[0] if ":" in tf_op else tf_op


def tf_ops(data: bytes) -> Dict[str, Dict[str, str]]:
    """Device plane name -> event name (the HLO instruction text that
    ``ProfileData`` gives each event) -> the op's JAX name stack, for each
    event metadata entry that has one, directly or through
    ``deduplicated_name``."""
    out = {}
    for number, v in fields(data):
        if number == 1:
            name, ops = _plane(data, v)
            if name.startswith(DEVICE_PREFIX):
                out[name] = ops
    return out

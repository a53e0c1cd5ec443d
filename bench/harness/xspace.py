"""The few fields of a profiler's XSpace protobuf that
``jax.profiler.ProfileData`` does not expose: the stats of each event
metadata entry, which for an XLA op carry ``tf_op`` (JAX's op path, every
``jax.named_scope`` in it), ``source``, ``hlo_category``, ``flops``,
``bytes_accessed`` and ``program_id``.

A plain reader of the protobuf wire format, so that no package beyond
JAX is needed.  Fields read (``tsl/profiler/protobuf/xplane.proto``):
``XSpace.planes`` (1); ``XPlane.name`` (2), ``event_metadata`` (4) and
``stat_metadata`` (5), maps from an id to an entry; ``XEventMetadata.name``
(2) and ``stats`` (5); ``XStatMetadata.name`` (2); ``XStat.metadata_id``
(1) and its value: double (2), uint64 (3), int64 (4), string (5), bytes
(6), or a reference to a stat metadata entry whose name is the value (7).
Everything else, the planes' lines and events above all, is skipped.
"""
from __future__ import annotations

import re
import struct
from typing import Dict, Iterator, List, Tuple

Stats = Dict[str, object]


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, i: int, j: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of the message in ``b[i:j]``: an
    int for a varint, the ``(start, end)`` of a length-delimited field,
    the 8 or 4 raw bytes of a fixed one."""
    while i < j:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value, i = b[i:i + 8], i + 8
        elif wire == 5:
            value, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield field, wire, value


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(b: bytes, span) -> Iterator[Tuple[int, int]]:
    """The value (field 2) of a map entry."""
    for f, _, v in _fields(b, *span):
        if f == 2:
            yield v


def _stat(b: bytes, span, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for f, _, v in _fields(b, *span):
        if f == 1:
            name = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif f in (5, 6):
            value = _text(b, v)
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def event_metadata(data: bytes, plane: str = r"/device:") \
        -> Dict[str, List[Tuple[str, Stats]]]:
    """For each plane whose name matches ``plane`` (a regex searched in
    the name): ``[(event metadata name, {stat name: value})]``."""
    rx = re.compile(plane)
    out: Dict[str, List[Tuple[str, Stats]]] = {}
    for f, _, span in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for pf, _, pv in _fields(data, *span):
            if pf == 2:
                name = _text(data, pv)
            elif pf == 4:
                events.extend(_map_values(data, pv))
            elif pf == 5:
                for sv in _map_values(data, pv):
                    sid, sname = 0, ""
                    for sf, _, x in _fields(data, *sv):
                        if sf == 1:
                            sid = x
                        elif sf == 2:
                            sname = _text(data, x)
                    stats[sid] = sname
        if not rx.search(name):
            continue
        entries = []
        for ev in events:
            ename, estats = "", {}
            for ef, _, x in _fields(data, *ev):
                if ef == 2:
                    ename = _text(data, x)
                elif ef == 5:
                    k, v = _stat(data, x, stats)
                    estats[k] = v
            entries.append((ename, estats))
        out[name] = entries
    return out

"""Op metadata from an ``.xplane.pb`` file: each device op's name-scope path.

``jax.profiler.ProfileData`` gives the stats of each event (its device
offset and duration) but not the stats of the event's metadata, where the
profiler keeps an op's ``tf_op`` (the HLO ``op_name``: the ``jit(...)``
path with every ``jax.named_scope`` on the way) and the ``program_id`` of
the program that holds it.  This module reads them straight from the
XSpace wire format (protobuf, ``tsl/profiler/protobuf/xplane.proto``),
with no dependency: only the messages below are decoded, and every other
field (the events themselves among them) is skipped by its length.

    XSpace         planes = 1
    XPlane         name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    XEventMetadata id = 1, name = 2, stats = 5
    XStatMetadata  id = 1, name = 2
    XStat          metadata_id = 1, double 2, uint64 3, int64 4, str 5,
                   bytes 6, ref 7 (the name of a stat metadata)
"""
from __future__ import annotations

import dataclasses
import pathlib
import struct

VARINT, I64, LEN, I32 = 0, 1, 2, 5
DEVICE_PREFIX = "/device:"


@dataclasses.dataclass(frozen=True)
class OpMeta:
    name: str                 # the event name: the op's HLO text
    tf_op: str                # name-scope path, "" where the op has none
    program_id: int | None


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, wire type, value)`` of one message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == VARINT:
            v, i = _varint(buf, i)
        elif wt == I64:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wt == LEN:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wt == I32:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}: not an xplane")
        yield num, wt, v


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_value(buf, span):
    """The value span of a map entry (key = 1, value = 2)."""
    for num, wt, v in _fields(buf, *span):
        if num == 2 and wt == LEN:
            return v
    return None


def _stat(buf, span, stat_names: dict):
    mid, val = 0, None
    for num, wt, v in _fields(buf, *span):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = _str(buf, v)
        elif num == 7:
            val = stat_names.get(v, "")
    return stat_names.get(mid, ""), val


def _plane(buf, span):
    name, events, stat_names = "", [], {}
    for num, wt, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 4:
            events.append(_map_value(buf, v))
        elif num == 5:
            sm = _map_value(buf, v)
            if sm is not None:
                sid, sname = 0, ""
                for n2, _, v2 in _fields(buf, *sm):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2:
                        sname = _str(buf, v2)
                stat_names[sid] = sname
    return name, [e for e in events if e is not None], stat_names


def _op(buf, span, stat_names: dict) -> OpMeta:
    name, stats = "", {}
    for num, _, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 5:
            k, val = _stat(buf, v, stat_names)
            stats[k] = val
    pid = stats.get("program_id")
    return OpMeta(name, str(stats.get("tf_op") or ""),
                  None if pid is None else int(pid))


def device_ops(data: bytes) -> dict:
    """``{device plane name: [OpMeta, ...]}``: the event metadata of every
    device plane of a serialized XSpace."""
    buf = memoryview(data)
    out = {}
    for num, wt, span in _fields(buf):
        if num != 1 or wt != LEN:
            continue
        name, events, stat_names = _plane(buf, span)
        if name.startswith(DEVICE_PREFIX):
            out[name] = [_op(buf, e, stat_names) for e in events]
    return out


def read(path) -> dict:
    return device_ops(pathlib.Path(path).read_bytes())


def tf_ops(metas) -> dict:
    """``{(event name, program id): tf_op}`` of one plane's ops, and
    ``{(event name, None): tf_op}`` where that name has one path in every
    program that holds it."""
    out, by_name = {}, {}
    for m in metas:
        out[(m.name, m.program_id)] = m.tf_op
        by_name.setdefault(m.name, set()).add(m.tf_op)
    for name, paths in by_name.items():
        if len(paths) == 1:
            out[(name, None)] = next(iter(paths))
    return out

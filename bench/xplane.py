"""Read the HLO ``op_name`` path of each device operation from a
profiler trace.

``jax.profiler.ProfileData`` yields an event's own stats, not those of
its metadata, and the ``op_name`` of a device operation (which carries
the program's ``jax.named_scope``s) is a stat of the metadata: ``tf_op``,
written ``<op_name>:``. A TPU trace leaves it out for loops and
conditionals; their ``op_name`` is in the optimized HLO module that the
trace holds for each program (the ``Hlo Proto`` stat of the
``/host:metadata`` plane), found by the operation's ``program_id`` and
instruction name. This module reads both from the ``.xplane.pb`` wire
format, as ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto`` lay it out.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

__all__ = ["op_paths"]

# field numbers: XSpace.planes; XPlane.name, .lines, .event_metadata,
# .stat_metadata; XLine.events; XEventMetadata.display_name, .stats;
# XStat.metadata_id, .uint64_value, .str_value, .bytes_value, .ref_value
_PLANES = 1
_NAME, _LINES, _EVENT_META, _STAT_META = 2, 3, 4, 5
_LINE_EVENTS = 4
_META_DISPLAY, _META_STATS = 4, 5
_STAT_ID, _STAT_U64, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 3, 5, 6, 7
# HloProto.hlo_module; HloModuleProto.computations;
# HloComputationProto.instructions; HloInstructionProto.name, .metadata;
# OpMetadata.op_name
_HLO_MODULE, _COMPUTATIONS, _INSTRUCTIONS = 1, 3, 2
_INSTR_NAME, _INSTR_META, _OP_NAME = 1, 7, 2


def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint,
    a memoryview for anything length-delimited or fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} is not in xplane.proto")
        yield key >> 3, value


def _first(buf) -> Dict[int, object]:
    """The first value of each field of one message."""
    out: Dict[int, object] = {}
    for k, v in _fields(buf):
        out.setdefault(k, v)
    return out


def _text(v) -> str:
    return bytes(v).decode() if v is not None else ""


def _entry(buf) -> Tuple[int, memoryview]:
    """One ``map<int64, message>`` entry: (key, value message)."""
    entry = _first(buf)
    return entry.get(1, 0), entry.get(2, memoryview(b""))


def _plane(buf) -> Dict[int, list]:
    parts: Dict[int, list] = {}
    for k, v in _fields(buf):
        parts.setdefault(k, []).append(v)
    return parts


def _stat_names(parts) -> Dict[int, str]:
    names = {}
    for raw in parts.get(_STAT_META, []):
        key, meta = _entry(raw)
        names[key] = _text(_first(meta).get(_NAME))
    return names


def _hlo_op_names(parts) -> Dict[int, Dict[str, str]]:
    """``{program_id: {instruction: op_name}}`` from the HLO modules of
    the ``/host:metadata`` plane."""
    stat_names = _stat_names(parts)
    out: Dict[int, Dict[str, str]] = {}
    for raw in parts.get(_EVENT_META, []):
        program, meta = _entry(raw)
        for k, v in _fields(meta):
            if k != _META_STATS:
                continue
            stat = _first(v)
            if stat_names.get(stat.get(_STAT_ID)) != "Hlo Proto":
                continue
            module = _first(stat[_STAT_BYTES]).get(_HLO_MODULE, b"")
            names = out.setdefault(program, {})
            for kc, comp in _fields(module):
                if kc != _COMPUTATIONS:
                    continue
                for ki, instr in _fields(comp):
                    if ki != _INSTRUCTIONS:
                        continue
                    f = _first(instr)
                    if _INSTR_META in f:
                        op = _text(_first(f[_INSTR_META]).get(_OP_NAME))
                        if op:
                            names[_text(f.get(_INSTR_NAME))] = op
    return out


def op_paths(path, *, prefix: str = "/device:",
             line: str = "XLA Ops") -> Dict[str, List[Tuple[str, str]]]:
    """``{plane: [(event name, op_name path), ...]}`` over the events of
    the line ``line`` of every plane whose name starts with ``prefix``,
    in the file's order, which is the order ``ProfileData`` yields them.
    The path is the event's ``tf_op``, else its instruction's
    ``op_name`` in the program's HLO module, else ``""``."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    hlo: Dict[int, Dict[str, str]] = {}
    devices = []
    for k, raw in _fields(data):
        if k != _PLANES:
            continue
        name = _text(_first(raw).get(_NAME))
        if name == "/host:metadata":
            hlo.update(_hlo_op_names(_plane(raw)))
        elif name.startswith(prefix):
            devices.append((name, raw))
    out: Dict[str, List[Tuple[str, str]]] = {}
    for name, raw in devices:
        parts = _plane(raw)
        stat_names = _stat_names(parts)
        ops = {}
        for entry in parts.get(_EVENT_META, []):
            key, meta = _entry(entry)
            op_name, display, tf_op, program = "", "", "", None
            for k, v in _fields(meta):
                if k == _NAME:
                    op_name = _text(v)
                elif k == _META_DISPLAY:
                    display = _text(v)
                elif k == _META_STATS:
                    stat = _first(v)
                    kind = stat_names.get(stat.get(_STAT_ID))
                    if kind == "tf_op":
                        tf_op = (_text(stat[_STAT_STR]) if _STAT_STR in stat
                                 else stat_names.get(stat.get(_STAT_REF), ""))
                    elif kind == "program_id":
                        program = stat.get(_STAT_U64)
            if not tf_op:
                tf_op = hlo.get(program, {}).get(display, "")
            ops[key] = (op_name, tf_op)
        for raw_line in parts.get(_LINES, []):
            fields = list(_fields(raw_line))
            if any(k == _NAME and _text(v) == line for k, v in fields):
                out[name] = [ops.get(_first(ev).get(1, 0), ("", ""))
                             for k, ev in fields if k == _LINE_EVENTS]
    return out

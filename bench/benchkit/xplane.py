"""What ``jax.profiler.ProfileData`` does not expose of an ``.xplane.pb``:
the HLO of every module the trace ran, and from it what each device
operation is.

The file is an ``XSpace`` protobuf.  The plane ``/host:metadata`` keeps,
per module, an event metadata entry named like the module's events on the
``XLA Modules`` line (``jit_argsort(4683730863320365931)``) with an
``Hlo Proto`` stat.  Only the planes' metadata maps are decoded here, by
the protobuf wire format; the event lines are skipped unread.

Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes 1;
XPlane.name 2, .event_metadata 4, .stat_metadata 5; map entries key 1,
value 2; XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
XStat.metadata_id 1, .str_value 5, .bytes_value 6; HloProto.hlo_module 1.
"""
from __future__ import annotations

import re
from typing import Dict, Iterator, Tuple

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"

# opcodes a device operation is classed by, where a fusion holds them
NOTABLE = ("gather", "scatter", "dynamic-update-slice", "dynamic-slice",
           "sort", "custom-call", "all-to-all", "all-gather", "all-reduce",
           "reduce-scatter", "collective-permute", "reduce", "dot",
           "convolution")

_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|"
                     r"branch_computations|called_computations)="
                     r"\{?([%\w.\-, ]+)\}?")


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def hlo_protos(path: str) -> Dict[str, bytes]:
    """Module name -> serialized ``HloModuleProto``, from the metadata
    plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, bytes] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_entry(v)[1])
            elif pf == 5:
                sid, md = _entry(v)
                stat_names[sid] = next(
                    (bytes(x).decode() for g, x in _fields(md) if g == 2), "")
        if name != METADATA_PLANE:
            continue
        for md in events:
            ev_name, proto = None, None
            for g, v in _fields(md):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                        proto = stat[6]
            if ev_name and proto is not None:
                module = next((v for g, v in _fields(proto) if g == 1), None)
                if module is not None:
                    out[ev_name] = bytes(module)
    return out


def hlo_text(module_proto: bytes) -> str:
    from jax._src.lib import _jax
    return _jax.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()


def opcode(instruction: str) -> str:
    """The opcode of one HLO instruction's text (after its ``=``)."""
    m = _OPCODE.search(instruction)
    return m.group(1) if m else ""


def categories(text: str) -> Dict[str, str]:
    """Instruction name -> category, for every instruction of a module:
    its opcode, and for a fusion, call or loop the notable opcodes of the
    computations it calls, e.g. ``fusion[gather]``."""
    comps: Dict[str, list] = {}
    current = None
    for line in text.splitlines():
        m = _COMP.match(line) if line and not line[0].isspace() else None
        if m:
            current = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            rhs = m.group(2)
            called = []
            for c in _CALLED.finditer(rhs):
                called += [x.strip().lstrip("%") for x in c.group(1).split(",")
                           if x.strip()]
            current.append((m.group(1), opcode(rhs), called))

    inside: Dict[str, set] = {}

    def notable(comp: str, seen=()) -> set:
        if comp in inside:
            return inside[comp]
        found = set()
        for _, op, called in comps.get(comp, []):
            if op in NOTABLE:
                found.add(op)
            for c in called:
                if c not in seen:
                    found |= notable(c, seen + (comp,))
        inside[comp] = found
        return found

    out: Dict[str, str] = {}
    for instrs in comps.values():
        for name, op, called in instrs:
            held = set()
            for c in called:
                held |= notable(c)
            out[name] = f"{op}[{','.join(sorted(held))}]" if held else op
    return out

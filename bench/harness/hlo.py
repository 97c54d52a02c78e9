"""The HLO op names of the programs a profiler trace ran.

A trace's ``/host:metadata`` plane holds, for each program that ran while
the profiler recorded, the program's optimized HLO as an ``HloProto`` in a
stat of an event-metadata entry named after the program (as its runs are
on the device's ``XLA Modules`` line).  ``jax.profiler.ProfileData`` does
not reach event metadata, so this module reads the ``.xplane.pb`` file's
protobuf wire format itself and decodes only the fields it needs:

* ``XSpace``: planes (1);
* ``XPlane``: name (2), event_metadata (4, a map: key 1, value 2),
  stat_metadata (5, a map);
* ``XEventMetadata``: name (2), stats (5); ``XStat``: metadata_id (1),
  bytes_value (6); ``XStatMetadata``: id (1), name (2);
* ``HloProto``: hlo_module (1); ``HloModuleProto``: computations (3);
  ``HloComputationProto``: instructions (2); ``HloInstructionProto``:
  name (1), opcode (2), metadata (7); ``OpMetadata``: op_name (2).

A device op of a program (``%fusion.125 = ...`` on a TPU's ``XLA Ops``
line) is then named by its instruction's ``op_name``: the ``jax.named_scope``
path it was traced under.
"""

from __future__ import annotations

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    pos, hi = lo, len(buf) if hi is None else hi
    while pos < hi:
        key, pos = _varint(buf, pos)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _plane_hlo(buf, lo: int, hi: int, fragment: str) -> dict:
    events, stat_ids = [], {}
    for num, v in _fields(buf, lo, hi):
        if num in (4, 5):
            entry = dict(_fields(buf, *v))
            if 2 not in entry:
                continue
            if num == 4:
                events.append(entry[2])
            else:
                meta = dict(_fields(buf, *entry[2]))
                if 2 in meta:
                    stat_ids[_text(buf, meta[2])] = meta.get(1)
    want = stat_ids.get(HLO_PROTO_STAT)
    out = {}
    for span in events:
        name, proto = None, None
        for num, v in _fields(buf, *span):
            if num == 2:
                name = _text(buf, v)
            elif num == 5:
                stat = dict(_fields(buf, *v))
                if stat.get(1) == want and 6 in stat:
                    proto = stat[6]
        if name and proto and fragment in name:
            out[name] = instructions(buf, proto)
    return out


def instructions(buf, proto) -> dict[str, tuple[str, str]]:
    """``{instruction name: (opcode, op_name)}`` of an ``HloProto`` held in
    ``buf[proto[0]:proto[1]]``."""
    out = {}
    for num, module in _fields(buf, *proto):
        if num != 1:
            continue
        for num, comp in _fields(buf, *module):
            if num != 3:
                continue
            for num, inst in _fields(buf, *comp):
                if num != 2:
                    continue
                name = opcode = op_name = ""
                for num, v in _fields(buf, *inst):
                    if num == 1:
                        name = _text(buf, v)
                    elif num == 2:
                        opcode = _text(buf, v)
                    elif num == 7:
                        meta = dict(_fields(buf, *v))
                        if 2 in meta:
                            op_name = _text(buf, meta[2])
                out[name] = (opcode, op_name)
    return out


def op_names(path: str, fragment: str = "") -> dict[str, dict]:
    """Per program of the trace at ``path`` whose name holds ``fragment``:
    ``{instruction name: (opcode, op_name)}``.  Empty where the trace holds
    no HLO."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, *plane)
                     if n == 2), "")
        if name == METADATA_PLANE:
            out.update(_plane_hlo(buf, *plane, fragment))
    return out

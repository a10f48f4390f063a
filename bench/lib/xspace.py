"""The ``op_name`` of every HLO instruction of the programs a profiler
trace holds.

A TPU trace's ``XLA Ops`` events carry the instruction's text without its
metadata, so the ``op_name`` (where JAX puts ``jax.named_scope``) comes
from the HLO protos the profiler stores beside the events: the
``/host:metadata`` plane has one event metadata per compiled program,
named as the program's ``XLA Modules`` events are, with an ``Hlo Proto``
stat. ``jax.profiler.ProfileData`` does not expose event metadata, so the
few fields needed are read from the protobuf wire format here
(``XSpace`` in tsl's ``xplane.proto``, ``HloProto`` in xla's
``hlo.proto``).
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterator, Tuple

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def _varint(b: memoryview, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def fields(b: memoryview) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int for varints, a
    memoryview for length-delimited fields, raw bytes for fixed ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + size]), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _str(v) -> str:
    return bytes(v).decode()


def hlo_protos(path: Path) -> Dict[str, memoryview]:
    """Program name (as its ``XLA Modules`` events give it) -> its
    serialized ``HloProto``."""
    data = memoryview(Path(path).read_bytes())
    out: Dict[str, memoryview] = {}
    for num, plane in fields(data):                 # XSpace.planes
        if num != 1:
            continue
        parts = list(fields(plane))
        if not any(n == 2 and _str(v) == METADATA_PLANE for n, v in parts):
            continue
        stat_names = {}
        for n, entry in parts:                      # XPlane.stat_metadata
            if n == 5:
                value = dict(fields(entry)).get(2)
                if value is not None:
                    meta = dict(fields(value))
                    stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
        for n, entry in parts:                      # XPlane.event_metadata
            if n != 4:
                continue
            value = dict(fields(entry)).get(2)
            if value is None:
                continue
            name, proto = None, None
            for m, v in fields(value):              # XEventMetadata
                if m == 2:
                    name = _str(v)
                elif m == 5:                        # .stats: XStat
                    stat = dict(fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        proto = stat.get(6)         # bytes_value
            if name is not None and proto is not None:
                out[name] = proto
    return out


def instruction_op_names(hlo_proto: memoryview) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of the
    module (fusions' inner computations too); '' where it has none."""
    out: Dict[str, str] = {}
    for num, module in fields(hlo_proto):           # HloProto.hlo_module
        if num != 1:
            continue
        for n, comp in fields(module):              # .computations
            if n != 3:
                continue
            for m, instr in fields(comp):           # .instructions
                if m != 2:
                    continue
                name, op_name = None, ""
                for k, v in fields(instr):
                    if k == 1:
                        name = _str(v)
                    elif k == 7:                    # .metadata: OpMetadata
                        op_name = _str(dict(fields(v)).get(2, b""))
                if name is not None:
                    out[name] = op_name
    return out


def op_names(path: Path) -> Dict[str, Dict[str, str]]:
    """Program name -> instruction name -> ``op_name``."""
    return {prog: instruction_op_names(p)
            for prog, p in hlo_protos(path).items()}


def instruction(event_name: str) -> str:
    """An ``XLA Ops`` event's instruction name: ``%fusion.573 = ...`` ->
    ``fusion.573``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name

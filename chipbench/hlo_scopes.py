"""The program's own names for the device's operations, from the trace.

An ``XLA Ops`` event is named by the HLO instruction's text
(``%fusion.12 = ...``) and carries no scope.  The same ``.xplane.pb``
holds, on its ``/host:metadata`` plane, the ``HloProto`` of every module
that ran, and there each instruction has the ``op_name`` JAX gave it:
the ``jax.named_scope`` path of the operation it came from
(``jit(epoch_fn)/while/body/.../opt/mul``).  ``load`` reads those pairs
with a walker over the protobuf wire format: standard library only, no
``xprof``, no ``tsl``, no ``.proto`` files.

Field path: ``XSpace.planes(1)`` -> ``XPlane.name(2) == "/host:metadata"``
-> ``event_metadata(4)`` map value ``(2)`` -> ``XEventMetadata.name(2)``
(the launch's name on ``XLA Modules``, ``jit_wrapped(<program id>)``) and
``stats(5)`` -> ``XStat.bytes_value(6)`` -> ``HloProto.hlo_module(1)`` ->
``computations(3)`` -> ``instructions(2)`` -> ``name(1)``, ``metadata(7)``
-> ``op_name(2)``.  A fusion's ``op_name`` is its root's.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterator, Optional, Tuple

METADATA_PLANE = b"/host:metadata"
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is a view of ``buf``, not a copy."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (_FIXED64, _FIXED32):
            size = 8 if wire == _FIXED64 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield tag >> 3, wire, value


def _sub(buf, number: int) -> Iterator[memoryview]:
    """The length-delimited values of field ``number``."""
    return (v for f, w, v in _fields(buf) if f == number and w == _BYTES)


def _first(buf, number: int) -> Optional[memoryview]:
    return next(_sub(buf, number), None)


def _instructions(hlo_proto) -> Iterator[Tuple[str, str]]:
    """(instruction name, op_name) of every instruction of an ``HloProto``
    whose metadata names one."""
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name = _first(instruction, 1)
                metadata = _first(instruction, 7)
                op_name = _first(metadata, 2) if metadata is not None else None
                if name is not None and op_name:
                    yield bytes(name).decode(), bytes(op_name).decode()


def load(xplane_path: str) -> Dict[Tuple[str, str], str]:
    """``{(module, instruction): op_name}``; ``module`` is the launch's name
    as the ``XLA Modules`` line has it (``jit_epoch_fn(<program id>)``),
    ``instruction`` the HLO instruction's name without its ``%``."""
    with open(xplane_path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[Tuple[str, str], str] = {}
    for plane in _sub(space, 1):
        name = _first(plane, 2)
        if name is None or bytes(name) != METADATA_PLANE:
            continue
        for entry in _sub(plane, 4):
            event = _first(entry, 2)
            module = _first(event, 2) if event is not None else None
            if module is None:
                continue
            module = bytes(module).decode()
            for stat in _sub(event, 5):
                proto = _first(stat, 6)
                if proto is None:
                    continue
                for instruction, op_name in _instructions(proto):
                    out[module, instruction] = op_name
    return out


def xplane_of(ctx) -> Optional[str]:
    """The trace file of this run: ``ctx.xplane`` where the harness gives
    it, else the newest ``.xplane.pb`` under
    ``<checkout>/.chipbench_out/trace/*/`` (the harness clears the cell's
    directory before it traces and reduces straight after, so the newest
    is this run's).  ``None`` where there is none."""
    given = getattr(ctx, "xplane", None)
    if given:
        return given
    import chipbench

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(chipbench.__file__)))
    paths = glob.glob(os.path.join(
        checkout, ".chipbench_out", "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb",
    ))
    return max(paths, key=os.path.getmtime) if paths else None


def of(ctx) -> Dict[Tuple[str, str], str]:
    """``load`` of this run's trace, read once per run (kept on ``ctx``)."""
    cached = getattr(ctx, "_hlo_scopes", None)
    if cached is None:
        path = xplane_of(ctx)
        cached = ctx._hlo_scopes = load(path) if path else {}
    return cached

"""The op path of each device op in a TPU trace, read from its file.

``jax.profiler.ProfileData`` gives a device op its name (the HLO text that
``Trace.ops[i].name`` holds) and its event stats, but not the stats of the
op's metadata. Those carry the op path XLA kept from the program
(``tf_op``): ``jit(_index_engine)/paris.select/top_k`` for an op under the
program's ``jax.named_scope("paris.select")``. This module decodes just
enough of the ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``)
to map each device op's name to that path: planes (1), each plane's name
(2), event metadata (4) and stat metadata (5), and each event metadata's
name (2) and stats (5). A plane's lines, the bulk of the file, are skipped.
"""

from __future__ import annotations

import functools
import glob
import os

from chipbench import harness, trace

SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
ENTRY_VALUE = 2  # a map entry: key 1, value 2
META_NAME, META_STATS = 2, 5
STAT_METADATA_ID, STAT_STR, STAT_REF = 1, 5, 7
OP_PATH_STAT = "tf_op"


def _varint(buf: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes, i: int = 0, end: int = None):
    """(field number, value) of each field of one message: an int for a
    varint, ``(start, end)`` offsets for a length-delimited field; fixed
    32- and 64-bit fields are skipped."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _entry_values(buf: bytes, span: tuple) -> list:
    return [v for f, v in _fields(buf, *span) if f == ENTRY_VALUE]


def _plane_paths(buf: bytes, span: tuple) -> dict:
    name, events, stat_names = "", [], {}
    for field, value in _fields(buf, *span):
        if field == PLANE_NAME:
            name = _text(buf, value)
        elif field == PLANE_EVENT_METADATA:
            events.extend(_entry_values(buf, value))
        elif field == PLANE_STAT_METADATA:
            for meta in _entry_values(buf, value):
                got = dict(_fields(buf, *meta))
                stat_names[got.get(1, 0)] = _text(buf, got.get(2, (0, 0)))
    if not trace.DEVICE_PLANE.match(name):
        return {}
    paths = {}
    for meta in events:
        op, path = None, None
        for field, value in _fields(buf, *meta):
            if field == META_NAME:
                op = _text(buf, value)
            elif field == META_STATS:
                stat = dict(_fields(buf, *value))
                if stat_names.get(stat.get(STAT_METADATA_ID)) != OP_PATH_STAT:
                    continue
                if STAT_STR in stat:
                    path = _text(buf, stat[STAT_STR])
                elif STAT_REF in stat:
                    path = stat_names.get(stat[STAT_REF])
        if op and path:
            # XLA writes "<op path>:<op type>"; the type is empty here.
            paths.setdefault(op, path.rsplit(":", 1)[0])
    return paths


def from_bytes(buf: bytes) -> dict:
    """``{device op name: op path}`` over every TPU plane of a serialized
    ``XSpace``."""
    paths = {}
    for field, value in _fields(buf):
        if field == SPACE_PLANES:
            for op, path in _plane_paths(buf, value).items():
                paths.setdefault(op, path)
    return paths


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime_ns: int) -> dict:
    with open(path, "rb") as f:
        return from_bytes(f.read())


def trace_file(cell: str):
    """The newest ``*.xplane.pb`` of ``cell``'s traced window, found as
    ``harness.Tracer.stop`` finds it, or None."""
    found = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, cell, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def for_cell(cell: str) -> dict:
    """The op paths of ``cell``'s trace file (cached per file); empty when
    there is none."""
    path = trace_file(cell)
    if path is None:
        return {}
    return _read(path, os.stat(path).st_mtime_ns)


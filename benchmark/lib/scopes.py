"""Device time by ``jax.named_scope``: what ``lib/xplane.py`` drops.

``xplane.load`` keeps an operation's name, start and end. The scope an
operation was traced under (``jit(step)/jvp(DecoderLM)/block_0/moe/
moolib.moe.experts/ragged_dot``) is not on the event: it is the ``tf_op``
stat of the event's *metadata*, which ``jax.profiler.ProfileData`` does not
hand out. So this module reads the same ``.xplane.pb`` itself, with a
decoder of the protobuf wire format for the six messages it needs
(tensorflow/tsl ``xplane.proto``; field numbers below), and nothing but the
standard library.

The arithmetic (:func:`scope_seconds`) works on plain tuples and is tested
on hand-made lines; :func:`load` is tested against ``xplane.load`` on the
recorded trace that ``tests/data`` keeps.

A scope here is the LAST ``moolib.<...>`` name on an operation's path: the
innermost one (``moolib.vtrace`` inside ``moolib.loss``). A transform may
wrap it (``jvp(moolib.loss)``), so it is found by pattern, not by splitting
at ``/``. A fusion carries the path of one of the operations fused into it,
so time at a scope's edges can land on its neighbour.

One kind of operation loses its path on the way to the chip: XLA's own
expansion of ``jax.lax.ragged_dot`` names its kernels
``%ragged-dot-<...>`` and gives them that as their path too (read off the
compiled step's HLO and the first trace, PR 26). Only the dropless expert
layer calls ``ragged_dot`` in this repo, so an operation of that name
without a scope is ``moolib.moe.experts``' (``KERNELS``).
"""

from __future__ import annotations

import gzip
import re
from typing import Dict, Iterable, List, Optional, Tuple

from . import xplane

SCOPE = re.compile(r"moolib\.[A-Za-z0-9_.]+")
NO_SCOPE = "(none)"
# Kernels whose path the compiler drops, by the start of their name.
KERNELS = {"%ragged-dot": "moolib.moe.experts"}


# --- protobuf wire format ---------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: a varint as an
    int, a length-delimited field as a memoryview, fixed fields as bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = bytes(buf[pos:pos + 8]), pos + 8
        elif wire == 5:
            value, pos = bytes(buf[pos:pos + 4]), pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, wire, value


def _map_entry(buf) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 (map),
# stat_metadata = 5 (map); XLine: name = 2, timestamp_ns = 3, events = 4;
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
# XEventMetadata: id = 1, name = 2, stats = 5; XStatMetadata: name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7.

def _plane(buf) -> dict:
    name, lines, events_meta, stats_meta = "", [], {}, {}
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            key, value = _map_entry(v)
            events_meta[key] = value
        elif number == 5:
            key, value = _map_entry(v)
            stats_meta[key] = next(
                (_text(x) for n, _, x in _fields(value) if n == 2), ""
            )
    return {"name": name, "lines": lines, "events_meta": events_meta,
            "stats_meta": stats_meta}


def _event_metadata(buf, stats_meta: dict) -> Tuple[str, Optional[str]]:
    """An operation's name and its ``tf_op`` stat (None where it has
    none)."""
    name, tf_op = "", None
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 5:
            stat = {n: x for n, _, x in _fields(v)}
            if stats_meta.get(stat.get(1)) == "tf_op":
                if 5 in stat:
                    tf_op = _text(stat[5])
                elif 7 in stat:  # a reference into the stat names
                    tf_op = stats_meta.get(stat[7])
    return name, tf_op


def _line(buf) -> Tuple[str, int, list]:
    name, timestamp_ns, events = "", 0, []
    for number, _, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            timestamp_ns = v
        elif number == 4:
            e = {n: x for n, _, x in _fields(v)}
            events.append((e.get(1, 0), e.get(2, 0), e.get(3, 0)))
    return name, timestamp_ns, events


def load(path: str) -> Dict[str, List[Tuple[str, Optional[str], float, float]]]:
    """Every TPU plane's ``XLA Ops`` line as ``(name, tf_op, start ns,
    end ns)``, on the clock ``xplane.load`` uses."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, _, v in _fields(space):
        if number != 1:
            continue
        plane = _plane(v)
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        meta: Dict[int, Tuple[str, Optional[str]]] = {}
        rows = []
        for raw in plane["lines"]:
            name, timestamp_ns, events = _line(raw)
            if name != xplane.OPS_LINE:
                continue
            for metadata_id, offset_ps, duration_ps in events:
                if metadata_id not in meta:
                    meta[metadata_id] = _event_metadata(
                        plane["events_meta"].get(metadata_id, b""),
                        plane["stats_meta"],
                    )
                op, tf_op = meta[metadata_id]
                start = float(timestamp_ns) + offset_ps / 1000.0
                rows.append((op, tf_op, start, start + duration_ps / 1000.0))
        out[plane["name"]] = rows
    return out


# --- arithmetic ---------------------------------------------------------------

def scope_of(tf_op: Optional[str], name: str = "") -> str:
    found = SCOPE.findall(tf_op or "")
    if found:
        return found[-1]
    for prefix, scope in KERNELS.items():
        if name.startswith(prefix):
            return scope
    return NO_SCOPE


def scope_seconds(planes: Dict[str, list],
                  window: Optional[Tuple[float, float]] = None
                  ) -> Dict[str, float]:
    """Seconds by scope inside ``window`` (ns), mean over the chips. Time
    is self time: a ``while`` holds its body's operations, and each
    nanosecond goes to the innermost operation that covers it, so the
    scopes (with ``(none)``) add up to the chip's busy time."""
    total: Dict[str, float] = {}
    for rows in planes.values():
        events = xplane.clip(
            (xplane.Event(scope_of(tf_op, name), start, end)
             for name, tf_op, start, end in rows), window,
        )
        for scope, ns in xplane.self_times(events).items():
            total[scope] = total.get(scope, 0.0) + ns / 1e9 / len(planes)
    return total


def window_of(trace) -> Optional[Tuple[float, float]]:
    """The span of the harness's ``bench.window`` annotation in a trace
    ``xplane.load`` read, as ``xplane.summarize`` takes it."""
    return xplane.span(
        [e for e in xplane.host_spans(trace) if e.name == "bench.window"]
    )

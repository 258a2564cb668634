"""From a profiler trace to numbers: busy union, per-operation self time,
per-program time, collective time exposed, idle gaps and who owned them.

Everything works on plain ``Event`` tuples (start and end in nanoseconds), so
the arithmetic is tested on hand-made lines; :func:`load` is the only part
that reads an ``.xplane.pb`` (through ``jax.profiler.ProfileData``).

A TPU's plane is ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one event per
executed operation (a ``while`` holds its body's operations nested inside
it); its ``XLA Modules`` line one event per executed program. Host threads
are lines of ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` lands.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # start-to-done spans of asynchronous ops
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute,
# with XLA's -start/-done halves and numbered copies.
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
)


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns


Trace = Dict[str, Dict[str, List[Event]]]  # plane -> line -> events


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Every plane and line of the file as lists of :class:`Event`."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    trace: Trace = {}
    for plane in data.planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                Event(e.name, float(e.start_ns),
                      float(e.start_ns) + float(e.duration_ns))
                for e in line.events
            )
    return trace


def device_planes(trace: Trace) -> List[str]:
    return sorted(
        (p for p in trace if DEVICE_PLANE.match(p)),
        key=lambda p: int(DEVICE_PLANE.match(p).group(1)),
    )


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def measure(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The part of merged intervals ``a`` that merged intervals ``b`` leave
    uncovered."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def clip(events: Iterable[Event], window: Optional[Tuple[float, float]]):
    if window is None:
        return list(events)
    lo, hi = window
    return [
        Event(e.name, max(e.start, lo), min(e.end, hi))
        for e in events if e.end > lo and e.start < hi
    ]


def busy_ns(events: Iterable[Event]) -> float:
    """Time in which some operation ran: the union of the events."""
    return measure(union((e.start, e.end) for e in events))


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Of events of one line, nested by containment (a ``while`` holds its
    body's operations): each name's time not covered by events inside it.
    The self times of a line add up to its busy union."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    self_ns: Dict[str, float] = {}
    stack: List[list] = []  # [event, ns covered by its children]

    def close():
        event, covered = stack.pop()
        self_ns[event.name] = (
            self_ns.get(event.name, 0.0) + (event.end - event.start) - covered
        )

    for e in order:
        while stack and stack[-1][0].end <= e.start:
            close()
        if stack:
            stack[-1][1] += min(e.end, stack[-1][0].end) - e.start
        stack.append([e, 0.0])
    while stack:
        close()
    return self_ns


def short_name(name: str, limit: int = 96) -> str:
    """An operation's name as the trace gives it is its whole HLO line:
    keep the name, the result's shape (the first of a tuple's) and the
    opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    if rest.startswith("("):  # a tuple of shapes, which has spaces
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape = rest[1:i].split(", ")[0] + ",..."
        tail = rest[i + 1:].lstrip()
    else:
        shape, _, tail = rest.partition(" ")
    opcode = tail.partition("(")[0]
    return f"{head.lstrip('%')} = {shape} {opcode}"[:limit]


def exposed_collective_ns(events: Sequence[Event]) -> float:
    """Collective time during which nothing else ran on that chip: the
    union of the collective events less the union of the other operations.
    An operation that holds a collective inside it (a ``while``, a
    conditional) is its container, not something else running, and does
    not count as cover."""
    coll = sorted(
        (e for e in events if COLLECTIVE.match(e.name)),
        key=lambda e: e.start,
    )
    starts = [c.start for c in coll]

    def holds_a_collective(e: Event) -> bool:
        i = bisect.bisect_left(starts, e.start)
        while i < len(coll) and coll[i].start <= e.end:
            if coll[i].end <= e.end:
                return True
            i += 1
        return False

    rest = union(
        (e.start, e.end) for e in events
        if not COLLECTIVE.match(e.name) and not holds_a_collective(e)
    )
    return measure(subtract(union((c.start, c.end) for c in coll), rest))


def idle_gaps(events: Sequence[Event], window: Tuple[float, float],
              host: Sequence[Event], top: int = 10):
    """The longest stretches of ``window`` in which no operation ran, each
    named after the host span that covers most of it (``(none)`` where no
    span of ``host`` does). Returns ``[[name, seconds], ...]``, summed by
    name, longest first."""
    gaps = subtract([window], union((e.start, e.end) for e in events))
    by_owner: Dict[str, float] = {}
    for lo, hi in gaps:
        best, best_ns = "(none)", 0.0
        for h in host:
            over = min(hi, h.end) - max(lo, h.start)
            if over > best_ns:
                best, best_ns = h.name, over
        by_owner[best] = by_owner.get(best, 0.0) + (hi - lo)
    ranked = sorted(by_owner.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def span(events: Sequence[Event]) -> Optional[Tuple[float, float]]:
    if not events:
        return None
    return min(e.start for e in events), max(e.end for e in events)


def host_spans(trace: Trace, prefix: str = "bench.") -> List[Event]:
    """The harness's own ``TraceAnnotation`` spans on the host threads."""
    return [
        e for plane, lines in trace.items() if plane.startswith("/host:")
        for events in lines.values() for e in events
        if e.name.startswith(prefix)
    ]


def summarize(trace: Trace, window: Optional[Tuple[float, float]] = None,
              top: int = 10) -> Optional[dict]:
    """The reduction every trace-reading metric shares. ``window`` (ns, the
    trace's clock) defaults to the span of the harness's ``bench.window``
    annotation, else to the span of all device operations. None where no
    operation ran on a device."""
    planes = device_planes(trace)
    marks = [e for e in host_spans(trace) if e.name == "bench.window"]
    if window is None and marks:
        window = span(marks)
    per_chip = []
    for plane in planes:
        per_chip.append(trace[plane].get(OPS_LINE, []))
    if window is None:
        window = span([e for ops in per_chip for e in ops])
    if window is None:
        return None
    host = [e for e in host_spans(trace) if e.name != "bench.window"]
    chips = []
    for plane, ops in zip(planes, per_chip):
        ops = clip(ops, window)
        modules = clip(trace[plane].get(MODULES_LINE, []), window)
        # An asynchronous collective's span (start to done) is on its own
        # line; the operations that may hide it are on the ops line.
        spans = [
            e for e in clip(trace[plane].get(ASYNC_LINE, []), window)
            if COLLECTIVE.match(e.name)
        ]
        self_ns = self_times(ops)
        programs: Dict[str, list] = {}
        for m in modules:
            rec = programs.setdefault(m.name, [0, 0.0])
            rec[0] += 1
            rec[1] += m.end - m.start
        chips.append({
            "plane": plane,
            "busy_s": busy_ns(ops) / 1e9,
            "exposed_collective_s": exposed_collective_ns(ops + spans) / 1e9,
            "collective_s": busy_ns(
                [e for e in ops + spans if COLLECTIVE.match(e.name)]
            ) / 1e9,
            "op_self_s": {k: v / 1e9 for k, v in self_ns.items()},
            "programs": {
                k: {"count": n, "seconds": ns / 1e9}
                for k, (n, ns) in programs.items()
            },
            "idle_gaps": idle_gaps(ops, window, host, top),
        })
    if not chips or not any(c["busy_s"] > 0 for c in chips):
        return None
    ops_total: Dict[str, float] = {}
    for c in chips:
        for k, v in c["op_self_s"].items():
            k = short_name(k)
            ops_total[k] = ops_total.get(k, 0.0) + v / len(chips)
    ranked = sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": sum(c["busy_s"] for c in chips) / len(chips),
        "chips": chips,
        "device_ops": [[k, v] for k, v in ranked],
        "idle_gaps": max(
            (c["idle_gaps"] for c in chips),
            key=lambda g: sum(s for _, s in g),
        ),
    }

"""What the readers of the program's own phases and spans share.

The loop's StepScope keeps seconds by phase; the driver hands over its
summary at the window's two ends (``readings["stepscope"]``) and the rows
``train()`` logged there (``readings["rows"]``). In a traced run every phase
is also a ``moolib.<loop>.<phase>`` span on the host plane of the trace, on
the clock the device's operations are on, which is what gives an idle gap of
the device its owner.

A program older than its spans has neither the phase names nor the spans:
every function here then returns None, and the harness leaves the metric
out of the line.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import xplane

SPAN_PREFIX = "moolib."
UNOWNED = "(none)"


def phase_seconds(readings, phases: Sequence[str]) -> Optional[float]:
    """Seconds the loop spent in ``phases`` between the window's two
    ends. None where the later reading lacks one of them: a turn of the
    loop enters every phase it has, so a name that is missing is a name
    the program does not have."""
    pair = readings.get("stepscope")
    if not pair or not pair[0] or not pair[1]:
        return None
    a, b = pair
    if any(p not in b["phases"] for p in phases):
        return None
    return sum(b["phases"][p] - a["phases"].get(p, 0.0) for p in phases)


def ms_per_update(readings, phases: Sequence[str]) -> Optional[float]:
    """The phases' seconds between the two readings over the updates
    gained between the two rows, in ms."""
    seconds = phase_seconds(readings, phases)
    rows = readings.get("rows")
    if seconds is None or not rows:
        return None
    updates = rows[1]["updates"] - rows[0]["updates"]
    if updates <= 0:
        return None
    return 1e3 * seconds / updates


def share_of_wall(readings, phases: Sequence[str]) -> Optional[float]:
    """The phases' seconds over the loop's wall seconds, in percent."""
    seconds = phase_seconds(readings, phases)
    if seconds is None:
        return None
    a, b = readings["stepscope"]
    wall = b["wall_s"] - a["wall_s"]
    if wall <= 0:
        return None
    return 100.0 * seconds / wall


def owners_of(trace: xplane.Trace) -> List[xplane.Event]:
    """The program's spans that can own an idle gap: every ``moolib.``
    span but a loop's whole ``step`` (its phases are inside it)."""
    return [
        e for e in xplane.host_spans(trace, prefix=SPAN_PREFIX)
        if not e.name.endswith(".step")
    ]


def gap_owners(gaps: Sequence[Tuple[float, float]],
               owners: Sequence[xplane.Event]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` (sorted, disjoint) by owner. A gap goes,
    whole, to the span that covers most of it, as in
    :func:`xplane.idle_gaps`; of spans that cover it alike the shortest,
    which of a phase and one nested in it is the inner; ``(none)`` where no
    span touches it. One pass over both lists: a traced window holds tens
    of thousands of each."""
    order = sorted(owners, key=lambda e: (e.start, e.end - e.start))
    by_owner: Dict[str, float] = {}
    live: List[xplane.Event] = []
    k = 0
    for lo, hi in gaps:
        while k < len(order) and order[k].start < hi:
            live.append(order[k])
            k += 1
        live = [h for h in live if h.end > lo]
        best, best_ns, best_len = UNOWNED, 0.0, 0.0
        for h in live:
            over = min(hi, h.end) - max(lo, h.start)
            length = h.end - h.start
            if over > best_ns or (over == best_ns and over > 0
                                  and length < best_len):
                best, best_ns, best_len = h.name, over, length
        by_owner[best] = by_owner.get(best, 0.0) + (hi - lo)
    return by_owner


def idle_owners(trace: Optional[xplane.Trace]):
    """``[[owner, seconds], ...]``, longest first, of the idle seconds of
    the traced window (``bench.window``, else the span of the device's
    operations) on the chip that idled most. None where the trace has no
    device plane or the program no spans."""
    if trace is None:
        return None
    planes = xplane.device_planes(trace)
    owners = owners_of(trace)
    if not planes or not owners:
        return None
    marks = [e for e in xplane.host_spans(trace) if e.name == "bench.window"]
    window = xplane.span(marks) if marks else xplane.span(
        [e for p in planes for e in trace[p].get(xplane.OPS_LINE, [])]
    )
    if window is None:
        return None
    worst: Dict[str, float] = {}
    for plane in planes:
        ops = xplane.clip(trace[plane].get(xplane.OPS_LINE, []), window)
        gaps = xplane.subtract(
            [window], xplane.union((e.start, e.end) for e in ops)
        )
        found = gap_owners(gaps, owners)
        if sum(found.values()) > sum(worst.values()):
            worst = found
    return sorted(
        ([name, ns / 1e9] for name, ns in worst.items()),
        key=lambda kv: -kv[1],
    )

"""What the loop's thread waits for while it is in ``host_sync``.

Three sources, one reader each kind:

- the parts of the phase (``readings["stepscope"]``: the summary's
  ``"parts"``, seconds by ``"host_sync.<part>"``), read like a phase,
  between the window's two readings over the updates between the two rows;
- the rows' own columns ``env_step_s`` and ``env_ready_idle_s``, which the
  EnvPool's workers stamped: the envs' own step and how long a finished
  batch lay ready, over the act calls between the two rows;
- the traced window: every ``moolib.<loop>.host_sync`` span of the loop's
  thread cut by what the chip was running meanwhile, from the ``XLA
  Modules`` and ``XLA Ops`` lines of the device's plane.

A program older than its parts has none of the three: every function here
then returns None, and the harness leaves the metric out of the line. None
also where the reader could not tell (no program of the learner's found in
the window): a metric here never reads 0 for "could not tell".
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from . import xplane

LOOP = "vtrace_learner"
PHASE = "host_sync"
PARTS = ("act_wait", "action_readback", "logits_readback", "unroll_write")
# The names the `XLA Modules` line gives learner.py's three jits
# (`jit_<function>(<fingerprint>)`): make_grad_step's `step` (under a mesh
# `sharded_step`), make_apply_step's `apply`, make_act_step's `act`.
LEARN_PROGRAMS = ("jit_step", "jit_sharded_step", "jit_apply")
ACT_PROGRAMS = ("jit_act",)
# The classes a `host_sync` nanosecond falls into, in the order they claim
# it; they are disjoint and sum to the spans. `no_program` is a transfer in
# flight and nothing at all alike: the trace's `#Chip0 Host Interface` plane
# holds no line that would tell them apart (PERF.md section 7).
CLASSES = ("learn_program", "act_program", "other_program", "no_program")

Intervals = List[Tuple[float, float]]


# -- the ledger's parts -------------------------------------------------------

def part_seconds(readings, parts: Sequence[str] = PARTS
                 ) -> Optional[Dict[str, float]]:
    """Seconds in each of ``host_sync``'s ``parts`` between the window's
    two ends. None where the later reading lacks one: an act call enters
    all four, so a part that is missing is a part the program lacks."""
    pair = readings.get("stepscope")
    if not pair or not pair[0] or not pair[1]:
        return None
    a, b = (s.get("parts") or {} for s in pair)
    keys = [f"{PHASE}.{p}" for p in parts]
    if any(k not in b for k in keys):
        return None
    return {p: b[k] - a.get(k, 0.0) for p, k in zip(parts, keys)}


def updates_between(readings) -> Optional[float]:
    rows = readings.get("rows")
    if not rows:
        return None
    updates = rows[1]["updates"] - rows[0]["updates"]
    return updates if updates > 0 else None


def act_calls_between(readings, context) -> Optional[float]:
    """Act calls between the two rows: one a batch of env steps."""
    rows = readings.get("rows")
    batch = context.get("cell", {}).get("train_config", {}).get(
        "actor_batch_size"
    )
    if not rows or not batch:
        return None
    calls = (rows[1]["env_steps"] - rows[0]["env_steps"]) / batch
    return calls if calls > 0 else None


def parts_ms_per_update(readings, parts: Sequence[str]) -> Optional[float]:
    """The rule of ``spans.ms_per_update``, for parts of ``host_sync``."""
    seconds = part_seconds(readings, parts)
    updates = updates_between(readings)
    if seconds is None or updates is None:
        return None
    return 1e3 * sum(seconds.values()) / updates


def print_sync_parts(readings, context) -> None:
    """``[sync_parts]``: the four parts in ms an update and ms an act call,
    and their sum over the phase's own seconds."""
    seconds = part_seconds(readings)
    updates = updates_between(readings)
    if seconds is None or updates is None:
        return
    a, b = readings["stepscope"]
    phase = b["phases"].get(PHASE, 0.0) - a["phases"].get(PHASE, 0.0)
    calls = act_calls_between(readings, context)
    fields = []
    for part in PARTS:
        field = f"{part}={1e3 * seconds[part] / updates:.3f}"
        if calls:
            field += f"/{1e3 * seconds[part] / calls:.4f}"
        fields.append(field)
    total = sum(seconds.values())
    print(
        "[sync_parts] ms/update" + ("/ms/act_call " if calls else " ")
        + " ".join(fields)
        + f" parts={1e3 * total / updates:.3f} "
        f"host_sync={1e3 * phase / updates:.3f} "
        f"parts_over_phase={total / phase if phase > 0 else float('nan'):.4f}"
        f" updates={updates:g}" + (f" act_calls={calls:g}" if calls else ""),
        flush=True,
    )


# -- the rows' columns --------------------------------------------------------

def row_ms_per_act(readings, context, column: str) -> Optional[float]:
    """A cumulative column of seconds, between the two rows, over the act
    calls between them, in ms. None where the rows lack the column."""
    rows = readings.get("rows")
    calls = act_calls_between(readings, context)
    if not rows or calls is None or any(column not in r for r in rows):
        return None
    return 1e3 * (rows[1][column] - rows[0][column]) / calls


# -- the traced window --------------------------------------------------------

def intersect(a: Intervals, b: Intervals) -> Intervals:
    """The part of merged intervals ``a`` that merged intervals ``b``
    cover."""
    return xplane.subtract(a, xplane.subtract(a, b))


def _program(name: str) -> str:
    return name.partition("(")[0]


def cut_by_chip(spans: Intervals, modules: Sequence[xplane.Event],
                ops: Sequence[xplane.Event]) -> Optional[Dict[str, float]]:
    """Nanoseconds of ``spans`` (merged) by what the chip ran meanwhile:
    ``CLASSES``, which sum to the spans, and ``ops_idle``, the nanoseconds
    in which the ``XLA Ops`` line ran nothing (all of ``no_program`` and
    the gaps between a program's operations). None where ``modules`` holds
    none of the learner's three programs: then nothing says what ran."""
    by_kind: Dict[str, list] = {"learn": [], "act": [], "other": []}
    for m in modules:
        name = _program(m.name)
        kind = ("learn" if name in LEARN_PROGRAMS
                else "act" if name in ACT_PROGRAMS else "other")
        by_kind[kind].append((m.start, m.end))
    if not by_kind["learn"] and not by_kind["act"]:
        return None
    out, left = {}, spans
    for cls, kind in zip(CLASSES, ("learn", "act", "other")):
        cover = xplane.union(by_kind[kind])
        out[cls] = xplane.measure(intersect(left, cover))
        left = xplane.subtract(left, cover)
    out["no_program"] = xplane.measure(left)
    out["ops_idle"] = xplane.measure(xplane.subtract(
        spans, xplane.union((e.start, e.end) for e in ops)
    ))
    return out


def sync_device(readings) -> Optional[dict]:
    """The pass the two ``device_trace`` readers share, made once a run
    (kept in ``readings``): the loop's ``host_sync`` spans inside
    ``bench.window``, and their ``act_wait`` parts alone, cut by what the
    first chip ran. ``{"updates": n, "host_sync": {...}, "act_wait":
    {...}}`` in nanoseconds, with ``spans`` the spans' own sum;
    prints ``[sync_device]``. None, with the reason printed, where there
    is nothing to cut or nothing to cut it by."""
    if "sync_device" in readings:
        return readings["sync_device"]
    readings["sync_device"] = found = _sync_device(readings.get("trace"))
    return found


def _sync_device(trace: Optional[xplane.Trace]) -> Optional[dict]:
    if trace is None:
        return None
    planes = xplane.device_planes(trace)
    host = xplane.host_spans(trace, prefix=f"moolib.{LOOP}.")
    marks = [e for e in xplane.host_spans(trace) if e.name == "bench.window"]
    if not planes or not host or not marks:
        return None  # a CPU run, or a program with no spans
    window = xplane.span(marks)
    host = xplane.clip(host, window)
    updates = sum(1 for e in host if e.name.endswith(".grad_dispatch"))
    phase = [e for e in host if e.name.endswith("." + PHASE)]
    part = [e for e in host if e.name.endswith(f".{PHASE}.act_wait")]
    if not part:
        return None  # the program is older than the parts
    if not updates:
        print("[sync_device] no reading: no grad_dispatch span in the "
              "traced window", flush=True)
        return None
    lines = trace[planes[0]]
    modules = xplane.clip(lines.get(xplane.MODULES_LINE, []), window)
    ops = xplane.clip(lines.get(xplane.OPS_LINE, []), window)
    found = {"updates": updates}
    for key, events in (("host_sync", phase), ("act_wait", part)):
        spans = xplane.union((e.start, e.end) for e in events)
        cut = cut_by_chip(spans, modules, ops)
        if cut is None:
            print("[sync_device] no reading: none of "
                  f"{LEARN_PROGRAMS + ACT_PROGRAMS} on the "
                  f"{xplane.MODULES_LINE!r} line of {planes[0]} "
                  f"({sorted({_program(m.name) for m in modules})[:8]})",
                  flush=True)
            return None
        cut["spans"] = xplane.measure(spans)
        found[key] = cut
    print("[sync_device] ms/update " + " ".join(
        f"{key}:" + ",".join(
            f"{name}={cut[name] / 1e6 / updates:.3f}"
            for name in CLASSES + ("ops_idle", "spans")
        ) for key, cut in ((k, found[k]) for k in ("host_sync", "act_wait"))
    ) + f" updates={updates}", flush=True)
    return found


def sync_device_ms_per_update(readings, name: str) -> Optional[float]:
    """One number of the ``host_sync`` spans' cut, in ms an update of the
    traced window."""
    found = sync_device(readings)
    if found is None:
        return None
    return found["host_sync"][name] / 1e6 / found["updates"]

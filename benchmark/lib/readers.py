"""Small pieces the per-layer readers share. A reader is
``read(readings, context) -> value or None``; None means there was nothing
to read, and the harness leaves the metric out of the line."""

from __future__ import annotations

from . import counts, peaks


def stepscope_share(readings, phase: str):
    """Seconds the loop's StepScope attributed to ``phase`` over the loop's
    wall seconds, between the window's two ends, in percent."""
    pair = readings.get("stepscope")
    if not pair or not pair[0] or not pair[1]:
        return None
    a, b = pair
    wall = b["wall_s"] - a["wall_s"]
    if wall <= 0:
        return None
    spent = b["phases"].get(phase, 0.0) - a["phases"].get(phase, 0.0)
    return 100.0 * spent / wall


def step_program(chip: dict):
    """The program that took most of a chip's traced time: the train step.
    Returns ``(name, count, seconds)`` or None."""
    if not chip["programs"]:
        return None
    name, rec = max(chip["programs"].items(), key=lambda kv: kv[1]["seconds"])
    return name, rec["count"], rec["seconds"]


def device_seconds_per_step(readings):
    """Mean over the chips of the step program's device time per
    execution."""
    summary = readings.get("summary")
    if not summary:
        return None
    per_chip = []
    for chip in summary["chips"]:
        found = step_program(chip)
        if found is None or found[1] == 0:
            return None
        per_chip.append(found[2] / found[1])
    return sum(per_chip) / len(per_chip)


def idle_share(readings):
    summary = readings.get("summary")
    if not summary or summary["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])


def step_roofline(readings, context):
    """The counts and the least time of one step on one chip of this
    kind (an unknown kind raises)."""
    return counts.roofline(
        context["config"], readings["frames_per_step_per_chip"],
        peaks.peaks(context["device"]["kind"]),
    )

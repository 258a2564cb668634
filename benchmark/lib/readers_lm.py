"""What the language-model cell's per-layer readers share: device seconds
by named scope, a step at a time, and the counts they are held against. A
reader returns None where the run has nothing to read (no trace, or a
program without the scopes or counters)."""

from __future__ import annotations

from . import counts_lm, peaks
from .readers import device_seconds_per_step, step_program

MOE_SCOPES = ("moolib.moe.route", "moolib.moe.gather", "moolib.moe.experts",
              "moolib.moe.combine")
DISPATCH_SCOPES = ("moolib.moe.route", "moolib.moe.gather",
                   "moolib.moe.combine")
HEAD_LOSS_SCOPES = ("moolib.lm.head", "moolib.loss", "moolib.vtrace")


def scope_seconds_per_step(readings, names):
    """Device seconds a step spends in the named scopes: their seconds in
    the traced window, scaled by the step program's seconds a step over its
    seconds in the window. None where the trace holds none of them."""
    by_scope = readings.get("scope_seconds")
    summary = readings.get("summary")
    per_step = device_seconds_per_step(readings)
    if not by_scope or not summary or per_step is None:
        return None
    if not any(name in by_scope for name in names):
        return None
    in_window = sum(
        step_program(chip)[2] for chip in summary["chips"]
    ) / len(summary["chips"])
    if in_window <= 0:
        return None
    spent = sum(by_scope.get(name, 0.0) for name in names)
    return spent * per_step / in_window


def share_of_step(readings, names):
    """Percent of the step's device time spent in the named scopes."""
    spent = scope_seconds_per_step(readings, names)
    if spent is None:
        return None
    return 100.0 * spent / device_seconds_per_step(readings)


def model(context) -> dict:
    return context["config"]["model"]["kwargs"]


def flops_parts(readings, context):
    counters = readings.get("counters")
    if not counters or "done_column" not in readings:
        return None
    return counts_lm.forward_flops(
        model(context), readings["frames_per_step_per_chip"],
        counters["moe_assignments_held"], readings["done_column"],
    )


def chip_peaks(context) -> dict:
    return peaks.peaks(context["device"]["kind"])

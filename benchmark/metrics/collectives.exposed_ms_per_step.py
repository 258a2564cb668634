"""Collective time during which no other operation ran, on the worst chip,
per step traced."""
from benchmark.lib.readers import step_program


def read(readings, context):
    summary = readings.get("summary")
    if not summary or len(summary["chips"]) < 2:
        return None
    worst = None
    for chip in summary["chips"]:
        found = step_program(chip)
        if found is None or found[1] == 0:
            return None
        per_step = chip["exposed_collective_s"] / found[1] * 1e3
        worst = per_step if worst is None else max(worst, per_step)
    return worst

"""Model FLOPs utilisation: the step's model FLOPs (3x forward, nothing
recomputed) times steps per second on the host clock, over one chip's peak."""
from benchmark.lib.readers import step_roofline


def read(readings, context):
    if "steps_per_s" not in readings:
        return None
    r = step_roofline(readings, context)
    peak = r["flops"] / r["flops_seconds"]
    return 100.0 * r["flops"] * readings["steps_per_s"] / peak

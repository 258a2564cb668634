"""Assignments of the fullest held expert over the mean of the held
experts, both averaged over the layers: the step's own counters."""


def read(readings, context):
    counters = readings.get("counters")
    if not counters or not counters.get("moe_load_mean"):
        return None
    return counters["moe_load_max"] / counters["moe_load_mean"]

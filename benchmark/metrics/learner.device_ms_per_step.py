"""Device time of the step program per execution, from the trace's
program line, mean over the chips."""
from benchmark.lib.readers import device_seconds_per_step


def read(readings, context):
    seconds = device_seconds_per_step(readings)
    return None if seconds is None else seconds * 1e3

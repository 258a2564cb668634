"""Milliseconds of the loop's thread per update blocked reading the
gradient steps' loss, entropy and gradient norm back for a log row."""
from benchmark.lib.spans import ms_per_update


def read(readings, context):
    return ms_per_update(readings, ("metrics_drain",))

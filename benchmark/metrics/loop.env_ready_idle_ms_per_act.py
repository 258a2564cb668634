"""How long a finished batch lay ready before the loop asked for it, in ms
an act call (the rows' ``env_ready_idle_s``): the room a shorter turn has
before ``env_wait`` takes its place."""
from benchmark.lib.waits import row_ms_per_act


def read(readings, context):
    return row_ms_per_act(readings, context, "env_ready_idle_s")

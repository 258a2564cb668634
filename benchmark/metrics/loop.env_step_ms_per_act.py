"""The envs' own step, in ms an act call: from a batch's dispatch to the
finish stamp of its slowest worker's slice (the rows' ``env_step_s``),
whenever the loop came back for it. The floor under a turn of the loop."""
from benchmark.lib.waits import row_ms_per_act


def read(readings, context):
    return row_ms_per_act(readings, context, "env_step_s")

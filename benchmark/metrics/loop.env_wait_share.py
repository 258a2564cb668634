"""Share of the loop's wall time spent waiting for an env batch."""
from benchmark.lib.readers import stepscope_share


def read(readings, context):
    return stepscope_share(readings, "env_wait")

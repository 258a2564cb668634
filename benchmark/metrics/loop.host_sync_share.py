"""Share of the loop's wall time in the blocking action and logits
readback after each act step."""
from benchmark.lib.readers import stepscope_share


def read(readings, context):
    return stepscope_share(readings, "host_sync")

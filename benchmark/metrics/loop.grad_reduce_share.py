"""Share of the loop's wall time inside ``Accumulator.reduce_gradients``."""
from benchmark.lib.readers import stepscope_share


def read(readings, context):
    return stepscope_share(readings, "grad_allreduce")

"""Of the traced window's ``host_sync`` spans, the milliseconds per update
during which the chip ran the gradient or the apply program: the loop's
thread waiting for its next actions behind the learner's own work. Prints
``[sync_device]``, every class of what the chip ran meanwhile."""
from benchmark.lib.waits import sync_device_ms_per_update


def read(readings, context):
    return sync_device_ms_per_update(readings, "learn_program")

"""Milliseconds of the loop's thread per update in the calls of the
gradient step and the apply step (dispatch, not the device's time)."""
from benchmark.lib.spans import ms_per_update


def read(readings, context):
    return ms_per_update(readings, ("grad_dispatch", "apply_dispatch"))

"""Percent of the step's device time in ``moolib.moe.route``, ``gather``
and ``combine``: what a dense model would not pay."""
from benchmark.lib import readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, readers_lm.DISPATCH_SCOPES)

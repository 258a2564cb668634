"""Device milliseconds of ``moolib.vtrace`` a step: the action
log-probabilities and the reverse scan over the unroll."""
from benchmark.lib import readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(readings, ("moolib.vtrace",))
    return None if seconds is None else seconds * 1e3

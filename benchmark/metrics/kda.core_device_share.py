"""Percent of the step's device time in ``moolib.lm.kda_core``: the gated
delta rule's recurrence of every delta-rule block, forward, rebuilt and
backward, whatever implements it. None where the program has no such
scope."""
from benchmark.lib import counts_kda, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_kda.CORE_SCOPE)

"""Percent of the step's device time in ``moolib.lm.blockdiff_local``: the
``D x D`` scores of every row's own block against its own copy's keys and
the merge with the flash call's result by the two row statistics, every
layer's, forward, rebuilt and backward. None where the program has no such
scope."""
from benchmark.lib import counts_sdar, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_sdar.LOCAL_SCOPE)

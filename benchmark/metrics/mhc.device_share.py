"""Percent of the step's device time in the residual mixing:
``moolib.lm.hc_mix`` (statistics, the ``phi`` product, the sigmoids, the
Sinkhorn iterations), ``moolib.lm.hc_pre`` (the weighted sum into the
sublayer's input) and ``moolib.lm.hc_post`` (the remix and the gated
write-back), forward and backward. None where the program has no such
scopes."""
from benchmark.lib import counts_mhc, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_mhc.SCOPES)

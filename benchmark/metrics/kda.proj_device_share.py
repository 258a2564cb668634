"""Percent of the step's device time in ``moolib.lm.kda_proj``: the
delta-rule blocks' three projections, their convolutions, ``silu`` and
l2norm, both low-rank gates, ``beta``, the output's norm, gate and
projection. None where the program has no such scope."""
from benchmark.lib import counts_kda, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_kda.PROJ_SCOPE)

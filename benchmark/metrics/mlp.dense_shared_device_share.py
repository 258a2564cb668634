"""Percent of the step's device time in ``moolib.lm.mlp_dense`` and
``moolib.moe.shared``: the gated MLPs every token passes, the first
block's and the shared expert of every sparse block."""
from benchmark.lib import readers_lm


def read(readings, context):
    return readers_lm.share_of_step(
        readings, ("moolib.lm.mlp_dense", "moolib.moe.shared")
    )

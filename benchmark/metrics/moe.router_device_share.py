"""Percent of the step's device time in ``moolib.moe.router_mlp``: the MLP
router of every expert layer, forward, rebuilt and backward (the
down-projection, the state of the layer before, the norm, two hidden
layers, the output and its softmax). None where the program has no such
scope."""
from benchmark.lib import counts_cca, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_cca.ROUTER_SCOPE)

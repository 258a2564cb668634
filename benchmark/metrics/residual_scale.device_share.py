"""Percent of the step's device time in ``moolib.lm.residual_scale``: the
scaled skeleton's two merges a block, ``a_r (x + b_r) + a_y (y + b_y)`` in
float32, forward, rebuilt and backward (with the four vectors' gradients,
each a reduction over the tokens). None where the program has no such
scope."""
from benchmark.lib import counts_cca, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_cca.SCALE_SCOPE)

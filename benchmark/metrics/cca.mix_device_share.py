"""Percent of the step's device time in ``moolib.lm.cca_mix``: what
compressed convolutional attention does between its projections and its
core, every block's, forward, rebuilt and backward (both convolutions, the
query-key mean, the normalisation, the temperature, the rotary). None
where the program has no such scope."""
from benchmark.lib import counts_cca, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_cca.MIX_SCOPE)

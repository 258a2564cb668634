"""Percent of the step's device time in ``moolib.lm.mla_proj``: both
low-rank paths, their norms, the rotary, the assembly of heads and the
output projection of every latent-attention block."""
from benchmark.lib import readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, ("moolib.lm.mla_proj",))

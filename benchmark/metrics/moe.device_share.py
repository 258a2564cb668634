"""Percent of the step's device time in the four ``moolib.moe.*`` scopes:
route, gather, experts, combine."""
from benchmark.lib import readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, readers_lm.MOE_SCOPES)

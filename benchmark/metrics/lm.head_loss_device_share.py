"""Percent of the step's device time in ``moolib.lm.head``,
``moolib.loss`` and ``moolib.vtrace``: the head over the vocabulary held,
the loss over its logits, and the V-trace scan."""
from benchmark.lib import readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, readers_lm.HEAD_LOSS_SCOPES)

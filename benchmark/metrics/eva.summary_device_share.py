"""Percent of the step's device time in ``moolib.lm.eva_summary`` (the
chunk pooling: weights, summary keys and values, both directions) and
``moolib.lm.eva_merge`` (the two partial results merged by their row
statistics). None where the program has no such scopes."""
from benchmark.lib import counts_eva, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_eva.SUMMARY_SCOPES)

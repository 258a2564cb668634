"""Percent of the step's device time in ``moolib.lm.blockdiff_rows``: the
building of the copies' inputs (the masked ids and their embedding rows)
and the choice of every token's scored row, forward and backward. None
where the program has no such scope."""
from benchmark.lib import counts_sdar, readers_lm


def read(readings, context):
    return readers_lm.share_of_step(readings, counts_sdar.ROWS_SCOPE)

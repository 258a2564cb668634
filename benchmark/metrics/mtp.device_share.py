"""Percent of the step's device time under ``moolib.lm.mtp``: the
multi-token-prediction module's projection, its block (whatever scope
inside it an operation carries), its pass through the head and its
cross-entropy."""
from benchmark.lib import readers_latent, readers_lm


def read(readings, context):
    under = readings.get("seconds_under_mtp")
    if not under:
        return None
    scope = readers_latent.MTP_SCOPE
    return readers_lm.share_of_step(
        dict(readings, scope_seconds={scope: under}), (scope,)
    )

"""What the block-diffusion cell's per-layer readers share beyond
``lib/readers_lm.py``: the counts of its own description
(``lib/counts_sdar.py``). A reader returns None where the run has nothing
to read (no trace, a program without the scopes or counters, a model
without ``diffusion``)."""

from __future__ import annotations

from . import counts_sdar, readers_lm


def flops_parts(readings, context):
    model = readers_lm.model(context)
    counters = readings.get("counters")
    if not counters or "done_column" not in readings or not model.get(
        "diffusion"
    ):
        return None
    return counts_sdar.forward_flops(
        model, counters["moe_assignments_held"], readings["done_column"]
    )

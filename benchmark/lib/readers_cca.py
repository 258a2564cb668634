"""What the compressed-attention cell's per-layer readers share beyond
``lib/readers_lm.py``: the counts of its own description
(``lib/counts_cca.py``). A reader returns None where the run has nothing
to read."""

from __future__ import annotations

from . import counts_cca, readers_lm


def flops_parts(readings, context):
    model = readers_lm.model(context)
    counters = readings.get("counters")
    if not counters or "done_column" not in readings or not any(
        kind.get("cca") for kind in model["attention_kinds"].values()
    ):
        return None
    return counts_cca.forward_flops(
        model, readings["frames_per_step_per_chip"],
        counters["moe_assignments_held"], readings["done_column"],
    )

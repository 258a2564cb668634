"""What the chunk-summary cell's per-layer readers share beyond
``lib/readers_lm.py``: the counts of its own description
(``lib/counts_eva.py``). A reader returns None where the run has nothing
to read."""

from __future__ import annotations

from . import counts_eva, readers_lm


def flops_parts(readings, context):
    model = readers_lm.model(context)
    if "done_column" not in readings or not any(
        kind.get("eva") for kind in model["attention_kinds"].values()
    ):
        return None
    return counts_eva.forward_flops(
        model, readings["frames_per_step_per_chip"], readings["done_column"]
    )

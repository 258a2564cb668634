"""What the latent-attention cell's per-layer readers share beyond
``lib/readers_lm.py``: the counts of its own description
(``lib/counts_mla.py``) and device time *under* a scope. A reader returns
None where the run has nothing to read."""

from __future__ import annotations

from . import counts_mla, readers_lm, scopes, xplane

MTP_SCOPE = "moolib.lm.mtp"


def seconds_under(planes, window, scope: str) -> float:
    """Device seconds inside ``window`` of the operations traced under
    ``scope``, whatever scope inside it they carry (``scopes.scope_seconds``
    gives an operation to its innermost scope, so a module that holds a
    whole block keeps only what no inner scope names). Self time, mean over
    the chips."""
    total = 0.0
    for rows in planes.values():
        events = xplane.clip(
            (xplane.Event(
                "in" if scope in scopes.SCOPE.findall(tf_op or "") else "out",
                start, end,
            ) for _, tf_op, start, end in rows), window,
        )
        total += xplane.self_times(events).get("in", 0.0) / 1e9 / len(planes)
    return total


def flops_parts(readings, context):
    counters = readings.get("counters")
    model = readers_lm.model(context)
    if not counters or "done_column" not in readings or not any(
        kind.get("latent") for kind in model["attention_kinds"].values()
    ):
        return None
    return counts_mla.forward_flops(
        model, readings["frames_per_step_per_chip"],
        counters["moe_assignments_held"], readings["done_column"],
    )

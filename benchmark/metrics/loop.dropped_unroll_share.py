"""Unrolls the backpressure dropped over unrolls the actors produced in
the window, from the rows ``train()`` returned."""


def read(readings, context):
    rows, produced = readings.get("rows"), readings.get("unrolls_produced")
    if not rows or not produced:
        return None
    a, b = rows
    return 100.0 * (b["dropped_unrolls"] - a["dropped_unrolls"]) / produced

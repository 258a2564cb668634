"""Milliseconds of the loop's thread per update spent driving the
Accumulator: ``acc_update`` (every turn's ``update()``), ``grad_allreduce``
(``reduce_gradients``) and ``grad_result`` (taking the reduced gradients
and zeroing them)."""
from benchmark.lib.spans import ms_per_update


def read(readings, context):
    return ms_per_update(
        readings, ("acc_update", "grad_allreduce", "grad_result")
    )

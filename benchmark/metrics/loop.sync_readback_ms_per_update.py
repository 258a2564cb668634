"""Milliseconds of the loop's thread per update spent copying the act
step's results out once they are computed: ``host_sync``'s parts
``action_readback`` and ``logits_readback``. Prints ``[sync_parts]``, all
four parts and their sum over the phase."""
from benchmark.lib.waits import parts_ms_per_update, print_sync_parts


def read(readings, context):
    print_sync_parts(readings, context)
    return parts_ms_per_update(
        readings, ("action_readback", "logits_readback")
    )

"""Milliseconds of the loop's thread per update spent in ``host_sync``'s
first part, ``act_wait``: ``block_until_ready`` on the act step's action,
the wait for the observation's copy in, the act program and whatever the
device had queued ahead of it. Nothing is read back yet."""
from benchmark.lib.waits import parts_ms_per_update


def read(readings, context):
    return parts_ms_per_update(readings, ("act_wait",))

"""Of the traced window's ``host_sync`` spans, the milliseconds per update
during which the chip's ``XLA Ops`` line ran nothing: host and chip both
waiting, which no faster program shortens."""
from benchmark.lib.waits import sync_device_ms_per_update


def read(readings, context):
    return sync_device_ms_per_update(readings, "ops_idle")

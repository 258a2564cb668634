"""1 - union of device-operation intervals over the traced window."""
from benchmark.lib.readers import idle_share


def read(readings, context):
    return idle_share(readings)

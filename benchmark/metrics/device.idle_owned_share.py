"""Of the idle seconds of the traced window, the share inside a gap whose
owner is one of the program's named spans (``moolib.<loop>.<phase>``, the
Accumulator's ``moolib.acc.*``). Prints the ten largest owners."""
from benchmark.lib.spans import UNOWNED, idle_owners


def read(readings, context):
    owners = idle_owners(readings.get("trace"))
    if not owners:
        return None
    idle = sum(seconds for _, seconds in owners)
    if idle <= 0:
        return None
    print("[idle_owners] " + " ".join(
        f"{name}={seconds:.4f}s" for name, seconds in owners[:10]
    ) + f" idle={idle:.4f}s", flush=True)
    owned = sum(seconds for name, seconds in owners if name != UNOWNED)
    return 100.0 * owned / idle

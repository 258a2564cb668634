"""Peak device memory on the fullest chip after the window."""


def read(readings, context):
    peak = context["device"].get("memory_peak_bytes", 0)
    return peak / 1e9 if peak > 0 else None

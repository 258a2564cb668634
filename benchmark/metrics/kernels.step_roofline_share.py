"""The least time one step could take on this chip (the larger of model
FLOPs over peak FLOP/s and least bytes over peak HBM bytes/s, both counted
from shapes) over the step's device time. Over 100% means the count is
wrong."""
from benchmark.lib.readers import device_seconds_per_step, step_roofline


def read(readings, context):
    seconds = device_seconds_per_step(readings)
    if seconds is None:
        return None
    r = step_roofline(readings, context)
    print(f"[roofline] one step: {r['flops']:.4g} FLOPs = "
          f"{r['flops_seconds'] * 1e3:.3f} ms at peak, "
          f"{r['least_bytes']:.4g} bytes = {r['bytes_seconds'] * 1e3:.3f} ms "
          f"at peak; bound by {r['bound_by']}; device time "
          f"{seconds * 1e3:.3f} ms", flush=True)
    return 100.0 * r["least_seconds"] / seconds

"""The least time one whole step could take on this chip (the larger of the
model's FLOPs over peak FLOP/s and its least bytes over peak HBM bytes/s,
``lib/counts_lm.py``, counted from the step's own ``moe_assignments_held``
and the batch's episode boundaries) over the step's device time: what
``kernels.step_roofline_share`` is to the cells ``lib/counts.py`` can walk.
Over 100% means the count is wrong."""
from benchmark.lib import counts_lm, readers_lm
from benchmark.lib.readers import device_seconds_per_step


def read(readings, context):
    parts = readers_lm.flops_parts(readings, context)
    seconds = device_seconds_per_step(readings)
    if parts is None or seconds is None:
        return None
    r = counts_lm.step_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"],
        readings["counters"]["moe_assignments_held"],
        readers_lm.chip_peaks(context),
    )
    print(f"[roofline] one step: {r['flops']:.4g} FLOPs, "
          f"{r['least_bytes']:.4g} bytes, {r['least_seconds'] * 1e3:.3f} ms "
          f"at peak (bound by {r['bound_by']}); device time "
          f"{seconds * 1e3:.3f} ms", flush=True)
    return 100.0 * r["least_seconds"] / seconds

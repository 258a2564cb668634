"""The least time the chip could take for the expert layers' grouped
products of one step (the larger of their FLOPs over peak FLOP/s and their
least bytes over peak HBM bytes/s, counted from the step's own
``moe_assignments_held``) over the device time of ``moolib.moe.experts``.
Over 100% means the count is wrong."""
from benchmark.lib import counts_lm, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, ("moolib.moe.experts",)
    )
    counters = readings.get("counters")
    if not seconds or not counters:
        return None
    r = counts_lm.experts_least(
        readers_lm.model(context), counters["moe_assignments_held"],
        readers_lm.chip_peaks(context),
    )
    print(f"[roofline] experts of one step: "
          f"{counters['moe_assignments_held']:.0f} assignments held, "
          f"{r['flops']:.4g} FLOPs, {r['least_bytes']:.4g} bytes, "
          f"{r['least_seconds'] * 1e3:.3f} ms at peak (bound by "
          f"{r['bound_by']}); device time {seconds * 1e3:.3f} ms",
          flush=True)
    return 100.0 * r["least_seconds"] / seconds

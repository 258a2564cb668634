"""The least time the chip could take for the residual mixing of one step
(``lib/counts_mhc.py``: every stream read and written as often as a fused
pass has to, forward and backward, 2 B an element; or the ``phi``
products' and the remix's FLOPs, 3x forward, if larger; over the peaks)
over the device time of ``moolib.lm.hc_mix`` + ``hc_pre`` + ``hc_post``.
Over 100% means the count is wrong. None where the program has no such
scopes or its description no ``residual``."""
from benchmark.lib import counts_mhc, readers_lm


def read(readings, context):
    model = readers_lm.model(context)
    seconds = readers_lm.scope_seconds_per_step(readings, counts_mhc.SCOPES)
    if not seconds or not model.get("residual"):
        return None
    r = counts_mhc.mixing_least(
        model, readings["frames_per_step_per_chip"],
        readers_lm.chip_peaks(context),
    )
    print(f"[roofline] residual mixing of one step, "
          f"{counts_mhc.sublayers(model)} sublayers: {r['flops']:.4g} FLOPs, "
          f"{r['least_bytes']:.4g} bytes, {r['least_seconds'] * 1e3:.3f} ms "
          f"at peak (bound by {r['bound_by']}); device time "
          f"{seconds * 1e3:.3f} ms", flush=True)
    return 100.0 * r["least_seconds"] / seconds

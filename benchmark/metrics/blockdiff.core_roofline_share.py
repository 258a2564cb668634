"""The least time the chip could take for the flash calls of one step of
the decoder under block diffusion (FLOPs of the tiles the rank rule
leaves, the copies as grouped query heads of the clean keys, 3x forward,
or their least bytes, over the peaks: ``lib/counts_sdar.py``) over the
device time of ``moolib.lm.attn_core``. The rows' own blocks and the merge
are ``blockdiff.local_device_share``'s, not this scope's. Over 100% means
the count is wrong. None where the description has no ``diffusion``."""
from benchmark.lib import counts_sdar, readers_lm, readers_sdar


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, counts_sdar.CORE_SCOPE
    )
    parts = readers_sdar.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    peaks = readers_lm.chip_peaks(context)
    r = counts_sdar.core_least(
        readers_lm.model(context), parts, readings["done_column"], peaks
    )
    print(f"[roofline] flash calls of one step: {r['flops']:.4g} FLOPs in "
          f"visited tiles ({3 * parts['attention_pairs']:.4g} in visible "
          f"pairs, the rows' own blocks among them), "
          f"{r['least_seconds'] * 1e3:.3f} ms at peak "
          f"({peaks['flops_per_s']:.4g} FLOP/s, "
          f"{peaks['hbm_bytes_per_s']:.4g} B/s; bound by {r['bound_by']}); "
          f"device time {seconds * 1e3:.3f} ms; backend "
          f"{readings.get('attention_backend')!r}", flush=True)
    return 100.0 * r["least_seconds"] / seconds

"""The least time the chip could take for the attention cores of one step
of the decoder with compressed convolutional attention (FLOPs of the
visible key blocks only at its query heads, 3x forward, or their least
bytes, over the peaks: ``lib/counts_cca.py`` on ``counts_lm``'s rule, a
block a repeat) over the device time of ``moolib.lm.attn_core``. Over
100% means the count is wrong. None where the description has no kind
with ``cca``."""
from benchmark.lib import counts_cca, readers_cca, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, counts_cca.CORE_SCOPE
    )
    parts = readers_cca.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    r = counts_cca.core_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"], readers_lm.chip_peaks(context),
    )
    print(f"[roofline] attention cores of one step: {r['flops']:.4g} FLOPs "
          f"in visible tiles ({3 * parts['attention_pairs']:.4g} in visible "
          f"pairs), {r['least_seconds'] * 1e3:.3f} ms at peak (bound by "
          f"{r['bound_by']}); device time {seconds * 1e3:.3f} ms; backend "
          f"{readings.get('attention_backend')!r}", flush=True)
    return 100.0 * r["least_seconds"] / seconds

"""The least time the chip could take for both attention calls of every
block of one step (FLOPs of the visible tiles, local and summary, 3x
forward, or their least bytes, over the peaks; ``lib/counts_eva.py``) over
the device time of ``moolib.lm.attn_core``. Over 100% means the count is
wrong. None where the program has no such scope or its description no
attention kind with ``eva``."""
from benchmark.lib import counts_eva, readers_eva, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, ("moolib.lm.attn_core",)
    )
    parts = readers_eva.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    r = counts_eva.attention_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"], readers_lm.chip_peaks(context),
    )
    print(f"[roofline] chunk-summary attention cores of one step: "
          f"{r['flops']:.4g} FLOPs in visible tiles "
          f"({3 * parts['attention_pairs']:.4g} in visible pairs), "
          f"{r['least_bytes']:.4g} bytes, {r['least_seconds'] * 1e3:.3f} ms "
          f"at peak (bound by {r['bound_by']}); device time "
          f"{seconds * 1e3:.3f} ms; backend "
          f"{readings.get('attention_backend')!r}", flush=True)
    return 100.0 * r["least_seconds"] / seconds

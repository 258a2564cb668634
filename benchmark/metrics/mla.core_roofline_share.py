"""The least time the chip could take for the latent attention's cores of
one step (FLOPs of the flash kernels' visible tiles at a head of 256, 3x
forward, or their least bytes, over the peaks; ``lib/counts_mla.py``) over
the device time of ``moolib.lm.attn_core``. Over 100% means the count is
wrong."""
from benchmark.lib import counts_mla, readers_latent, readers_lm


def read(readings, context):
    seconds = readers_lm.scope_seconds_per_step(
        readings, ("moolib.lm.attn_core",)
    )
    parts = readers_latent.flops_parts(readings, context)
    if not seconds or parts is None:
        return None
    r = counts_mla.attention_least(
        readers_lm.model(context), parts,
        readings["frames_per_step_per_chip"], readers_lm.chip_peaks(context),
    )
    print(f"[roofline] latent attention cores of one step: {r['flops']:.4g} "
          f"FLOPs in visible tiles ({3 * parts['attention_pairs']:.4g} in "
          f"visible pairs), {r['least_bytes']:.4g} bytes, "
          f"{r['least_seconds'] * 1e3:.3f} ms at peak (bound by "
          f"{r['bound_by']}); device time {seconds * 1e3:.3f} ms; backend "
          f"{readings.get('attention_backend')!r}", flush=True)
    return 100.0 * r["least_seconds"] / seconds

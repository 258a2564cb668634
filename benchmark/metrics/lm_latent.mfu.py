"""Model FLOPs utilisation of the latent-attention decoder: the step's
model FLOPs (``lib/counts_mla.py``: 3x forward, attention by visible
pairs, routed experts by the assignments held, the prediction module in,
nothing recomputed) times steps per second on the host clock, over one
chip's peak."""
from benchmark.lib import counts_mla, readers_latent, readers_lm


def read(readings, context):
    parts = readers_latent.flops_parts(readings, context)
    if parts is None or "steps_per_s" not in readings:
        return None
    flops = counts_mla.train_flops(parts)
    print("[flops] forward, by part: " + ", ".join(
        f"{k} {v:.4g}" for k, v in parts.items()
    ) + f"; one training step {flops:.4g}", flush=True)
    peak = readers_lm.chip_peaks(context)["flops_per_s"]
    return 100.0 * flops * readings["steps_per_s"] / peak

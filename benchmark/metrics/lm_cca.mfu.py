"""Model FLOPs utilisation of the decoder with compressed convolutional
attention and top-1 experts: the step's model FLOPs (``lib/counts_cca.py``:
3x forward, attention by visible pairs, experts by the assignments held,
a skipped token counts nothing, nothing rebuilt counts) times steps per
second on the host clock, over one chip's peak."""
from benchmark.lib import counts_cca, readers_cca, readers_lm


def read(readings, context):
    parts = readers_cca.flops_parts(readings, context)
    if parts is None or "steps_per_s" not in readings:
        return None
    flops = counts_cca.train_flops(parts)
    print("[flops] forward, by part: " + ", ".join(
        f"{k} {v:.4g}" for k, v in parts.items()
    ) + f"; one training step {flops:.4g}", flush=True)
    peak = readers_lm.chip_peaks(context)["flops_per_s"]
    return 100.0 * flops * readings["steps_per_s"] / peak

"""Model FLOPs utilisation of the decoder with chunk-summary attention:
the step's model FLOPs (``lib/counts_eva.py``: 3x forward, attention by
visible pairs of both key sets, nothing rebuilt counts) times steps per
second on the host clock, over one chip's peak."""
from benchmark.lib import counts_eva, readers_eva, readers_lm


def read(readings, context):
    parts = readers_eva.flops_parts(readings, context)
    if parts is None or "steps_per_s" not in readings:
        return None
    flops = counts_eva.train_flops(parts)
    print("[flops] forward, by part: " + ", ".join(
        f"{k} {v:.4g}" for k, v in parts.items()
    ) + f"; one training step {flops:.4g}", flush=True)
    peak = readers_lm.chip_peaks(context)["flops_per_s"]
    return 100.0 * flops * readings["steps_per_s"] / peak

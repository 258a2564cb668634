"""Model FLOPs utilisation of the decoder under block diffusion: the
step's model FLOPs (``lib/counts_sdar.py``: 3x forward, every one of the
``(1 + S) L`` rows counted, attention by visible pairs, experts by the
assignments held, nothing rebuilt counts) times steps per second on the
host clock, over one chip's peak."""
from benchmark.lib import counts_sdar, readers_lm, readers_sdar


def read(readings, context):
    parts = readers_sdar.flops_parts(readings, context)
    if parts is None or "steps_per_s" not in readings:
        return None
    flops = counts_sdar.train_flops(parts)
    print("[flops] forward, by part: " + ", ".join(
        f"{k} {v:.4g}" for k, v in parts.items()
    ) + f"; one training step {flops:.4g}", flush=True)
    peak = readers_lm.chip_peaks(context)["flops_per_s"]
    return 100.0 * flops * readings["steps_per_s"] / peak

"""Model FLOPs utilisation of the decoder with delta-rule blocks: the
step's model FLOPs (``lib/counts_kda.py``: 3x forward, softmax attention
by visible pairs, the recurrence as written, routed experts by the
assignments held, nothing rebuilt counts) times steps per second on the
host clock, over one chip's peak."""
from benchmark.lib import counts_kda, readers_kda, readers_lm


def read(readings, context):
    parts = readers_kda.flops_parts(readings, context)
    if parts is None or "steps_per_s" not in readings:
        return None
    flops = counts_kda.train_flops(parts)
    print("[flops] forward, by part: " + ", ".join(
        f"{k} {v:.4g}" for k, v in parts.items()
    ) + f"; one training step {flops:.4g}", flush=True)
    peak = readers_lm.chip_peaks(context)["flops_per_s"]
    return 100.0 * flops * readings["steps_per_s"] / peak

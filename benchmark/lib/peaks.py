"""Peaks of one chip, keyed by the exact ``device_kind`` jax reports. A kind
that is not here is an error, never a default: a share of a guessed peak is
worse than none."""

# Google Cloud documentation, "TPU v5e" system architecture page
# (cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16 and HBM2e at
# 819 GB/s. A peak goes in with the first metric that reads it.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add it to benchmark/lib/peaks.py with its "
            "source; a device metric is never computed against a guess."
        ) from None

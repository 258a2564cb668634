"""Analytic FLOPs accounting and MFU (model FLOPs utilization) reporting.

The reference never reports FLOPs — its perf story is env-steps/s alone
(reference: README.md:34-37 qualitative scaling claim). On TPU the actionable
perf question is "how busy is the MXU", so the benchmark reports MFU:
achieved model FLOP/s divided by the chip's peak. FLOPs are counted
analytically from the architecture (convolutions dominate ImpalaNet; the
V-trace scan, optimizer update, and normalization are O(params) or O(T*B)
elementwise and contribute <1% — they are deliberately excluded so the
number is a *model* FLOPs utilization, comparable across implementations).

Convention: a MAC counts as 2 FLOPs. A training step costs 3x the forward
pass (one forward, ~2x forward for the backward's two matmul-shaped products
per layer).
"""

from __future__ import annotations

import math
from typing import Sequence

__all__ = [
    "conv2d_flops",
    "dense_flops",
    "lstm_flops",
    "impala_layer_walk",
    "impala_forward_flops",
    "impala_train_flops",
    "device_peak_flops",
    "TRAIN_FLOPS_MULTIPLIER",
]

# fwd + backward(dL/dx + dL/dW) — each backward product is matmul-shaped with
# the same FLOP count as the forward contraction.
TRAIN_FLOPS_MULTIPLIER = 3


def conv2d_flops(h_out: int, w_out: int, kh: int, kw: int, c_in: int, c_out: int) -> int:
    """FLOPs for one conv2d application on a single image (2 * MACs)."""
    return 2 * h_out * w_out * kh * kw * c_in * c_out


def dense_flops(d_in: int, d_out: int) -> int:
    return 2 * d_in * d_out


def lstm_flops(d_in: int, hidden: int) -> int:
    """FLOPs for one LSTM cell step on one sample: 4 gates, two matmuls each."""
    return 2 * 4 * hidden * (d_in + hidden)


# ImpalaNet architecture defaults — the single source shared by
# impala_layer_walk and impala_forward_flops so the two signatures cannot
# drift (models/impala.py mirrors these).
_IMPALA_DEFAULTS = dict(
    height=84, width=84, in_channels=4, channels=(16, 32, 32),
    hidden_size=256, num_actions=6, use_lstm=False, lstm_size=256,
)


def impala_layer_walk(
    height: int = _IMPALA_DEFAULTS["height"],
    width: int = _IMPALA_DEFAULTS["width"],
    in_channels: int = _IMPALA_DEFAULTS["in_channels"],
    channels: Sequence[int] = _IMPALA_DEFAULTS["channels"],
    hidden_size: int = _IMPALA_DEFAULTS["hidden_size"],
    num_actions: int = _IMPALA_DEFAULTS["num_actions"],
    use_lstm: bool = _IMPALA_DEFAULTS["use_lstm"],
    lstm_size: int = _IMPALA_DEFAULTS["lstm_size"],
):
    """Yield per-layer records for ImpalaNet (models/impala.py):
    ``(name, flops_per_frame, contraction_k, output_lanes_n, out_elems)``.

    The architecture walk :func:`impala_forward_flops` sums. Mirrors the
    model exactly: per ConvSequence one 3x3 conv at the incoming
    resolution, a stride-2 SAME max-pool, then two residual blocks (four
    3x3 convs) at the pooled resolution; 84x84 input pools 84→42→21→11;
    then the FC trunk, optional LSTM, and both heads.

    ``contraction_k`` / ``output_lanes_n`` are the implicit-matmul dims the
    MXU sees (convs: K = kh*kw*c_in, N = c_out).
    """
    h, w, c = height, width, in_channels
    for i, ch in enumerate(channels):
        yield (f"s{i}.conv {c}->{ch} @{h}x{w}",
               conv2d_flops(h, w, 3, 3, c, ch), 9 * c, ch, h * w * ch)
        h, w = math.ceil(h / 2), math.ceil(w / 2)  # SAME pool, stride 2
        for j in range(4):
            yield (f"s{i}.res{j // 2}.conv{j % 2} {ch}->{ch} @{h}x{w}",
                   conv2d_flops(h, w, 3, 3, ch, ch), 9 * ch, ch, h * w * ch)
        c = ch
    d_in = h * w * c
    yield (f"dense {d_in}->{hidden_size}", dense_flops(d_in, hidden_size),
           d_in, hidden_size, hidden_size)
    if use_lstm:
        # 4 gates over [x; h]: one matmul of K = in+hidden, N = 4*hidden.
        yield (f"lstm {hidden_size}+{lstm_size}",
               lstm_flops(hidden_size, lstm_size),
               hidden_size + lstm_size, 4 * lstm_size, lstm_size)
        hidden_size = lstm_size
    yield (f"policy head {hidden_size}->{num_actions}",
           dense_flops(hidden_size, num_actions),
           hidden_size, num_actions, num_actions)
    yield (f"baseline head {hidden_size}->1",
           dense_flops(hidden_size, 1), hidden_size, 1, 1)


def impala_forward_flops(
    height: int = _IMPALA_DEFAULTS["height"],
    width: int = _IMPALA_DEFAULTS["width"],
    in_channels: int = _IMPALA_DEFAULTS["in_channels"],
    channels: Sequence[int] = _IMPALA_DEFAULTS["channels"],
    hidden_size: int = _IMPALA_DEFAULTS["hidden_size"],
    num_actions: int = _IMPALA_DEFAULTS["num_actions"],
    use_lstm: bool = _IMPALA_DEFAULTS["use_lstm"],
    lstm_size: int = _IMPALA_DEFAULTS["lstm_size"],
) -> int:
    """Forward FLOPs per frame for ImpalaNet — sum of the layer walk."""
    return sum(
        rec[1]
        for rec in impala_layer_walk(
            height=height,
            width=width,
            in_channels=in_channels,
            channels=channels,
            hidden_size=hidden_size,
            num_actions=num_actions,
            use_lstm=use_lstm,
            lstm_size=lstm_size,
        )
    )


def impala_train_flops(frames: int, **kw) -> int:
    """Total model FLOPs for one train step consuming ``frames`` frames
    (= (T+1) * B forward frames; the bootstrap frame is real compute)."""
    return TRAIN_FLOPS_MULTIPLIER * frames * impala_forward_flops(**kw)


# Peak dense matmul throughput per chip, bf16, FLOP/s, keyed by the exact
# ``jax.devices()[0].device_kind`` string. Public numbers from
# cloud.google.com/tpu/docs (per-chip; a jax device is one chip on v4+).
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e / Trillium
    "TPU v6e": 918e12,
}


def device_peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s for a jax ``device_kind``. A kind that is not in
    the table is an error, never a default: an MFU over a guessed peak is
    worse than none."""
    try:
        return _PEAK_BF16[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak recorded for device_kind {device_kind!r}; known: "
            f"{sorted(_PEAK_BF16)} (add it to moolib_tpu/utils/flops.py with "
            "its source)"
        ) from None

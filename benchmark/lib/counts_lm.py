"""Operations and least bytes of one training step of a decoder language
model that learns as a token-level policy, counted from its description
(``config["model"]["kwargs"]``) and from what the step itself reports: the
assignments its experts held, and the episode boundaries of its batch.

Conventions, as ``lib/counts.py`` has them: a multiply-accumulate is 2
FLOPs; a training step costs 3x the forward pass; nothing recomputed
counts (a flash backward rebuilds the scores: not counted); elementwise
work (norms, rotary, softmax, the gate, V-trace, the optimizer) is left
out, so the count is the *model's* FLOPs.

What depends on the data is counted from the data: an expert costs what
its assignments cost, and attention what the visible part of the score
matrix costs: pairs (query i, key j) for the model's FLOPs, whole tiles of
the kernel's block size for the kernel's roofline (a tile that holds one
visible pair is computed whole, and one that holds none is skipped).
"""

from __future__ import annotations

import numpy as np

from .counts import TRAIN_FLOPS_MULTIPLIER


def segments(done_column) -> np.ndarray:
    """Running count of ``done``: the episode each position belongs to."""
    return np.cumsum(np.asarray(done_column).astype(np.int64))


def visible_pairs(seg: np.ndarray, window) -> int:
    """Pairs (i, j) with j <= i, one segment, and i - j < window."""
    T = len(seg)
    i = np.arange(T)
    # first position of i's segment (ids never decrease)
    start = np.searchsorted(seg, seg, side="left")
    reach = i - start + 1
    if window is not None:
        reach = np.minimum(reach, window)
    return int(reach.sum())


def visible_tiles(seg: np.ndarray, block: int, window) -> int:
    """Tiles of ``block`` x ``block`` that hold a visible pair, by the
    rule the flash kernels skip by: not above the diagonal, not out of the
    window's reach, segment ranges that meet."""
    T = len(seg)
    block = min(block, T)
    n = T // block
    lo = seg.reshape(n, block).min(axis=1)
    hi = seg.reshape(n, block).max(axis=1)
    count = 0
    for qi in range(n):
        for ki in range(qi + 1):
            if window is not None and (
                ki * block + block - 1 < qi * block - (window - 1)
            ):
                continue
            if lo[ki] <= hi[qi] and hi[ki] >= lo[qi]:
                count += 1
    return count


def forward_flops(model: dict, tokens: int, assignments_held: float,
                  done_column) -> dict:
    """Forward FLOPs of one step over ``tokens`` positions of one packed
    sequence, by part. ``assignments_held``: the step's own counter, summed
    over layers."""
    d, D = model["hidden_size"], model["head_dim"]
    H, Hkv = model["num_heads"], model["num_kv_heads"]
    V, f = model["vocab_size"], model["moe_intermediate_size"]
    layers = model["layers"]
    seg = segments(done_column)
    pairs = tiles = 0
    for layer in layers:
        window = model["attention_kinds"][layer["attention"]]["window"]
        pairs += visible_pairs(seg, window)
        tiles += visible_tiles(seg, model["attention_block"], window)
    block = min(model["attention_block"], tokens)
    per_pair = 2 * 2 * D * H  # q.k and p.v, every query head
    return {
        "projections": len(layers) * tokens * 2 * d * D * (2 * H + 2 * Hkv),
        "router": len(layers) * tokens * 2 * d * model["num_experts"],
        "experts": assignments_held * 3 * 2 * d * f,
        "attention_pairs": pairs * per_pair,
        "attention_tiles": tiles * block * block * per_pair,
        "head": tokens * 2 * d * (V + 1),
    }


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    forward = sum(v for k, v in parts.items() if k != "attention_tiles")
    return TRAIN_FLOPS_MULTIPLIER * forward


def experts_least(model: dict, assignments_held: float, peaks: dict) -> dict:
    """The grouped products of the expert layers over a whole step: FLOPs
    (3x forward) and least bytes. Bytes: every held expert's three
    matrices read forward, read backward and their gradient written, at
    the computing width (2 B), and a row of every assignment's input,
    hidden pair, product and output written forward and read backward."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    count = (model["experts_held"] or [0, model["num_experts"]])[1]
    weights = len(model["layers"]) * count * 3 * d * f
    flops = TRAIN_FLOPS_MULTIPLIER * assignments_held * 3 * 2 * d * f
    least_bytes = 3 * 2 * weights + 2 * 2 * assignments_held * (2 * d + 3 * f)
    return _least(flops, least_bytes, peaks)


def attention_least(model: dict, parts: dict, tokens: int,
                    peaks: dict) -> dict:
    """The attention cores over a whole step: FLOPs of the visible tiles
    (3x forward) and least bytes (q, k, v and the output, read or written
    once forward and once backward with their gradients, 2 B)."""
    D = model["head_dim"]
    heads = 2 * model["num_heads"] + 2 * model["num_kv_heads"]
    least_bytes = len(model["layers"]) * tokens * D * heads * 2 * 3
    return _least(
        TRAIN_FLOPS_MULTIPLIER * parts["attention_tiles"], least_bytes, peaks
    )


def parameters(model: dict) -> int:
    """Parameters held: a layer's projections, router, held experts and
    two norm scales; embedding, head, final norm and the value unit."""
    d, D, V = model["hidden_size"], model["head_dim"], model["vocab_size"]
    heads = 2 * model["num_heads"] + 2 * model["num_kv_heads"]
    count = (model["experts_held"] or [0, model["num_experts"]])[1]
    layer = (d * D * heads + d * model["num_experts"]
             + count * 3 * d * model["moe_intermediate_size"] + 2 * d)
    return len(model["layers"]) * layer + 2 * V * d + d + d + 1


def step_least(model: dict, parts: dict, tokens: int,
               assignments_held: float, peaks: dict) -> dict:
    """One whole training step: the model's FLOPs (:func:`train_flops`)
    and least bytes by ``lib/counts.py``'s rule, every activation written
    forward and read backward at the computing width (2 B) and every
    parameter read once at 4 B. Activations: a position's four rows of
    the hidden width a layer (two norms, two residual sums), its q, k, v
    and attention output, the final norm's row; an assignment's rows as
    :func:`experts_least` counts them; the float32 logits (4 B, written
    and read) and the float32 behaviour logits (read)."""
    d, D, V = model["hidden_size"], model["head_dim"], model["vocab_size"]
    f = model["moe_intermediate_size"]
    heads = 2 * model["num_heads"] + 2 * model["num_kv_heads"]
    per_position = len(model["layers"]) * (4 * d + D * heads) + d
    least_bytes = (
        2 * 2 * (tokens * per_position + assignments_held * (2 * d + 3 * f))
        + tokens * V * 4 * 3
        + parameters(model) * 4
    )
    return _least(train_flops(parts), least_bytes, peaks)


def _least(flops: float, least_bytes: float, peaks: dict) -> dict:
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = least_bytes / peaks["hbm_bytes_per_s"]
    return {
        "flops": flops, "least_bytes": least_bytes,
        "least_seconds": max(t_flops, t_bytes),
        "bound_by": "flops" if t_flops >= t_bytes else "bytes",
    }
